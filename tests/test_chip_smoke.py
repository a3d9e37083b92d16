"""chip_smoke.py at a tiny size on the CPU backend: every phase's control
flow and checks (the Pallas arm in interpret mode), the four-chip phase on
the virtual mesh, and main()'s refusal to report anything off a TPU."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(autouse=True)
def _result_date(monkeypatch):
    monkeypatch.setenv("ERP_RESULT_DATE", chip_smoke.RESULT_DATE)


def _ctx(tmp_path, shape=chip_smoke.TINY):
    ctx = chip_smoke.Ctx(workdir=str(tmp_path), shape=shape)
    chip_smoke.phase_device(ctx, require_tpu=False)
    chip_smoke.make_workunit(ctx)
    return ctx


def test_single_chip_phases_at_tiny_size(tmp_path):
    ctx = _ctx(tmp_path)
    chip_smoke.run_phases(ctx, [
        ("main", chip_smoke.phase_main),
        ("whiten", chip_smoke.phase_whiten),
        ("steady", chip_smoke.phase_steady),
        ("oracle", chip_smoke.phase_oracle),
        ("profile", chip_smoke.phase_profile),
        ("served", chip_smoke.phase_served),
    ])
    assert ctx.info["oracle_recall"] == 1.0
    assert "no device plane" in ctx.info["profile_warning"]
    for key in ("main_compile_s", "whitening_s", "steady_templates_per_s"):
        assert ctx.info[key] > 0


def test_pallas_phase_in_interpret_mode(tmp_path):
    """The Pallas kernels' contracts need production-like orbit slopes
    (the tiny fixture bank is far steeper), so this arm runs on a
    shallow bank."""
    flat = chip_smoke.dataclasses.replace(
        chip_smoke.TINY, P_orb=500.0, tau=0.2, P_range=(400.0, 600.0),
        tau_max=0.3,
    )
    ctx = _ctx(tmp_path, flat)
    ctx.batch = 4
    chip_smoke.run_phases(ctx, [
        ("whiten", chip_smoke.phase_whiten),
        ("steady", chip_smoke.phase_steady),
        ("pallas", lambda c: chip_smoke.phase_pallas(c, interpret=True)),
    ])
    assert ctx.info["pallas_state_bit_identical"]


@pytest.mark.parametrize("resident", [False, True], ids=["xla", "resident"])
def test_four_chip_phase_on_the_virtual_mesh(tmp_path, monkeypatch, resident):
    """The tiny bank on the XLA resampler; and, with the resident chain
    forced (interpret mode), a shallow bank whose orbits it admits, as a
    TPU runs the shipped one: every template of the mesh's run on it."""
    shape = chip_smoke.TINY
    if resident:
        monkeypatch.setenv("ERP_PALLAS_RESIDENT", "1")
        shape = chip_smoke.dataclasses.replace(
            shape, P_orb=200.0, tau=0.03, P_range=(100.0, 300.0),
            tau_max=0.03,
        )
    ctx = _ctx(tmp_path, shape)
    chip_smoke.phase_four_chips(ctx, n_dev=4)
    n_res = ctx.info["mesh_templates_resident"]
    assert n_res == (shape.n_bank if resident else 0)


def test_a_wrong_top_candidate_fails_the_main_phase(tmp_path):
    ctx = _ctx(tmp_path)
    ctx.shape = chip_smoke.dataclasses.replace(ctx.shape, f_signal=77.0)
    with pytest.raises(chip_smoke.SmokeError, match="top candidate"):
        chip_smoke.phase_main(ctx)


def test_main_fails_without_a_tpu(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "phase_build", lambda ctx: None)
    assert chip_smoke.main(["--workdir", str(tmp_path)]) != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err
    assert '"ok"' not in out
