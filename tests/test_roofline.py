"""MFU/roofline accounting (runtime/roofline.py)."""

import numpy as np
import pytest

from boinc_app_eah_brp_tpu.runtime.roofline import (
    pipeline_costs,
    roofline_report,
)

# production geometry (2^22-sample WU, padding 3.0, f0 400)
NS, NU, FUND, HARM = 12_582_912, 4_194_304, 329_551, 5_272_824


def test_stage_costs_positive_and_fft_dominant():
    costs = pipeline_costs(NS, NU, FUND, HARM)
    names = [c.name for c in costs]
    assert names == [
        "resample_split", "rfft_packed+power", "harmonic_sum", "merge(M,T)"
    ]
    for c in costs:
        assert c.hbm_bytes > 0
    fft = costs[1]
    assert fft.matmul_flops > 1e9  # the only MXU stage
    # the packed cascade's matmul FLOPs follow the live plan
    from boinc_app_eah_brp_tpu.ops.fft import fft_plan

    plan = fft_plan(NS // 2)
    assert fft.matmul_flops == 8.0 * (NS // 2) * sum(plan)


def test_report_fields_and_bounds():
    r = roofline_report(NS, NU, FUND, HARM, chip="v5e")
    assert r["chip"] == "v5e"
    assert r["attainable_templates_per_sec"] > 100
    assert r["model_bound"] in {s["stage"] for s in r["per_template"]}
    assert "mfu" not in r  # no measurement given

    r2 = roofline_report(
        NS, NU, FUND, HARM, chip="v5e", measured_templates_per_sec=30.4
    )
    assert 0.0 < r2["mfu"] < 1.0
    assert 0.0 < r2["hbm_utilization"] < 1.0
    # 30 t/s is far below the model bound: the named bound is the gap
    assert "layout/overhead" in r2["bound"]
    r3 = roofline_report(
        NS, NU, FUND, HARM, chip="v5e",
        measured_templates_per_sec=0.9 * r["attainable_templates_per_sec"],
    )
    assert r3["bound"] == r3["model_bound"]


@pytest.mark.parametrize(
    "platform,kind,gen",
    [("tpu", "TPU v5 lite", "v5e"), ("cpu", "cpu", "cpu"), ("tpu", "TPU v9", None)],
)
def test_chip_generation_by_device_kind(platform, kind, gen):
    """A v5e reports device_kind "TPU v5 lite"; a CPU device is "cpu"; an
    accelerator missing from the peak table raises instead of taking the
    CPU placeholder peaks."""
    from types import SimpleNamespace

    from boinc_app_eah_brp_tpu.runtime.roofline import chip_generation

    dev = SimpleNamespace(platform=platform, device_kind=kind)
    if gen is None:
        with pytest.raises(ValueError, match="TPU v9"):
            chip_generation(dev)
    else:
        assert chip_generation(dev) == gen


def test_chip_generation_of_the_test_backend():
    from boinc_app_eah_brp_tpu.runtime.roofline import chip_generation

    assert chip_generation() == "cpu"


def test_projection_across_generations():
    """The cross-generation projection (BASELINE north star: linear scale
    to v5p-64) lists per-chip attainable rates consistent with the chip
    peaks: v5p has both higher MXU and HBM peaks than v5e, so its
    projected per-chip rate must be strictly higher."""
    r = roofline_report(NS, NU, FUND, HARM, chip="v5e")
    proj = r["projection"]
    assert set(proj) == {"v4", "v5e", "v5p", "v6e"}
    assert (
        proj["v5e"]["attainable_templates_per_sec_per_chip"]
        == r["attainable_templates_per_sec"]
    )
    assert (
        proj["v5p"]["attainable_templates_per_sec_per_chip"]
        > proj["v5e"]["attainable_templates_per_sec_per_chip"]
    )
