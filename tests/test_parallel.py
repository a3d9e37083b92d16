"""Sharded search on the virtual 8-device CPU mesh: the shard_map path must
produce exactly the single-device (M, T) state — shard count and padding are
not allowed to change results (the stand-in for BOINC's cross-host
agreement validation, SURVEY.md section 4.4)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from boinc_app_eah_brp_tpu.io.templates import TemplateBank
from boinc_app_eah_brp_tpu.models import SearchGeometry, run_bank
from boinc_app_eah_brp_tpu.oracle import DerivedParams, SearchConfig
from boinc_app_eah_brp_tpu.parallel import make_mesh, run_bank_sharded
from fixtures import small_bank, synthetic_timeseries


def _bigger_bank(n_templates: int) -> TemplateBank:
    """Deterministic bank spanning modulated + null templates."""
    rng = np.random.default_rng(11)
    P = np.concatenate([[1000.0], rng.uniform(1.5, 3.0, n_templates - 1)])
    tau = np.concatenate([[0.0], rng.uniform(0.0, 0.1, n_templates - 1)])
    psi = np.concatenate([[0.0], rng.uniform(0.0, 2 * np.pi, n_templates - 1)])
    return TemplateBank(P, tau, psi)


@pytest.fixture(scope="module")
def problem():
    n = 2048
    ts = synthetic_timeseries(n, f_signal=41.0, P_orb=1.9, tau=0.05, psi0=0.4, amp=6.0)
    cfg = SearchConfig(window=100)
    derived = DerivedParams.derive(n, 500.0, cfg)
    geom = SearchGeometry.from_derived(derived, max_slope=0.5, lut_step=0.05)
    return ts, geom


def test_mesh_defaults_to_all_devices():
    mesh = make_mesh()
    assert mesh.devices.size == len(jax.devices())


@pytest.mark.parametrize("n_dev", [2, 3, 5, 8])
def test_sharded_matches_single_device(problem, n_dev):
    if len(jax.devices()) < n_dev:
        pytest.skip("virtual device mesh unavailable")
    ts, geom = problem
    bank = _bigger_bank(23)  # not divisible by any batch -> exercises padding

    M1, T1 = run_bank(ts, bank.P, bank.tau, bank.psi0, geom, batch_size=4)
    mesh = make_mesh(n_dev)
    Ms, Ts = run_bank_sharded(
        ts, bank.P, bank.tau, bank.psi0, geom, mesh, per_device_batch=2
    )
    np.testing.assert_array_equal(np.asarray(M1), np.asarray(Ms))
    np.testing.assert_array_equal(np.asarray(T1), np.asarray(Ts))


def test_sharded_batch_size_invariance(problem):
    if len(jax.devices()) < 4:
        pytest.skip("virtual device mesh unavailable")
    ts, geom = problem
    bank = _bigger_bank(17)
    mesh = make_mesh(4)
    Ma, Ta = run_bank_sharded(
        ts, bank.P, bank.tau, bank.psi0, geom, mesh, per_device_batch=1
    )
    Mb, Tb = run_bank_sharded(
        ts, bank.P, bank.tau, bank.psi0, geom, mesh, per_device_batch=5
    )
    np.testing.assert_array_equal(np.asarray(Ma), np.asarray(Mb))
    np.testing.assert_array_equal(np.asarray(Ta), np.asarray(Tb))


def test_sharded_resume_and_early_stop(problem):
    if len(jax.devices()) < 2:
        pytest.skip("virtual device mesh unavailable")
    ts, geom = problem
    bank = _bigger_bank(20)
    mesh = make_mesh(2)

    stopped_at = {}

    def stop_after_first(done, total, M, T):
        stopped_at["done"] = done
        return False

    M_half, T_half = run_bank_sharded(
        ts,
        bank.P,
        bank.tau,
        bank.psi0,
        geom,
        mesh,
        per_device_batch=3,
        progress_cb=stop_after_first,
    )
    done = stopped_at["done"]
    assert 0 < done < len(bank)
    M_full, T_full = run_bank_sharded(
        ts,
        bank.P,
        bank.tau,
        bank.psi0,
        geom,
        mesh,
        per_device_batch=3,
        state=(M_half, T_half),
        start_template=done,
    )
    M_ref, T_ref = run_bank(ts, bank.P, bank.tau, bank.psi0, geom, batch_size=6)
    np.testing.assert_array_equal(np.asarray(M_full), np.asarray(M_ref))
    np.testing.assert_array_equal(np.asarray(T_full), np.asarray(T_ref))


def test_sharded_stop_template_matches_truncated_bank(problem):
    """stop_template masks the tail through the traced n_total operand
    (no recompile): the bounded run must equal a run over a bank that
    simply ends at the stop index."""
    if len(jax.devices()) < 2:
        pytest.skip("virtual device mesh unavailable")
    ts, geom = problem
    bank = _bigger_bank(20)
    mesh = make_mesh(2)
    stop = 13
    M_win, T_win = run_bank_sharded(
        ts, bank.P, bank.tau, bank.psi0, geom, mesh,
        per_device_batch=3, stop_template=stop,
    )
    M_ref, T_ref = run_bank(
        ts, bank.P[:stop], bank.tau[:stop], bank.psi0[:stop], geom,
        batch_size=6,
    )
    np.testing.assert_array_equal(np.asarray(M_ref), np.asarray(M_win))
    np.testing.assert_array_equal(np.asarray(T_ref), np.asarray(T_win))


def test_sharded_windows_compose_to_full_bank(problem):
    """Disjoint [start, stop) windows chained through the state operand
    reproduce the whole-bank state exactly — the invariant the multi-host
    shard leases (parallel/elastic.py) rely on."""
    if len(jax.devices()) < 2:
        pytest.skip("virtual device mesh unavailable")
    ts, geom = problem
    bank = _bigger_bank(21)
    mesh = make_mesh(2)
    M_a, T_a = run_bank_sharded(
        ts, bank.P, bank.tau, bank.psi0, geom, mesh,
        per_device_batch=2, stop_template=9,
    )
    M_ab, T_ab = run_bank_sharded(
        ts, bank.P, bank.tau, bank.psi0, geom, mesh,
        per_device_batch=2, state=(M_a, T_a), start_template=9,
    )
    M_ref, T_ref = run_bank(ts, bank.P, bank.tau, bank.psi0, geom, batch_size=4)
    np.testing.assert_array_equal(np.asarray(M_ref), np.asarray(M_ab))
    np.testing.assert_array_equal(np.asarray(T_ref), np.asarray(T_ab))


def test_sharded_exact_mean_matches_single_device(problem):
    """The exact_mean sharded path (host (n_steps, mean) inputs threaded
    through shard_map with their own axis specs, pad slots skipped on
    host) must reproduce the single-device exact_mean state."""
    if len(jax.devices()) < 4:
        pytest.skip("virtual device mesh unavailable")
    import dataclasses

    ts, geom = problem
    geom_em = dataclasses.replace(geom, exact_mean=True)
    bank = _bigger_bank(19)  # pad slots on the last sharded step

    M1, T1 = run_bank(ts, bank.P, bank.tau, bank.psi0, geom_em, batch_size=4)
    mesh = make_mesh(4)
    Ms, Ts = run_bank_sharded(
        ts, bank.P, bank.tau, bank.psi0, geom_em, mesh, per_device_batch=2
    )
    np.testing.assert_array_equal(np.asarray(M1), np.asarray(Ms))
    np.testing.assert_array_equal(np.asarray(T1), np.asarray(Ts))


# --- the mesh on the resident chain ------------------------------------------


@pytest.fixture(scope="module")
def resident_problem():
    """A WU and an 11-template bank (not a multiple of the global batch)
    with orbits the resident chain admits: periods of 100-300 s and delays
    of up to 60 samples.  Deeper delays at this length would let a
    nearest-index tie round one way in the chain's float32 arithmetic and
    the other in XLA's, which moves the padding mean visibly here."""
    from boinc_app_eah_brp_tpu.models.search import (
        lut_step_for_bank,
        max_slope_for_bank,
    )

    n = 4096
    ts = synthetic_timeseries(
        n, f_signal=33.0, P_orb=200.0, tau=0.03, psi0=1.2, amp=7.0
    )
    rng = np.random.default_rng(25)
    P = np.concatenate([[1000.0, 200.0], rng.uniform(100.0, 300.0, 9)])
    tau = np.concatenate([[0.0, 0.03], rng.uniform(0.0, 0.03, 9)])
    psi = np.concatenate([[0.0, 1.2], rng.uniform(0.0, 2 * np.pi, 9)])
    derived = DerivedParams.derive(n, 500.0, SearchConfig(window=200))
    geom = SearchGeometry.from_derived(
        derived,
        max_slope=max_slope_for_bank(P, tau),
        lut_step=lut_step_for_bank(P, derived.dt),
    )
    return ts, TemplateBank(P, tau, psi), geom


@pytest.fixture
def resident(monkeypatch):
    """The resident chain forced on this backend (interpret mode), as a
    TPU runs it by default, with the run's counters recorded."""
    from boinc_app_eah_brp_tpu.runtime import metrics

    monkeypatch.setenv("ERP_PALLAS_RESIDENT", "1")
    assert metrics.configure(force=True)
    yield lambda name: (
        metrics.snapshot()["counters"].get(name) or {}
    ).get("value", 0)
    metrics.finish(0)


@pytest.mark.parametrize(
    "allow_pallas", [True, False], ids=["resident", "xla-rung"]
)
def test_resident_mesh_matches_single_chip_resident(
    resident_problem, resident, allow_pallas
):
    """The mesh's (M, T) are the bits of one chip's ``run_bank`` on the
    resident chain, on the chain and on the ladder's XLA rung (which on
    this backend gives the chain's bits exactly, as
    tests/test_pallas_resample.py shows for one chip); the resident count
    is every template on the chain and none on the rung.  The rung is the
    dispatch loop the ladder runs after a Pallas fallback."""
    from boinc_app_eah_brp_tpu.models.search import use_pallas_resident
    from boinc_app_eah_brp_tpu.parallel import sharded_search

    if len(jax.devices()) < 4:
        pytest.skip("virtual device mesh unavailable")
    ts, bank, geom = resident_problem
    assert use_pallas_resident(geom)
    M1, T1 = run_bank(ts, bank.P, bank.tau, bank.psi0, geom, batch_size=4)
    n1 = resident("search.templates_resident")
    assert n1 == resident("search.templates") == len(bank)
    Ms, Ts = sharded_search._run_bank_sharded_attempt(
        ts, bank.P, bank.tau, bank.psi0, geom, make_mesh(4),
        per_device_batch=2, allow_pallas=allow_pallas,
    )
    np.testing.assert_array_equal(np.asarray(M1), np.asarray(Ms))
    np.testing.assert_array_equal(np.asarray(T1), np.asarray(Ts))
    assert resident("search.templates") == 2 * len(bank)
    assert resident("search.templates_resident") - n1 == (
        len(bank) if allow_pallas else 0
    )


def test_resident_mesh_step_records_its_resampler(resident_problem, resident):
    from boinc_app_eah_brp_tpu.parallel import make_sharded_batch_step

    _, _, geom = resident_problem
    mesh = make_mesh(2)
    assert make_sharded_batch_step(geom, mesh, 2).resident
    assert not make_sharded_batch_step(geom, mesh, 2, allow_pallas=False).resident


def test_mesh_pallas_failure_falls_back_to_xla(
    resident_problem, resident, monkeypatch
):
    """Two planted failures of the resident mesh step: the ladder drops to
    the XLA body, counts ``resilience.pallas_fallback``, and the answer is
    the clean run's."""
    from boinc_app_eah_brp_tpu.parallel import sharded_search
    from boinc_app_eah_brp_tpu.runtime import resilience

    if len(jax.devices()) < 4:
        pytest.skip("virtual device mesh unavailable")
    ts, bank, geom = resident_problem
    mesh = make_mesh(4)
    M0, T0 = run_bank_sharded(
        ts, bank.P, bank.tau, bank.psi0, geom, mesh, per_device_batch=2
    )
    monkeypatch.setenv("ERP_RETRY_BUDGET", "4")
    monkeypatch.setenv("ERP_RETRY_BASE_S", "0")
    monkeypatch.setenv("ERP_RETRY_MAX_S", "0")
    resilience.begin_run()
    real = sharded_search.make_sharded_batch_step

    def flaky(*a, **k):
        step = real(*a, **k)
        if not step.resident:
            return step

        def boom(*args):
            raise RuntimeError("UNAVAILABLE: injected Mosaic failure")

        boom.resident = True
        return boom

    monkeypatch.setattr(sharded_search, "make_sharded_batch_step", flaky)
    try:
        M1, T1 = run_bank_sharded(
            ts, bank.P, bank.tau, bank.psi0, geom, mesh, per_device_batch=2
        )
    finally:
        resilience._run_policy = None  # don't leak spent budget
    assert resident("resilience.pallas_fallback") == 1
    np.testing.assert_array_equal(np.asarray(M0), np.asarray(M1))
    np.testing.assert_array_equal(np.asarray(T0), np.asarray(T1))
