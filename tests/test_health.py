"""Numerical-health watchdog (runtime/health.py): env surface, NaN
detection through the dispatch loop, abort semantics, the sentinel drift
probe, and the driver-level HealthError -> RADPUL_EVAL mapping."""

import os
import subprocess
import sys

import numpy as np
import pytest

from boinc_app_eah_brp_tpu.io import write_template_bank, write_workunit
from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig
from boinc_app_eah_brp_tpu.runtime import health, metrics
from boinc_app_eah_brp_tpu.runtime.health import HealthError
from fixtures import small_bank, synthetic_timeseries

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- env surface -----------------------------------------------------------

def test_disabled_by_default(monkeypatch):
    monkeypatch.delenv(health.HEALTH_EVERY_ENV, raising=False)
    assert health.every() == 0
    assert health.watchdog() is None


def test_env_parsing(monkeypatch):
    monkeypatch.setenv(health.HEALTH_EVERY_ENV, "32")
    monkeypatch.setenv(health.HEALTH_ACTION_ENV, "ABORT")
    monkeypatch.setenv(health.HEALTH_TOL_ENV, "0.5")
    monkeypatch.setenv(health.HEALTH_SENTINELS_ENV, "7")
    assert health.every() == 32
    assert health.action() == "abort"
    assert health.tolerance() == 0.5
    assert health.sentinel_count() == 7
    # garbage falls back to safe defaults rather than raising
    monkeypatch.setenv(health.HEALTH_EVERY_ENV, "nope")
    monkeypatch.setenv(health.HEALTH_ACTION_ENV, "explode")
    assert health.every() == 0
    assert health.action() == "warn"


def test_disabled_path_never_imports_jax(tmp_path):
    """ERP_HEALTH_EVERY=0 (the default) must be a true no-op: importing
    the module and taking the disabled branch pulls in no jax."""
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("ERP_HEALTH_EVERY", None)
    r = subprocess.run(
        [
            sys.executable, "-c",
            "import sys\n"
            "from boinc_app_eah_brp_tpu.runtime import health\n"
            "assert health.watchdog() is None\n"
            "assert 'jax' not in sys.modules, 'disabled path imported jax'\n",
        ],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r.returncode == 0, r.stderr


# --- dispatch-loop integration --------------------------------------------

def _search_setup():
    from boinc_app_eah_brp_tpu.models import search as msearch

    ts = synthetic_timeseries(
        4096, f_signal=33.0, P_orb=2.2, tau=0.04, psi0=1.2, amp=7.0
    )
    cfg = SearchConfig(
        f0=250.0, padding=1.0, fA=0.04, window=200, white=False
    )
    derived = DerivedParams.derive(len(ts), 500.0, cfg)
    bank = small_bank(P_true=2.2, tau_true=0.04, psi_true=1.2)
    geom = msearch.SearchGeometry.from_derived(
        derived,
        exact_mean=True,
        max_slope=msearch.max_slope_for_bank(bank.P, bank.tau),
        lut_step=msearch.lut_step_for_bank(bank.P, derived.dt),
        lut_tiles=msearch.lut_tiles_for_bank(
            bank.P, bank.psi0, derived.n_unpadded, derived.dt
        ),
    )
    return ts, bank, geom, derived


def _poison_sumspec(monkeypatch):
    """Make every device power spectrum NaN — the corruption the merge
    would silently drop (NaN > M is False)."""
    import jax.numpy as jnp

    from boinc_app_eah_brp_tpu.models import search as msearch

    real = msearch.template_sumspec_fn

    def poisoned(geom):
        fn = real(geom)

        def wrapper(*a, **k):
            return fn(*a, **k) * jnp.float32("nan")

        return wrapper

    # one chip and the mesh build their step around the same body
    # (msearch.bank_batch_sums), which reads this name when it is built
    monkeypatch.setattr(msearch, "template_sumspec_fn", poisoned)


def test_healthy_run_checks_without_violations(monkeypatch):
    from boinc_app_eah_brp_tpu.models.search import run_bank

    monkeypatch.setenv(health.HEALTH_EVERY_ENV, "1")
    ts, bank, geom, _ = _search_setup()
    metrics.configure(force=True)
    run_bank(ts, bank.P, bank.tau, bank.psi0, geom, batch_size=2)
    snap = metrics.snapshot()
    assert snap["counters"]["health.checks"]["value"] >= 1
    assert (
        snap["counters"].get("health.violations", {}).get("value", 0) == 0
    )
    # the spectrum-max gauge saw a real finite peak
    assert snap["gauges"]["health.spectrum_max"]["value"] > 0


def test_nan_detected_and_counted_in_warn_mode(monkeypatch):
    from boinc_app_eah_brp_tpu.models.search import run_bank

    monkeypatch.setenv(health.HEALTH_EVERY_ENV, "1")
    monkeypatch.setenv(health.HEALTH_ACTION_ENV, "warn")
    _poison_sumspec(monkeypatch)
    ts, bank, geom, _ = _search_setup()
    metrics.configure(force=True)
    # warn mode: the run COMPLETES (matching the old silent behaviour)
    # but the corruption is now loudly counted
    run_bank(ts, bank.P, bank.tau, bank.psi0, geom, batch_size=2)
    snap = metrics.snapshot()
    assert snap["counters"]["health.violations"]["value"] >= 1
    assert snap["counters"]["health.nonfinite"]["value"] > 0


def test_nan_detection_latency_within_cadence(monkeypatch):
    """ERP_HEALTH_EVERY=N: the violation must fire by the first check
    boundary after the poisoned batch — with every=2 and batch=2 that is
    the FIRST batch, long before the end of the bank."""
    from boinc_app_eah_brp_tpu.models import search as msearch

    monkeypatch.setenv(health.HEALTH_EVERY_ENV, "2")
    monkeypatch.setenv(health.HEALTH_ACTION_ENV, "abort")
    _poison_sumspec(monkeypatch)
    ts, bank, geom, _ = _search_setup()
    metrics.configure(force=True)
    seen = []

    def progress(done, total, M, T):
        seen.append(done)
        return True

    with pytest.raises(HealthError):
        msearch.run_bank(
            ts, bank.P, bank.tau, bank.psi0, geom,
            batch_size=2, progress_cb=progress,
        )
    # aborted within the cadence window: at most every + lookahead*batch
    # templates were dispatched before the check tripped
    assert not seen or seen[-1] <= 2 + 2 * 2


def test_abort_mode_raises_health_error(monkeypatch):
    from boinc_app_eah_brp_tpu.models.search import run_bank

    monkeypatch.setenv(health.HEALTH_EVERY_ENV, "1")
    monkeypatch.setenv(health.HEALTH_ACTION_ENV, "abort")
    _poison_sumspec(monkeypatch)
    ts, bank, geom, _ = _search_setup()
    metrics.configure(force=True)
    with pytest.raises(HealthError, match="non-finite"):
        run_bank(ts, bank.P, bank.tau, bank.psi0, geom, batch_size=2)


def test_sharded_loop_checks_health(monkeypatch):
    from boinc_app_eah_brp_tpu.parallel import make_mesh, run_bank_sharded

    monkeypatch.setenv(health.HEALTH_EVERY_ENV, "1")
    ts, bank, geom, _ = _search_setup()
    metrics.configure(force=True)
    run_bank_sharded(
        ts, bank.P, bank.tau, bank.psi0, geom,
        make_mesh(4), per_device_batch=1,
    )
    snap = metrics.snapshot()
    assert snap["counters"]["health.checks"]["value"] >= 1
    assert (
        snap["counters"].get("health.violations", {}).get("value", 0) == 0
    )


def test_sharded_abort_on_nan(monkeypatch):
    from boinc_app_eah_brp_tpu.parallel import make_mesh, run_bank_sharded

    monkeypatch.setenv(health.HEALTH_EVERY_ENV, "1")
    monkeypatch.setenv(health.HEALTH_ACTION_ENV, "abort")
    _poison_sumspec(monkeypatch)
    ts, bank, geom, _ = _search_setup()
    metrics.configure(force=True)
    with pytest.raises(HealthError, match="non-finite"):
        run_bank_sharded(
            ts, bank.P, bank.tau, bank.psi0, geom,
            make_mesh(4), per_device_batch=1,
        )


# --- sentinel drift probe --------------------------------------------------

def test_sentinel_probe_matches_oracle(monkeypatch):
    monkeypatch.setenv(health.HEALTH_EVERY_ENV, "1")
    ts, bank, geom, derived = _search_setup()
    wd = health.watchdog()
    probe = health.SentinelProbe(
        lambda: ts, bank.P, bank.tau, bank.psi0, geom, derived, wd, k=2
    )
    metrics.configure(force=True)
    results = probe.probe("test")
    assert len(results) == 2
    for rec in results:
        assert rec["rel_err"] < health.tolerance(), rec
    assert wd.violations == 0
    # second probe reuses the cached goldens (drift detection, not
    # re-derivation): poison the oracle to prove it is not consulted
    monkeypatch.setattr(
        probe, "_oracle_power",
        lambda *a: pytest.fail("golden cache was bypassed"),
    )
    results2 = probe.probe("test")
    assert all(r["rel_err"] < health.tolerance() for r in results2)


def test_sentinel_probe_detects_drift(monkeypatch):
    monkeypatch.setenv(health.HEALTH_EVERY_ENV, "1")
    monkeypatch.setenv(health.HEALTH_ACTION_ENV, "warn")
    ts, bank, geom, derived = _search_setup()
    metrics.configure(force=True)  # before the probe registers its gauges
    wd = health.watchdog()
    probe = health.SentinelProbe(
        lambda: ts, bank.P, bank.tau, bank.psi0, geom, derived, wd, k=1
    )
    probe.probe("test")  # caches the honest goldens
    assert wd.violations == 0
    # simulate silent device drift: same (k, f0) peak, wrong power
    real_peak = probe._device_peak

    def drifted(t):
        k_h, f0, p = real_peak(t)
        return k_h, f0, p * 2.0

    monkeypatch.setattr(probe, "_device_peak", drifted)
    probe.probe("test")
    assert wd.violations == 1
    snap = metrics.snapshot()
    assert snap["gauges"]["health.sentinel_max_rel_err"]["value"] > 0.5


def test_sentinel_drift_aborts_in_abort_mode(monkeypatch):
    monkeypatch.setenv(health.HEALTH_EVERY_ENV, "1")
    monkeypatch.setenv(health.HEALTH_ACTION_ENV, "abort")
    ts, bank, geom, derived = _search_setup()
    wd = health.watchdog()
    probe = health.SentinelProbe(
        lambda: ts, bank.P, bank.tau, bank.psi0, geom, derived, wd, k=1
    )
    metrics.configure(force=True)
    monkeypatch.setattr(probe, "_device_peak", lambda t: (0, 300, 1e9))
    with pytest.raises(HealthError, match="sentinel"):
        probe.probe("test")


# --- driver-level integration ---------------------------------------------

def test_driver_maps_health_abort_to_radpul_eval(tmp_path, monkeypatch):
    """End to end: injected NaNs under ERP_HEALTH_ACTION=abort fail the
    run with RADPUL_EVAL (validation-failure class) and leave a black-box
    dump recording the violation."""
    import json

    from boinc_app_eah_brp_tpu.runtime import flightrec
    from boinc_app_eah_brp_tpu.runtime.driver import DriverArgs, run_search
    from boinc_app_eah_brp_tpu.runtime.errors import RADPUL_EVAL

    monkeypatch.setenv(health.HEALTH_EVERY_ENV, "1")
    monkeypatch.setenv(health.HEALTH_ACTION_ENV, "abort")
    monkeypatch.delenv("ERP_BLACKBOX", raising=False)
    monkeypatch.setenv("ERP_BLACKBOX_DIR", str(tmp_path))
    _poison_sumspec(monkeypatch)

    ts = synthetic_timeseries(
        4096, f_signal=33.0, P_orb=2.2, tau=0.04, psi0=1.2, amp=7.0
    )
    wu = str(tmp_path / "wu.bin4")
    write_workunit(wu, ts, tsample_us=500.0, scale=1.0)
    bankfile = str(tmp_path / "bank.dat")
    write_template_bank(
        bankfile, small_bank(P_true=2.2, tau_true=0.04, psi_true=1.2)
    )
    args = DriverArgs(
        inputfile=wu,
        outputfile=str(tmp_path / "out.cand"),
        templatebank=bankfile,
        checkpointfile=str(tmp_path / "cp.cpt"),
        window=200,
        batch_size=2,
    )
    try:
        assert run_search(args) == RADPUL_EVAL
    finally:
        flightrec.disarm()
    dumps = list(tmp_path.glob("erp-blackbox-*.json"))
    assert dumps, "health abort left no black-box dump"
    doc = json.load(open(dumps[0]))
    assert flightrec.validate_dump(doc) == []
    assert doc["reason"] == f"exit-code-{RADPUL_EVAL}"
    assert any(
        ev["kind"] == "health-violation" for ev in doc["events"]
    ), [ev["kind"] for ev in doc["events"]]
