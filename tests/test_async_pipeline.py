"""Async dispatch pipeline equivalence (models/search.py::run_bank).

The production loop — bank-resident parameters sliced on device, bounded
in-flight dispatch window, donated (M, T) — must be BIT-identical to an
independent oracle (each template's five-level sums on its own, folded
into (M, T) on the host by a strict ``>`` that keeps the first maximum)
for every lookahead K, across early quit mid-window and checkpoint/resume,
on both the whitened and the exact_mean (unwhitened) paths, single-chip
and sharded.  The golden-WU variant runs where the reference fixture
exists; the synthetic problem exercises the same code paths everywhere.
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from boinc_app_eah_brp_tpu.io.templates import read_template_bank
from boinc_app_eah_brp_tpu.models.search import (
    SearchGeometry,
    bank_params_host,
    host_exact_mean_params,
    init_state,
    prepare_ts,
    run_bank,
    template_params_host,
    template_sumspec_fn,
)
from boinc_app_eah_brp_tpu.oracle import DerivedParams, SearchConfig
from fixtures import synthetic_timeseries
from test_parallel import _bigger_bank


@pytest.fixture(scope="module")
def problem():
    n = 2048
    ts = synthetic_timeseries(
        n, f_signal=41.0, P_orb=1.9, tau=0.05, psi0=0.4, amp=6.0
    )
    cfg = SearchConfig(window=100)
    derived = DerivedParams.derive(n, 500.0, cfg)
    geom = SearchGeometry.from_derived(derived, max_slope=0.5, lut_step=0.05)
    return ts, geom


@pytest.fixture(scope="module")
def bank():
    return _bigger_bank(23)  # not batch-divisible -> final partial batch


def legacy_run(ts, bank, geom):
    """The synchronous reference: template by template, the five-level
    sums of ``template_sumspec_fn`` (with the host-exact mean where
    ``geom.exact_mean``), folded into (M, T) on the host with a strict
    ``>`` that keeps the first maximum — the result any batching of the
    bank must reproduce."""
    sums_fn = jax.jit(template_sumspec_fn(geom))
    M, T = (np.array(a) for a in init_state(geom))
    ts_np = np.asarray(ts, dtype=np.float32)
    ts_args = prepare_ts(geom, ts_np)
    for t in range(len(bank.P)):
        p = template_params_host(bank.P[t], bank.tau[t], bank.psi0[t], geom.dt)
        args = [ts_args, *(np.float32(x) for x in p)]
        if geom.exact_mean:
            ns, mn = host_exact_mean_params(ts_np, [p], geom)
            args += [ns[0], mn[0]]
        s = np.asarray(sums_fn(*args))
        better = s > M
        M = np.where(better, s, M)
        T = np.where(better, np.int32(t), T)
    return M, T


def test_bank_params_match_per_template_chain(problem, bank):
    """The vectorized whole-bank derivation is bit-for-bit the scalar
    per-template float32 chain (glibc sinf included)."""
    _, geom = problem
    vec = bank_params_host(bank.P, bank.tau, bank.psi0, geom.dt)
    for t in range(len(bank.P)):
        scalar = template_params_host(
            bank.P[t], bank.tau[t], bank.psi0[t], geom.dt
        )
        for k in range(4):
            assert vec[k][t] == scalar[k], (t, k)


@pytest.mark.parametrize("lookahead", [1, 2, 4])
def test_async_matches_synchronous(problem, bank, lookahead):
    ts, geom = problem
    Mref, Tref = legacy_run(ts, bank, geom)
    M, T = run_bank(
        ts, bank.P, bank.tau, bank.psi0, geom, batch_size=4,
        lookahead=lookahead,
    )
    np.testing.assert_array_equal(np.asarray(M), Mref)
    np.testing.assert_array_equal(np.asarray(T), Tref)


def test_async_exact_mean_matches_synchronous(problem, bank):
    """The prefetch-thread exact_mean feed must not change a bit vs the
    inline host pass."""
    ts, geom = problem
    geom_em = dataclasses.replace(geom, exact_mean=True)
    Mref, Tref = legacy_run(ts, bank, geom_em)
    M, T = run_bank(
        ts, bank.P, bank.tau, bank.psi0, geom_em, batch_size=4, lookahead=2
    )
    np.testing.assert_array_equal(np.asarray(M), Mref)
    np.testing.assert_array_equal(np.asarray(T), Tref)


def test_early_quit_mid_window_and_resume(problem, bank):
    """Quit with dispatches still in flight: the returned state must be
    consistent with exactly `done` templates merged, and resuming from it
    must land bit-identical to an uninterrupted run."""
    ts, geom = problem
    Mref, Tref = legacy_run(ts, bank, geom)

    seen = {}

    def quit_cb(done, total, M, T):
        seen["done"] = done
        return done < 12  # stop after 3 batches, inside a 4-deep window

    Mh, Th = run_bank(
        ts, bank.P, bank.tau, bank.psi0, geom, batch_size=4,
        lookahead=4, progress_cb=quit_cb,
    )
    done = seen["done"]
    assert 0 < done < len(bank.P)

    # the partial state alone must equal a legacy run over [0, done)
    import dataclasses as _dc

    partial_bank = type(bank)(
        bank.P[:done], bank.tau[:done], bank.psi0[:done]
    )
    Mp, Tp = legacy_run(ts, partial_bank, geom)
    np.testing.assert_array_equal(np.asarray(Mh), Mp)
    np.testing.assert_array_equal(np.asarray(Th), Tp)

    # checkpoint/resume round-trip through HOST copies (what a checkpoint
    # stores), then finish from `done`
    M2, T2 = run_bank(
        ts, bank.P, bank.tau, bank.psi0, geom, batch_size=4, lookahead=4,
        state=(jnp.asarray(np.asarray(Mh)), jnp.asarray(np.asarray(Th))),
        start_template=done,
    )
    np.testing.assert_array_equal(np.asarray(M2), Mref)
    np.testing.assert_array_equal(np.asarray(T2), Tref)


def test_progress_cb_state_is_readable_every_batch(problem, bank):
    """The lazy state handles handed to progress_cb must be readable at
    every dispatch (the checkpoint path reads them before the next step
    donates) and carry global template indices."""
    ts, geom = problem
    reads = []

    def cb(done, total, M, T):
        # d2h read BEFORE returning — after return the next dispatch
        # donates these buffers
        reads.append((done, np.asarray(M).copy(), np.asarray(T).copy()))
        return True

    run_bank(
        ts, bank.P, bank.tau, bank.psi0, geom, batch_size=4,
        lookahead=3, progress_cb=cb,
    )
    assert [r[0] for r in reads] == [4, 8, 12, 16, 20, 23]
    # maxima are monotone non-decreasing across dispatches
    for (_, M_a, _), (_, M_b, _) in zip(reads, reads[1:]):
        assert np.all(M_b >= M_a)
    # T carries global indices within the bank
    _, _, T_last = reads[-1]
    assert T_last.max() < len(bank.P)


def test_sharded_async_matches_single_device(problem):
    """The sharded bank-resident loop shares the single-chip feed
    contract: bit-identical (M, T) for any lookahead."""
    if len(jax.devices()) < 4:
        pytest.skip("virtual device mesh unavailable")
    from boinc_app_eah_brp_tpu.parallel import make_mesh, run_bank_sharded

    ts, geom = problem
    bank = _bigger_bank(23)
    Mref, Tref = legacy_run(ts, bank, geom)
    mesh = make_mesh(4)
    for lookahead in (1, 3):
        Ms, Ts = run_bank_sharded(
            ts, bank.P, bank.tau, bank.psi0, geom, mesh,
            per_device_batch=2, lookahead=lookahead,
        )
        np.testing.assert_array_equal(np.asarray(Ms), Mref)
        np.testing.assert_array_equal(np.asarray(Ts), Tref)


def test_golden_wu_async_matches_synchronous(problem, testwu_bank):
    """First 32 templates of the shipped stochastic bank (golden WU's
    own template set) through both formulations, on the synthetic series:
    the skip-gated reference fixture provides the production parameter
    ranges."""
    ts, _ = problem
    full = read_template_bank(testwu_bank)
    bank32 = type(full)(full.P[:32], full.tau[:32], full.psi0[:32])
    cfg = SearchConfig(window=100)
    derived = DerivedParams.derive(len(ts), 500.0, cfg)
    from boinc_app_eah_brp_tpu.models.search import (
        lut_step_for_bank,
        lut_tiles_for_bank,
        max_slope_for_bank,
    )

    geom = SearchGeometry.from_derived(
        derived,
        max_slope=max_slope_for_bank(bank32.P, bank32.tau),
        lut_step=lut_step_for_bank(bank32.P, derived.dt),
        lut_tiles=lut_tiles_for_bank(bank32.P, bank32.psi0, derived.t_obs),
    )
    Mref, Tref = legacy_run(ts, bank32, geom)
    for K in (1, 2, 4):
        M, T = run_bank(
            ts, bank32.P, bank32.tau, bank32.psi0, geom, batch_size=8,
            lookahead=K,
        )
        np.testing.assert_array_equal(np.asarray(M), Mref)
        np.testing.assert_array_equal(np.asarray(T), Tref)
