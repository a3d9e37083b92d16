"""Fused harmonic fold kernel (ops/pallas_sumspec.py):
interpret-mode bit-parity against the XLA path (ops/harmonic.py),
end-to-end goldens against the CPU oracle at the existing tolerances,
the fold's gate (default on a TPU, ERP_PALLAS_SUMSPEC=1 elsewhere), its
counter and cache key, the ERP_PRECISION contract, layout pinning (zero
recompiles across dispatch windows; the v5e compile of the pinned step
is in tests/test_tpu_compile.py),
and named-scope attribution (the kernel's instructions must carry
erp.sumspec)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from boinc_app_eah_brp_tpu.io.checkpoint import empty_candidates
from boinc_app_eah_brp_tpu.models import (
    SearchGeometry,
    run_bank,
)
from boinc_app_eah_brp_tpu.models.search import (
    bank_step_layouts,
    erp_precision,
    make_bank_step,
    state_to_natural,
    step_cache_key,
    use_pallas_sumspec,
)
from boinc_app_eah_brp_tpu.ops.harmonic import harmonic_sumspec
from boinc_app_eah_brp_tpu.ops.pallas_sumspec import (
    sumspec_applicable,
    sumspec_pallas_batch,
)
from boinc_app_eah_brp_tpu.oracle import (
    DerivedParams,
    SearchConfig,
    base_thresholds,
    finalize_candidates,
    run_search_oracle,
    update_toplist_from_maxima,
)
from boinc_app_eah_brp_tpu.runtime import devicecost, metrics
from fixtures import one_bank_step, small_bank, synthetic_timeseries

# --- gating ------------------------------------------------------------------


def test_gates(monkeypatch):
    """The fold is the step's harmonic sum on a TPU by default; on the CPU
    only ERP_PALLAS_SUMSPEC=1 forces it (interpret mode)."""
    assert sumspec_applicable(240, 3800)
    monkeypatch.delenv("ERP_PALLAS_SUMSPEC", raising=False)
    geom = _tiny_geom()
    assert not use_pallas_sumspec(geom)  # the CPU keeps the XLA sum
    monkeypatch.setenv("ERP_PALLAS_SUMSPEC", "1")
    assert use_pallas_sumspec(geom)
    monkeypatch.delenv("ERP_PALLAS_SUMSPEC")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert use_pallas_sumspec(geom)


def test_step_cache_key_parts_fold_from_xla_rung(monkeypatch):
    """A fold-built step and the ladder's XLA rung never share a resident
    executable, and the forced gate moves the key on the CPU."""
    monkeypatch.delenv("ERP_PALLAS_SUMSPEC", raising=False)
    monkeypatch.delenv("ERP_PALLAS_RESIDENT", raising=False)
    geom = _tiny_geom()
    k_xla = step_cache_key(geom, 4, False, True)
    monkeypatch.setenv("ERP_PALLAS_SUMSPEC", "1")
    k_fold = step_cache_key(geom, 4, False, True)
    assert k_fold != k_xla
    assert step_cache_key(geom, 4, False, False) != k_fold


def test_run_bank_counts_sumspec_templates(monkeypatch):
    """search.templates_sumspec counts the templates dispatched through a
    fold-built step: all of them when the fold is forced, none on the
    ladder's XLA rung (``allow_pallas=False``), with the same (M, T)."""
    monkeypatch.setenv("ERP_PALLAS_SUMSPEC", "1")
    monkeypatch.delenv("ERP_PALLAS_RESIDENT", raising=False)
    n = 4096
    ts = synthetic_timeseries(
        n, f_signal=33.0, P_orb=2.2, tau=0.04, psi0=1.2, amp=7.0
    )
    geom = _tiny_geom(n)
    bank = small_bank(P_true=2.2, tau_true=0.04, psi_true=1.2)
    assert make_bank_step(geom, 3).fused
    assert not make_bank_step(geom, 3, allow_pallas=False).fused

    def counts():
        c = metrics.snapshot()["counters"]
        return tuple(
            (c.get(k) or {}).get("value", 0)
            for k in ("search.templates", "search.templates_sumspec")
        )

    n_t = len(bank.P)
    assert metrics.configure(force=True)
    try:
        M1, T1 = run_bank(ts, bank.P, bank.tau, bank.psi0, geom, batch_size=3)
        assert counts() == (n_t, n_t)
        M0, T0 = run_bank(
            ts, bank.P, bank.tau, bank.psi0, geom, batch_size=3,
            allow_pallas=False,
        )
        assert counts() == (2 * n_t, n_t)
    finally:
        metrics.finish(0)
    np.testing.assert_array_equal(np.asarray(M1), np.asarray(M0))
    np.testing.assert_array_equal(np.asarray(T1), np.asarray(T0))


def test_kernel_is_registered_stage():
    """The fold kernel attributes to its own erp.* stage and collapses
    into the harmonic-sum ledger bucket (runtime/devicecost.py)."""
    assert devicecost.STAGES["sumspec"] == "harmonic-sum"
    assert devicecost.ledger_stage("sumspec") == "harmonic-sum"


# --- ERP_PRECISION scaffold --------------------------------------------------


def test_precision_default_is_f32(monkeypatch):
    monkeypatch.delenv("ERP_PRECISION", raising=False)
    assert erp_precision() == "f32"
    monkeypatch.setenv("ERP_PRECISION", "f32")
    assert erp_precision() == "f32"


def test_precision_bf16_raises_not_implemented(monkeypatch):
    """bf16 is reserved scaffolding (ROADMAP item 2): requesting it must
    fail loudly at step CONSTRUCTION with a clear message, not mid-run."""
    monkeypatch.setenv("ERP_PRECISION", "bf16")
    with pytest.raises(NotImplementedError, match="bf16"):
        erp_precision()
    with pytest.raises(NotImplementedError, match="ROADMAP.*f32"):
        make_bank_step(_tiny_geom(), batch_size=2)


def test_precision_rejects_unknown_mode(monkeypatch):
    monkeypatch.setenv("ERP_PRECISION", "fp8")
    with pytest.raises(ValueError, match="ERP_PRECISION"):
        erp_precision()


# --- kernel bit-parity vs the XLA reference ----------------------------------


@pytest.mark.parametrize(
    "window_2,fund_hi,harm_hi,L",
    [
        (50, 240, 3800, 4096),  # one 8-row tile, production-like ratios
        (16, 100, 1600, 2048),  # fund_hi not a multiple of anything nice
        (8, 600, 9000, 8192),  # whole chunks past the mask's edge
        (0, 33, 513, 1024),  # harm_hi just past a 16q+r boundary
        (0, 1100, 17000, 20000),  # harm_hi inside a chunk (q 1062, t 6)
        (0, 1100, 16896, 20000),  # harm_hi at a chunk boundary (16*16*66)
        (0, 2145, 34328, 65537),  # refdefault_p1's fund_hi : harm_hi : L
        (0, 20000, 330000, 340000),  # two row tiles a chunk, carried wrap
    ],
)
def test_bit_parity_with_xla_reference(window_2, fund_hi, harm_hi, L):
    """Fused fold == ops/harmonic.py state-form output, bit for bit:
    identical adds in identical order, identical run-max association,
    across the chunks' halos and the row tiles' carried wrap."""
    rng = np.random.default_rng(11)
    ps = rng.exponential(1.0, size=(2, L)).astype(np.float32)
    kw = dict(window_2=window_2, fund_hi=fund_hi, harm_hi=harm_hi)
    want = jax.vmap(lambda p: harmonic_sumspec(p, natural=False, **kw))(
        jnp.asarray(ps)
    )
    got = sumspec_pallas_batch(jnp.asarray(ps), interpret=True, **kw)
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _tiny_geom(n=4096):
    cfg = SearchConfig(window=200)
    derived = DerivedParams.derive(n, 500.0, cfg)
    return SearchGeometry.from_derived(derived, max_slope=0.5, lut_step=0.05)


def test_integrated_batch_step_matches_xla_step(monkeypatch):
    """ERP_PALLAS_SUMSPEC=1: the full bank step (resample -> packed FFT
    -> fused fold -> merge) produces the identical (M, T) state as the
    production XLA step."""
    from boinc_app_eah_brp_tpu.models.search import (
        prepare_ts,
        template_params_host,
    )

    n = 1 << 13
    ts = synthetic_timeseries(
        n, f_signal=33.0, P_orb=400.0, tau=0.1, psi0=1.2, amp=7.0
    )
    geom = _tiny_geom(n)
    params = [
        template_params_host(P, tau, psi, geom.dt)
        for P, tau, psi in [(1000.0, 0.0, 0.0), (400.0, 0.1, 1.2)]
    ]
    ts_args = prepare_ts(geom, ts)

    monkeypatch.delenv("ERP_PALLAS_SUMSPEC", raising=False)
    M1, T1 = one_bank_step(geom, ts_args, params)
    monkeypatch.setenv("ERP_PALLAS_SUMSPEC", "1")
    assert use_pallas_sumspec(geom)
    M2, T2 = one_bank_step(geom, ts_args, params)

    np.testing.assert_array_equal(M1, M2)
    np.testing.assert_array_equal(T1, T2)


# --- golden vs the CPU oracle ------------------------------------------------


def test_fused_bank_matches_sequential_oracle(monkeypatch):
    """Fused path end to end == the sequential CPU oracle: same
    candidates from the same workunit + bank, at the existing golden
    tolerances (exact except FFT-backend rounding on power)."""
    monkeypatch.setenv("ERP_PALLAS_SUMSPEC", "1")
    n = 4096
    ts = synthetic_timeseries(
        n, f_signal=33.0, P_orb=2.2, tau=0.04, psi0=1.2, amp=7.0
    )
    bank = small_bank(P_true=2.2, tau_true=0.04, psi_true=1.2)
    cfg = SearchConfig(window=200)
    derived = DerivedParams.derive(n, 500.0, cfg)

    seq = run_search_oracle(ts, bank, derived, cfg)
    out_seq = finalize_candidates(seq, derived.t_obs)

    geom = SearchGeometry.from_derived(derived, max_slope=0.5, lut_step=0.05)
    assert use_pallas_sumspec(geom)
    M, T = run_bank(ts, bank.P, bank.tau, bank.psi0, geom, batch_size=3)
    base_thr = base_thresholds(cfg.fA, derived.fft_size)
    batch_cands = update_toplist_from_maxima(
        empty_candidates(),
        state_to_natural(M, geom),
        state_to_natural(T, geom),
        bank.P,
        bank.tau,
        bank.psi0,
        base_thr,
        derived.window_2,
    )
    out_bat = finalize_candidates(batch_cands, derived.t_obs)

    assert len(out_bat) == len(out_seq)
    np.testing.assert_array_equal(out_bat["f0"], out_seq["f0"])
    np.testing.assert_array_equal(out_bat["n_harm"], out_seq["n_harm"])
    # CPU(numpy fft) vs XLA fft: powers agree to FFT tolerance
    np.testing.assert_allclose(out_bat["power"], out_seq["power"], rtol=2e-4)
    np.testing.assert_array_equal(out_bat["P_b"], out_seq["P_b"])
    np.testing.assert_array_equal(out_bat["tau"], out_seq["tau"])


# --- layout pinning ----------------------------------------------------------


def test_bank_step_layouts_match_step_signature():
    """The explicit layout pytrees must mirror make_bank_step's operand
    and result trees exactly — a drifted signature fails here before it
    fails as a cryptic jit tree mismatch on TPU."""
    geom = _tiny_geom()
    dev = jax.devices()[0]
    in_sh, out_sh = bank_step_layouts(geom, with_health=False, device=dev)
    # (ts_args, btau, bomega, bpsi0, bs0, t_offset, n_total, M, T)
    assert len(in_sh) == 9
    assert len(in_sh[0]) == (2 if geom.parity_split else 1)
    assert len(out_sh) == 2
    in_h, out_h = bank_step_layouts(geom, with_health=True, device=dev)
    assert len(out_h) == 3
    # donated operands (M, T at positions 7, 8) carry the same layout as
    # the step results they alias into
    assert in_sh[7] == out_sh[0] and in_sh[8] == out_sh[1]


def test_zero_recompiles_across_dispatch_windows(monkeypatch):
    """One bank-step executable serves every dispatch window: sliding
    t_offset over the bank-resident parameters must hit the same jit
    cache entry (the layout-pinning contract; watched through the
    jax.monitoring recompile counter)."""
    monkeypatch.setenv("ERP_PALLAS_SUMSPEC", "1")
    from boinc_app_eah_brp_tpu.models.search import (
        bank_params_host,
        init_state,
        prepare_ts,
        upload_bank,
    )

    n = 4096
    ts = synthetic_timeseries(n, f_signal=33.0, P_orb=2.2, tau=0.04, psi0=1.2)
    geom = _tiny_geom(n)
    bank = small_bank()
    params = bank_params_host(bank.P, bank.tau, bank.psi0, geom.dt)
    n_total = len(params[0])
    bparams = upload_bank(params, batch_size=2)
    ts_args = prepare_ts(geom, ts)
    M, T = init_state(geom)

    assert metrics.configure(force=True)
    try:
        step = make_bank_step(geom, batch_size=2)
        M, T = step(
            ts_args, *bparams, jnp.int32(0), jnp.int32(n_total), M, T
        )
        jax.block_until_ready((M, T))

        def recompiles():
            snap = metrics.snapshot()
            row = snap["counters"].get("jax.recompiles") or {}
            return row.get("value", 0)

        before = recompiles()
        for off in (2, 4):  # two further dispatch windows
            M, T = step(
                ts_args, *bparams, jnp.int32(off), jnp.int32(n_total), M, T
            )
        jax.block_until_ready((M, T))
        assert recompiles() == before
    finally:
        metrics.finish(0)


def test_run_bank_pallas_fallback_is_byte_identical(monkeypatch):
    """Two injected fused-kernel failures mid-run: the degradation
    ladder (runtime/resilience.py) disables Pallas and the completed
    run's (M, T) is byte-identical to a clean XLA run — the `make chaos`
    byte-identity property, unit-sized."""
    import boinc_app_eah_brp_tpu.models.search as search
    from boinc_app_eah_brp_tpu.runtime import resilience

    n = 4096
    ts = synthetic_timeseries(
        n, f_signal=33.0, P_orb=2.2, tau=0.04, psi0=1.2, amp=7.0
    )
    geom = _tiny_geom(n)
    bank = small_bank(P_true=2.2, tau_true=0.04, psi_true=1.2)

    monkeypatch.delenv("ERP_PALLAS_SUMSPEC", raising=False)
    M_ref, T_ref = run_bank(ts, bank.P, bank.tau, bank.psi0, geom, batch_size=3)

    monkeypatch.setenv("ERP_PALLAS_SUMSPEC", "1")
    monkeypatch.setenv("ERP_RETRY_BUDGET", "4")
    monkeypatch.setenv("ERP_RETRY_BASE_S", "0")
    monkeypatch.setenv("ERP_RETRY_MAX_S", "0")
    resilience.begin_run()

    real = search.make_bank_step

    def flaky(geom_, batch_size, with_health=False, allow_pallas=True):
        if allow_pallas and search.use_pallas_sumspec(geom_):
            def boom(*a, **k):
                raise RuntimeError("UNAVAILABLE: injected Mosaic failure")

            return boom
        return real(
            geom_, batch_size, with_health=with_health,
            allow_pallas=allow_pallas,
        )

    monkeypatch.setattr(search, "make_bank_step", flaky)
    try:
        M, T = run_bank(ts, bank.P, bank.tau, bank.psi0, geom, batch_size=3)
    finally:
        resilience._run_policy = None  # don't leak spent budget
    np.testing.assert_array_equal(np.asarray(M), np.asarray(M_ref))
    np.testing.assert_array_equal(np.asarray(T), np.asarray(T_ref))


# --- named-scope attribution -------------------------------------------------


def test_fused_bytes_attribute_to_sumspec_stage(monkeypatch):
    """The fold kernel's instructions in the dispatched bank step's
    OPTIMIZED module carry its own erp.sumspec scope — not an unscoped
    remainder the trace would book as ``step.other`` — and the stage
    collapses into the harmonic-sum bucket."""
    monkeypatch.setenv("ERP_PALLAS_SUMSPEC", "1")
    from boinc_app_eah_brp_tpu.models.search import (
        bank_params_host,
        init_state,
        prepare_ts,
        upload_bank,
    )

    geom = _tiny_geom()
    bank = small_bank()
    bparams = upload_bank(
        bank_params_host(bank.P, bank.tau, bank.psi0, geom.dt), 2
    )
    ts_args = prepare_ts(geom, synthetic_timeseries(4096))
    txt = (
        make_bank_step(geom, 2)
        .lower(ts_args, *bparams, jnp.int32(0), jnp.int32(len(bank.P)),
               *init_state(geom))
        .compile()
        .as_text()
    )
    scopes = devicecost.hlo_op_scopes(txt)
    sumspec = [
        name for name, op in scopes.items()
        if devicecost.stage_of_op_name(op) == "sumspec"
    ]
    assert sumspec, sorted(set(scopes.values()))[:20]
    assert devicecost.ledger_stage("sumspec") == "harmonic-sum"
