"""Fused Pallas resampler (ops/pallas_resample.py): interpret-mode
bit-parity against the XLA path, and the gate that makes the resident
chain the bank step's resampler on TPU where the geometry fits."""

import jax.numpy as jnp
import numpy as np
import pytest

from boinc_app_eah_brp_tpu.models.search import template_params_host
from boinc_app_eah_brp_tpu.ops.pallas_resample import (
    pallas_applicable,
    resample_fftprep_pallas_batch,
)
from boinc_app_eah_brp_tpu.ops.resample import resample_split
from fixtures import one_bank_step, synthetic_timeseries


# production-like slope/LUT bounds (the PALFA bank's pow2-ceil'd values)
MAX_SLOPE = 0.00390625
LUT_STEP = 1.52587890625e-05


def _mk(n, P, tau, psi, padding=1.5):
    ts = synthetic_timeseries(n, f_signal=33.0, P_orb=P, tau=tau, psi0=psi)
    dt = 500e-6
    nsamples = int(padding * n + 0.5)
    nsamples += nsamples % 2  # parity-split needs even padded length
    t32, om, ps0, s0 = template_params_host(P, tau, psi, dt)
    return ts, dt, nsamples, (t32, om, ps0, s0)


def _chain(ev, od, params, **kw):
    """The resident chain over a batch of ``params`` (one
    ``template_params_host`` tuple per template), in interpret mode."""
    tb = tuple(
        jnp.asarray(np.array([p[i] for p in params], dtype=np.float32))
        for i in range(4)
    )
    return resample_fftprep_pallas_batch(
        ev, od, *tb, lut_tiles=1024, interpret=True, **kw
    )


def test_gates():
    assert pallas_applicable(MAX_SLOPE, LUT_STEP, 1024)
    assert not pallas_applicable(0.5, LUT_STEP, 1024)  # select span too wide
    assert not pallas_applicable(MAX_SLOPE, 0.01, 1024)  # LUT drift too fast
    assert not pallas_applicable(MAX_SLOPE, None, 1024)  # exact-sine path


@pytest.mark.parametrize(
    "P,tau,psi",
    [
        (1000.0, 0.0, 0.0),  # null template
        (400.0, 0.12, 1.2),  # slope ~0.0019, inside the production bound
        (500.0, 0.2, 5.9),  # phase near 2pi
    ],
)
def test_bit_parity_with_xla_path(P, tau, psi):
    n = 1 << 14  # 4 kernel blocks per stream
    ts, dt, nsamples, (t32, om, ps0, s0) = _mk(n, P, tau, psi)
    slope = float(tau) * 2 * np.pi / P
    assert slope <= MAX_SLOPE
    ev = jnp.asarray(ts[0::2].copy())
    od = jnp.asarray(ts[1::2].copy())
    kw = dict(
        nsamples=nsamples,
        n_unpadded=n,
        dt=dt,
        max_slope=MAX_SLOPE,
        lut_step=LUT_STEP,
    )
    want_e, want_o = resample_split(
        ev, od, t32, om, ps0, s0, use_lut=True, lut_tiles=1024, **kw
    )
    got_e, got_o = _chain(ev, od, [(t32, om, ps0, s0)], **kw)
    np.testing.assert_array_equal(np.asarray(got_e)[0], np.asarray(want_e))
    np.testing.assert_array_equal(np.asarray(got_o)[0], np.asarray(want_o))


def test_bit_parity_partial_tail_block():
    """half not a multiple of the kernel block: the tail block's padding
    must not corrupt outputs or the trailing-run scan."""
    n = 10000  # half = 5000: one full + one partial block
    ts, dt, nsamples, (t32, om, ps0, s0) = _mk(n, 437.0, 0.15, 2.5)
    ev = jnp.asarray(ts[0::2].copy())
    od = jnp.asarray(ts[1::2].copy())
    kw = dict(
        nsamples=nsamples,
        n_unpadded=n,
        dt=dt,
        max_slope=MAX_SLOPE,
        lut_step=LUT_STEP,
    )
    want = resample_split(
        ev, od, t32, om, ps0, s0, use_lut=True, lut_tiles=1024, **kw
    )
    got = _chain(ev, od, [(t32, om, ps0, s0)], **kw)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g)[0], np.asarray(w))


def test_batched_variant_matches_vmapped_xla():
    """The chain over a batch (one launch, (T, parity, block) grid) ==
    vmapped XLA path, with its mean fill and FFT-prep padding, bit for
    bit."""
    import jax

    n = 1 << 13
    ts, dt, nsamples, _ = _mk(n, 400.0, 0.1, 1.2)
    ev = jnp.asarray(ts[0::2].copy())
    od = jnp.asarray(ts[1::2].copy())
    kw = dict(
        nsamples=nsamples,
        n_unpadded=n,
        dt=dt,
        max_slope=MAX_SLOPE,
        lut_step=LUT_STEP,
    )
    params = [
        template_params_host(P, tau, psi, dt)
        for P, tau, psi in [(1000.0, 0.0, 0.0), (400.0, 0.1, 1.2)]
    ]
    tb = tuple(
        jnp.asarray(np.array([p[i] for p in params], dtype=np.float32))
        for i in range(4)
    )
    pe, po = _chain(ev, od, params, **kw)
    we, wo = jax.vmap(
        lambda a, b, c, d: resample_split(
            ev, od, a, b, c, d, use_lut=True, lut_tiles=1024, **kw
        )
    )(*tb)
    np.testing.assert_array_equal(np.asarray(pe), np.asarray(we))
    np.testing.assert_array_equal(np.asarray(po), np.asarray(wo))


# --- resident resample -> FFT-prep chain -------------------------------------


def _prod_geom(n, padding=None):
    """Production-like geometry (slope/LUT bounds inside the kernel's
    gates) — the resident chain never applies at the steep toy bounds the
    sumspec tests use (max_slope=0.5 fails ``pallas_applicable``)."""
    from boinc_app_eah_brp_tpu.models.search import SearchGeometry
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig

    kw = {} if padding is None else {"padding": padding}
    cfg = SearchConfig(window=200, **kw)
    derived = DerivedParams.derive(n, 500.0, cfg)
    geom = SearchGeometry.from_derived(
        derived, max_slope=MAX_SLOPE, lut_step=LUT_STEP
    )
    return geom, derived, cfg


def _fitted_bank():
    """Templates whose actual slopes tau*2pi/P all sit inside MAX_SLOPE,
    so the kernel's select span covers them (unlike fixtures.small_bank,
    whose short periods are ~70x too steep for the production bound)."""
    from boinc_app_eah_brp_tpu.io.templates import TemplateBank

    P = [1000.0, 400.0, 500.0, 437.0]
    tau = [0.0, 0.12, 0.2, 0.15]
    psi = [0.0, 1.2, 5.9, 2.5]
    for p, t in zip(P, tau):
        assert t * 2 * np.pi / p <= MAX_SLOPE
    return TemplateBank(
        np.asarray(P, dtype=np.float64),
        np.asarray(tau, dtype=np.float64),
        np.asarray(psi, dtype=np.float64),
    )


def test_resident_gates(monkeypatch):
    from boinc_app_eah_brp_tpu.models.search import (
        SearchGeometry,
        resident_defers_renorm,
        use_pallas_resident,
    )
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig

    cfg = SearchConfig(window=200)
    derived = DerivedParams.derive(1 << 13, 500.0, cfg)
    geom_ok = SearchGeometry.from_derived(
        derived, max_slope=MAX_SLOPE, lut_step=LUT_STEP
    )
    geom_steep = SearchGeometry.from_derived(
        derived, max_slope=0.5, lut_step=LUT_STEP
    )
    monkeypatch.delenv("ERP_PALLAS_RESIDENT", raising=False)
    assert not use_pallas_resident(geom_ok)  # CPU backend: XLA by default
    monkeypatch.setenv("ERP_PALLAS_RESIDENT", "1")  # forces it off-TPU
    assert use_pallas_resident(geom_ok)
    assert not use_pallas_resident(geom_steep)  # select span gate
    # the driver defers whitening renorm only when the packed cascade FFT
    # path is active (the one whose renorm the kernel can absorb)
    monkeypatch.delenv("ERP_FORCE_CASCADE", raising=False)
    assert not resident_defers_renorm(geom_ok)  # CPU: native FFT
    monkeypatch.setenv("ERP_FORCE_CASCADE", "1")
    assert resident_defers_renorm(geom_ok)
    monkeypatch.delenv("ERP_PALLAS_RESIDENT", raising=False)
    assert not resident_defers_renorm(geom_ok)  # gate off => no deferral


def test_resident_gate_is_on_by_default_on_tpu(monkeypatch):
    """On a TPU backend the resident chain needs no environment: it is
    the step's resampler wherever the geometry fits, and the XLA
    resampler where it does not (steep orbits, unwhitened exact_mean).
    The CPU backend keeps XLA, and the step's residency key tells the
    two apart."""
    import dataclasses

    import jax

    from boinc_app_eah_brp_tpu.models.search import (
        resident_defers_renorm,
        step_cache_key,
        use_pallas_resident,
    )

    for env in ("ERP_PALLAS_RESIDENT", "ERP_PALLAS_SUMSPEC",
                "ERP_FORCE_CASCADE"):
        monkeypatch.delenv(env, raising=False)
    geom_ok, _, _ = _prod_geom(1 << 13)
    geom_steep = dataclasses.replace(geom_ok, max_slope=0.5)
    geom_exact = dataclasses.replace(geom_ok, exact_mean=True)

    assert not use_pallas_resident(geom_ok)
    assert not resident_defers_renorm(geom_ok)
    k_cpu = step_cache_key(geom_ok, 4, False, True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert use_pallas_resident(geom_ok)
    assert not use_pallas_resident(geom_steep)
    assert not use_pallas_resident(geom_exact)
    # the TPU's packed whitening path hands its renorm to the kernel
    assert resident_defers_renorm(geom_ok)
    k_tpu = step_cache_key(geom_ok, 4, False, True)
    assert k_tpu != k_cpu
    # the ladder's XLA rung keys apart from the resident step
    assert step_cache_key(geom_ok, 4, False, False) != k_tpu


def test_fftprep_is_registered_stage():
    """The finalize pass attributes to its own erp.fftprep scope and
    collapses into the resample ledger bucket (runtime/devicecost.py)."""
    from boinc_app_eah_brp_tpu.runtime import devicecost

    assert devicecost.STAGES["fftprep"] == "resample"
    assert devicecost.ledger_stage("fftprep") == "resample"


@pytest.mark.parametrize("n", [1 << 13, 10000])
def test_resident_chain_matches_two_stage(n):
    """resample_fftprep_pallas_batch == the two-stage XLA form (the
    vmapped resample_split, then its mean fill and tail) bit for bit —
    same head select, same mean fill, same tail — over three
    templates, including the partial-tail-block geometry (n=10000:
    half=5000, one full + one partial raw block against a padded output
    grid)."""
    import jax

    ts, dt, nsamples, _ = _mk(n, 400.0, 0.1, 1.2)
    ev = jnp.asarray(ts[0::2].copy())
    od = jnp.asarray(ts[1::2].copy())
    kw = dict(
        nsamples=nsamples,
        n_unpadded=n,
        dt=dt,
        max_slope=MAX_SLOPE,
        lut_step=LUT_STEP,
    )
    params = [
        template_params_host(P, tau, psi, dt)
        for P, tau, psi in [(1000.0, 0.0, 0.0), (400.0, 0.1, 1.2),
                            (437.0, 0.15, 2.5)]
    ]
    tb = tuple(
        jnp.asarray(np.array([p[i] for p in params], dtype=np.float32))
        for i in range(4)
    )
    we, wo = jax.vmap(
        lambda a, b, c, d: resample_split(
            ev, od, a, b, c, d, use_lut=True, lut_tiles=1024, **kw
        )
    )(*tb)
    ge, go = _chain(ev, od, params, **kw)
    np.testing.assert_array_equal(np.asarray(ge), np.asarray(we))
    np.testing.assert_array_equal(np.asarray(go), np.asarray(wo))


def test_kernel_renorm_fold_matches_prescaled_series():
    """The ``renorm=`` fold on an unscaled series == running the kernel
    on the prescaled series, bit for bit: the elementwise f32 multiply
    commutes through the gather/select ladder, and the mean/edge values
    are computed from the already-multiplied bits on both sides."""
    from boinc_app_eah_brp_tpu.ops.pallas_resample import (
        resample_fftprep_pallas_batch,
    )

    n = 1 << 13
    ts, dt, nsamples, _ = _mk(n, 400.0, 0.1, 1.2)
    r = float(np.sqrt(np.float32(nsamples)))
    ev = np.asarray(ts[0::2], dtype=np.float32)
    od = np.asarray(ts[1::2], dtype=np.float32)
    ev_s = ev * np.float32(r)  # IEEE f32 multiply == the XLA renorm bits
    od_s = od * np.float32(r)
    kw = dict(
        nsamples=nsamples,
        n_unpadded=n,
        dt=dt,
        max_slope=MAX_SLOPE,
        lut_step=LUT_STEP,
        lut_tiles=1024,
        interpret=True,
    )
    params = [
        template_params_host(P, tau, psi, dt)
        for P, tau, psi in [(1000.0, 0.0, 0.0), (400.0, 0.1, 1.2)]
    ]
    tb = tuple(
        jnp.asarray(np.array([p[i] for p in params], dtype=np.float32))
        for i in range(4)
    )
    ge, go = resample_fftprep_pallas_batch(
        jnp.asarray(ev), jnp.asarray(od), *tb, renorm=r, **kw
    )
    we, wo = resample_fftprep_pallas_batch(
        jnp.asarray(ev_s), jnp.asarray(od_s), *tb, renorm=None, **kw
    )
    np.testing.assert_array_equal(np.asarray(ge), np.asarray(we))
    np.testing.assert_array_equal(np.asarray(go), np.asarray(wo))


def test_integrated_resident_step_matches_xla_step(monkeypatch):
    """ERP_PALLAS_RESIDENT=1: the full bank step (resident resample ->
    FFT-prep -> packed FFT -> harmonic sum -> merge) produces the
    identical (M, T) state as the production XLA step."""
    from boinc_app_eah_brp_tpu.models.search import (
        prepare_ts,
        use_pallas_resident,
    )

    n = 1 << 13
    ts = synthetic_timeseries(
        n, f_signal=33.0, P_orb=400.0, tau=0.1, psi0=1.2, amp=7.0
    )
    geom, _, _ = _prod_geom(n, padding=1.5)
    params = [
        template_params_host(P, tau, psi, geom.dt)
        for P, tau, psi in [(1000.0, 0.0, 0.0), (400.0, 0.1, 1.2)]
    ]
    ts_args = prepare_ts(geom, ts)

    monkeypatch.delenv("ERP_PALLAS_RESIDENT", raising=False)
    M1, T1 = one_bank_step(geom, ts_args, params)

    monkeypatch.setenv("ERP_PALLAS_RESIDENT", "1")
    assert use_pallas_resident(geom)
    M2, T2 = one_bank_step(geom, ts_args, params)

    np.testing.assert_array_equal(M1, M2)
    np.testing.assert_array_equal(T1, T2)


def test_step_deferred_renorm_matches_prescaled(monkeypatch):
    """geom.ts_prescaled=False: both consumers of the unscaled series —
    the resident chain's kernel ``renorm=`` fold AND the XLA steps'
    in-step prescale (the degradation ladder's fallback rung) — produce
    the identical (M, T) as the prescaled series through the plain step."""
    import dataclasses

    from boinc_app_eah_brp_tpu.models.search import prepare_ts

    n = 1 << 13
    ts = synthetic_timeseries(
        n, f_signal=33.0, P_orb=400.0, tau=0.1, psi0=1.2, amp=7.0
    )
    geom, _, _ = _prod_geom(n, padding=1.5)
    r = np.float32(np.sqrt(np.float32(geom.nsamples)))
    ts32 = np.asarray(ts, dtype=np.float32)
    ts_scaled = ts32 * r  # the bits whiten_and_zap would have shipped
    params = [
        template_params_host(P, tau, psi, geom.dt)
        for P, tau, psi in [(1000.0, 0.0, 0.0), (400.0, 0.1, 1.2)]
    ]

    monkeypatch.delenv("ERP_PALLAS_RESIDENT", raising=False)
    Mr, Tr = one_bank_step(geom, prepare_ts(geom, ts_scaled), params)

    geom_def = dataclasses.replace(geom, ts_prescaled=False)
    args_def = prepare_ts(geom_def, ts32)
    # XLA step prescales inside the step (fallback-rung semantics)
    M1, T1 = one_bank_step(geom_def, args_def, params)
    np.testing.assert_array_equal(M1, Mr)
    np.testing.assert_array_equal(T1, Tr)

    # resident chain folds the renorm into the kernel gather
    monkeypatch.setenv("ERP_PALLAS_RESIDENT", "1")
    M2, T2 = one_bank_step(geom_def, args_def, params)
    np.testing.assert_array_equal(M2, Mr)
    np.testing.assert_array_equal(T2, Tr)


def test_whiten_defer_renorm_requires_packed_split_path(monkeypatch):
    """defer_renorm off the packed device-split path must raise, not
    silently ship an un-renormalized series into the plain search."""
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig
    from boinc_app_eah_brp_tpu.ops.whiten import whiten_and_zap

    monkeypatch.delenv("ERP_FORCE_CASCADE", raising=False)  # native FFT
    n = 4096
    cfg = SearchConfig(window=200)
    derived = DerivedParams.derive(n, 500.0, cfg)
    ts = synthetic_timeseries(n)
    with pytest.raises(ValueError, match="defer_renorm"):
        whiten_and_zap(
            ts, derived, cfg, np.zeros((0, 2)),
            return_device_split=True, defer_renorm=True,
        )


def test_whiten_defer_renorm_matches_prescaled_bits(monkeypatch):
    """On the packed path, the deferred halves times sqrt(nsamples) (one
    IEEE f32 multiply) == the renormalized halves, bit for bit — the
    contract that lets the kernel fold and ``_samples_to_host`` re-apply
    the scale without perturbing the oracle goldens."""
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig
    from boinc_app_eah_brp_tpu.ops.whiten import whiten_and_zap

    monkeypatch.setenv("ERP_FORCE_CASCADE", "1")  # packed cascade on CPU
    n = 4096
    cfg = SearchConfig(window=200)
    derived = DerivedParams.derive(n, 500.0, cfg)
    ts = synthetic_timeseries(n)
    ev0, od0 = whiten_and_zap(
        ts, derived, cfg, np.zeros((0, 2)), return_device_split=True
    )
    ev1, od1 = whiten_and_zap(
        ts, derived, cfg, np.zeros((0, 2)),
        return_device_split=True, defer_renorm=True,
    )
    r = np.float32(np.sqrt(np.float32(derived.nsamples)))
    np.testing.assert_array_equal(np.asarray(ev1) * r, np.asarray(ev0))
    np.testing.assert_array_equal(np.asarray(od1) * r, np.asarray(od0))


def test_step_cache_key_folds_gates(monkeypatch):
    """Every env consulted during step construction must move the
    residency key: a missing component would let the fleet server serve a
    stale executable across differently-gated WUs (step_cache_key
    docstring names this test)."""
    import dataclasses

    from boinc_app_eah_brp_tpu.models.search import step_cache_key

    geom, _, _ = _prod_geom(1 << 13)
    for env in ("ERP_PALLAS_RESIDENT", "ERP_PALLAS_SUMSPEC",
                "ERP_FORCE_CASCADE"):
        monkeypatch.delenv(env, raising=False)
    k0 = step_cache_key(geom, 4, False, True)
    assert k0 == step_cache_key(geom, 4, False, True)  # stable

    monkeypatch.setenv("ERP_PALLAS_RESIDENT", "1")
    k_res = step_cache_key(geom, 4, False, True)
    assert k_res != k0
    # the fallback rung (allow_pallas=False) keys differently from the
    # gated step even under the same env
    assert step_cache_key(geom, 4, False, False) != k_res

    monkeypatch.setenv("ERP_FORCE_CASCADE", "1")  # flips the FFT path
    k_casc = step_cache_key(geom, 4, False, True)
    assert k_casc != k_res

    # the deferred-renorm flag rides the geometry into the key
    geom_def = dataclasses.replace(geom, ts_prescaled=False)
    assert step_cache_key(geom_def, 4, False, True) != k_casc

    monkeypatch.delenv("ERP_FORCE_CASCADE", raising=False)
    monkeypatch.delenv("ERP_PALLAS_RESIDENT", raising=False)
    assert step_cache_key(geom, 4, False, True) == k0
    monkeypatch.setenv("ERP_PALLAS_SUMSPEC", "1")  # the fold's gate
    assert step_cache_key(geom, 4, False, True) != k0


def test_zero_recompiles_across_dispatch_windows_resident(monkeypatch):
    """One bank-step executable serves every dispatch window with the
    resident chain gated on: sliding t_offset must hit the same jit cache
    entry (jax.monitoring recompile counter)."""
    from boinc_app_eah_brp_tpu.models.search import (
        bank_params_host,
        init_state,
        make_bank_step,
        prepare_ts,
        upload_bank,
        use_pallas_resident,
    )
    from boinc_app_eah_brp_tpu.runtime import metrics

    monkeypatch.setenv("ERP_PALLAS_RESIDENT", "1")
    n = 4096
    ts = synthetic_timeseries(n, f_signal=33.0, P_orb=400.0, tau=0.1, psi0=1.2)
    geom, _, _ = _prod_geom(n)
    assert use_pallas_resident(geom)
    bank = _fitted_bank()
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
        import jax

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


def test_run_bank_resident_fallback_is_byte_identical(monkeypatch):
    """Two injected resident-chain failures mid-run: the degradation
    ladder disables Pallas and the completed run's (M, T) — with a
    DEFERRED whitening renorm in play — is byte-identical to a clean XLA
    run over the prescaled series: the fallback step re-applies the
    renorm itself (geom.ts_prescaled)."""
    import dataclasses

    import boinc_app_eah_brp_tpu.models.search as search
    from boinc_app_eah_brp_tpu.models import run_bank
    from boinc_app_eah_brp_tpu.models.search import (
        SearchGeometry,
        lut_step_for_bank,
        max_slope_for_bank,
    )
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig
    from boinc_app_eah_brp_tpu.ops.pallas_resample import pallas_applicable
    from boinc_app_eah_brp_tpu.runtime import resilience

    n = 4096
    ts = synthetic_timeseries(
        n, f_signal=33.0, P_orb=400.0, tau=0.1, psi0=1.2, amp=7.0
    )
    bank = _fitted_bank()
    cfg = SearchConfig(window=200)
    derived = DerivedParams.derive(n, 500.0, cfg)
    # derive the bounds from the bank, as the driver does — run_bank
    # validates the bank against them
    geom = SearchGeometry.from_derived(
        derived,
        max_slope=max_slope_for_bank(bank.P, bank.tau),
        lut_step=lut_step_for_bank(bank.P, derived.dt),
    )
    assert pallas_applicable(geom.max_slope, geom.lut_step, geom.lut_tiles)
    r = np.float32(np.sqrt(np.float32(geom.nsamples)))
    ts_scaled = np.asarray(ts, dtype=np.float32) * r

    monkeypatch.delenv("ERP_PALLAS_RESIDENT", raising=False)
    M_ref, T_ref = run_bank(
        ts_scaled, bank.P, bank.tau, bank.psi0, geom, batch_size=3
    )

    geom_def = dataclasses.replace(geom, ts_prescaled=False)
    monkeypatch.setenv("ERP_PALLAS_RESIDENT", "1")
    monkeypatch.setenv("ERP_RETRY_BUDGET", "4")
    monkeypatch.setenv("ERP_RETRY_BASE_S", "0")
    monkeypatch.setenv("ERP_RETRY_MAX_S", "0")
    resilience.begin_run()

    real = search.make_bank_step

    def flaky(geom_, batch_size, with_health=False, allow_pallas=True):
        if allow_pallas and search.use_pallas_resident(geom_):
            def boom(*a, **k):
                raise RuntimeError("UNAVAILABLE: injected Mosaic failure")

            return boom
        return real(
            geom_, batch_size, with_health=with_health,
            allow_pallas=allow_pallas,
        )

    monkeypatch.setattr(search, "make_bank_step", flaky)
    try:
        M, T = run_bank(
            np.asarray(ts, dtype=np.float32), bank.P, bank.tau, bank.psi0,
            geom_def, batch_size=3,
        )
    finally:
        resilience._run_policy = None  # don't leak spent budget
    np.testing.assert_array_equal(np.asarray(M), np.asarray(M_ref))
    np.testing.assert_array_equal(np.asarray(T), np.asarray(T_ref))


def test_run_bank_counts_resident_templates(monkeypatch):
    """search.templates_resident counts the templates dispatched through a
    step built on the resident chain (the step says which it was built
    with), and ``run_bank(allow_pallas=False)`` runs the ladder's XLA rung:
    no resident template, and the same (M, T) bits as the chain on this
    backend."""
    from boinc_app_eah_brp_tpu.models.search import (
        SearchGeometry,
        lut_step_for_bank,
        make_bank_step,
        max_slope_for_bank,
        run_bank,
    )
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig
    from boinc_app_eah_brp_tpu.runtime import metrics

    n = 4096
    ts = synthetic_timeseries(
        n, f_signal=33.0, P_orb=400.0, tau=0.1, psi0=1.2, amp=7.0
    )
    bank = _fitted_bank()
    derived = DerivedParams.derive(n, 500.0, SearchConfig(window=200))
    geom = SearchGeometry.from_derived(
        derived,
        max_slope=max_slope_for_bank(bank.P, bank.tau),
        lut_step=lut_step_for_bank(bank.P, derived.dt),
    )
    monkeypatch.setenv("ERP_PALLAS_RESIDENT", "1")
    assert make_bank_step(geom, 3).resident
    assert not make_bank_step(geom, 3, allow_pallas=False).resident

    def counts():
        c = metrics.snapshot()["counters"]
        return tuple(
            (c.get(k) or {}).get("value", 0)
            for k in ("search.templates", "search.templates_resident")
        )

    assert metrics.configure(force=True)
    try:
        M1, T1 = run_bank(ts, bank.P, bank.tau, bank.psi0, geom, batch_size=3)
        assert counts() == (4, 4)
        M0, T0 = run_bank(
            ts, bank.P, bank.tau, bank.psi0, geom, batch_size=3,
            allow_pallas=False,
        )
        assert counts() == (8, 4)
    finally:
        metrics.finish(0)
    np.testing.assert_array_equal(np.asarray(M1), np.asarray(M0))
    np.testing.assert_array_equal(np.asarray(T1), np.asarray(T0))
