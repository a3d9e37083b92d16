"""The BRP search model: per-template pipeline, vmapped batch step, and the
on-device candidate-maxima state.

This is the TPU-first restructuring of the reference's template loop
(``demod_binary.c:1180-1443``). The reference processes one template at a
time — resample kernel(s), FFT, harmonic-summing kernels, then a *host-side*
candidate scan over dirty pages with dynamic thresholds that feed back into
the next template. Here:

* the whole per-template pipeline is one pure function
  ``template -> sumspec maxima`` (float32[5, fund_hi]);
* a batch of templates runs under ``vmap`` in a single ``jit`` — the
  template-bank axis the reference leaves sequential is the main
  parallelism win (SURVEY.md section 2.5);
* instead of toplists + thresholds + dirty pages, the device carries
  ``M[k][j]`` (max summed power per fundamental bin over all templates so
  far) and ``T[k][j]`` (the first template index achieving it). The oracle
  test proves this yields the identical final candidate file; the dynamic
  threshold feedback (``demod_binary.c:1268-1282``) is pure pruning and the
  dirty-page machinery is a host-scan optimization — both are unnecessary
  when selection happens on device.

The merge uses strict ``>`` so earlier templates win ties, matching the
reference's keep-first-seen semantics (``demod_binary.c:1360``).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..oracle.pipeline import DerivedParams
from ..runtime import faultinject, flightrec, metrics, steptime, tracing
from ..runtime import watchdog as hangdog
from ..runtime.devicecost import stage_scope
from ..ops.harmonic import (
    from_natural_order,
    harmonic_sumspec,
    state_width,
    to_natural_order,
)
from ..ops.resample import resample, resample_split
from ..ops.spectrum import power_spectrum, power_spectrum_split


@dataclass(frozen=True)
class SearchGeometry:
    """Static (jit-constant) geometry of one search configuration."""

    nsamples: int
    n_unpadded: int
    fft_size: int
    window_2: int
    fund_hi: int
    harm_hi: int
    dt: float
    use_lut: bool = True
    # bank-wide bound on |d del_t/di| = tau*omega, sizing the resampler's
    # shifted-select window (ops/resample.py). The default covers the shipped
    # PALFA bank (max 0.00145) with 5x headroom; steeper banks must derive
    # their own via max_slope_for_bank().
    max_slope: float = 0.008
    # bank-wide bound on the per-sample LUT-index step 64*omega*dt/2pi,
    # sizing the blocked sine-table lookup (ops/sincos.py). Default covers
    # P_orb >= ~4 s at the production sample time.
    lut_step: float = 1e-3
    # tiled-LUT period count covering the search phase span
    # psi0 + omega*t_obs (ops/sincos.py); short-P banks derive a larger
    # table via lut_tiles_for_bank()
    lut_tiles: int = 1024
    # Replicate the reference's serial-float32 padding mean bit-for-bit by
    # computing (n_steps, mean) on host per template (oracle code path).
    # Matters on UNWHITENED data, where the f32 accumulator saturation
    # (~2e-3 relative) shifts mean-dominated low-bin candidate powers by
    # percent-level; whitened series are exactly zero-mean (bin 0 is
    # zeroed, ops/whiten.py) so the device's pairwise mean agrees to
    # ~1e-8 and the host pass is skipped. The driver sets this to
    # ``not cfg.white`` (demod_binary_resamp_cpu.c:121 semantics).
    exact_mean: bool = False
    # False when whitening deferred its final sqrt(nsamples)
    # renormalization (ops/whiten.py defer_renorm) so the resident
    # resample chain folds the multiply into its gather instead of
    # booking an extra (M, N) HBM pass.  Static: the step must bake the
    # scale into the Pallas kernels (renorm=) or prepend it on the XLA
    # fallback, and the flag rides ``geom`` into step_cache_key so
    # differently-scaled WUs can never share an executable.  The driver
    # flips it via dataclasses.replace after
    # whiten_and_zap(defer_renorm=True).
    ts_prescaled: bool = True

    @property
    def parity_split(self) -> bool:
        """Even lengths -> the parity-split pipeline (split resampler +
        packed half-length FFT) applies; always true for real WUs (4-bit
        packing makes n even and padding preserves it)."""
        return self.n_unpadded % 2 == 0 and self.nsamples % 2 == 0

    @classmethod
    def from_derived(
        cls,
        d: DerivedParams,
        use_lut: bool = True,
        max_slope: float = 0.008,
        lut_step: float = 1e-3,
        exact_mean: bool = False,
        lut_tiles: int = 1024,
    ) -> "SearchGeometry":
        return cls(
            nsamples=d.nsamples,
            n_unpadded=d.n_unpadded,
            fft_size=d.fft_size,
            window_2=d.window_2,
            fund_hi=d.fundamental_idx_hi,
            harm_hi=d.harmonic_idx_hi,
            dt=d.dt,
            use_lut=use_lut,
            max_slope=max_slope,
            lut_step=lut_step,
            exact_mean=exact_mean,
            lut_tiles=lut_tiles,
        )


def _pow2_ceil(x: float) -> float:
    """Round up to a power of two: the bounds are static jit arguments, so
    quantizing them makes the compiled executable (and the persistent
    compilation cache key, tools/create_wisdom.py) stable across similar
    banks instead of unique per bank."""
    import math

    return float(2.0 ** math.ceil(math.log2(x)))


def max_slope_for_bank(P: np.ndarray, tau: np.ndarray, headroom: float = 1.5) -> float:
    """Bank-derived modulation-slope bound for SearchGeometry.max_slope,
    rounded up to a power of two."""
    if len(P) == 0:
        return 0.008
    slope = float(np.max(np.asarray(tau) * (2.0 * np.pi / np.asarray(P))))
    return _pow2_ceil(max(slope * headroom, 1.0 / 1024.0))


def lut_step_for_bank(P: np.ndarray, dt: float, headroom: float = 1.5) -> float:
    """Bank-derived LUT-index-step bound for SearchGeometry.lut_step,
    rounded up to a power of two."""
    if len(P) == 0:
        return 1e-3
    step = 64.0 * float(dt) / float(np.min(np.asarray(P)))
    return _pow2_ceil(max(step * headroom, 1e-6))


def normalize_psi0(psi0: np.ndarray) -> np.ndarray:
    """Reduce initial orbital phases into [0, 2pi) on host, in double.

    The reference accepts arbitrary phase because its LUT wraps indices
    per element (``erp_utilities.cpp:176-209``, modff semantics); the
    blocked no-gather LUT needs a nonnegative monotone unwrapped index, so
    out-of-range psi0 is folded once up front instead.  In-range values
    pass through BIT-IDENTICAL (fmod is exact there), so production banks
    are untouched; folded values describe the same physical orbit, with
    the float32 working phase differing from the reference's unfolded one
    by ulps (documented deviation; device and oracle stay in lockstep by
    both consuming the normalized bank)."""
    psi = np.asarray(psi0, dtype=np.float64)
    out = np.fmod(psi, 2.0 * np.pi)
    out = np.where(out < 0.0, out + 2.0 * np.pi, out)
    return out


def lut_tiles_for_bank(
    P: np.ndarray,
    psi0: np.ndarray,
    n_unpadded: int,
    dt: float,
) -> int:
    """Tiled-LUT size covering this bank's phase span (normalized psi0 +
    omega*t_obs), rounded up to a power of two for jit-cache stability;
    clamped to [1024, ops.sincos.MAX_TILES]."""
    from ..ops.sincos import MAX_TILES

    if len(P) == 0:
        return 1024
    psi_max = float(np.max(normalize_psi0(psi0))) if len(psi0) else 2 * np.pi
    span = psi_max / (2.0 * np.pi) + n_unpadded * float(dt) / float(np.min(P))
    tiles = 1024
    while tiles - 2 < span and tiles < MAX_TILES:
        tiles *= 2
    return tiles


def validate_bank_bounds(
    geom: SearchGeometry,
    bank_P: np.ndarray,
    bank_tau: np.ndarray,
    bank_psi0: np.ndarray | None = None,
) -> None:
    """Check the bank against the geometry's static select-window bounds.

    Both search paths (``run_bank`` and ``parallel.run_bank_sharded``) call
    this: exceeding a bound would make the blocked no-gather formulations
    (``ops/resample.py``, ``ops/sincos.py``) silently select wrong samples.
    """
    if not len(bank_P):
        return
    P = np.asarray(bank_P)
    bank_slope = float(np.max(np.asarray(bank_tau) * (2.0 * np.pi / P)))
    if bank_slope > geom.max_slope:
        raise ValueError(
            f"template bank modulation slope {bank_slope:.3g} exceeds "
            f"geometry bound {geom.max_slope:.3g}; rebuild SearchGeometry "
            "with max_slope_for_bank(P, tau)"
        )
    if geom.use_lut:
        bank_lut_step = 64.0 * geom.dt / float(np.min(P))
        if bank_lut_step > geom.lut_step:
            raise ValueError(
                f"template bank LUT-index step {bank_lut_step:.3g} exceeds "
                f"geometry bound {geom.lut_step:.3g}; rebuild SearchGeometry "
                "with lut_step_for_bank(P, dt)"
            )
        # the blocked LUT requires a nonnegative phase (its unwrapped index
        # clips at 0) and a tiled table covering the whole span
        # psi0 + omega*t_obs
        psi0_max = 2.0 * np.pi
        if bank_psi0 is not None and len(bank_psi0):
            psi0_min = float(np.min(np.asarray(bank_psi0)))
            psi0_max = float(np.max(np.asarray(bank_psi0)))
            if psi0_min < 0.0 or psi0_max >= 2.0 * np.pi:
                raise ValueError(
                    f"template bank psi0 outside [0, 2pi) "
                    f"(min {psi0_min:.3g}, max {psi0_max:.3g}): fold the "
                    "bank through models.search.normalize_psi0 first (the "
                    "driver does this automatically)"
                )
        span_periods = (
            psi0_max / (2.0 * np.pi) + geom.n_unpadded * geom.dt / float(np.min(P))
        )
        if span_periods > geom.lut_tiles - 2:
            raise ValueError(
                f"search phase spans {span_periods:.0f} LUT periods, beyond "
                f"the geometry's tiled table ({geom.lut_tiles}); rebuild "
                "SearchGeometry with lut_tiles_for_bank(P, psi0, n, dt) "
                "(or use use_lut=False for P_orb below milliseconds)"
            )


def template_params_host(P, tau, psi0, dt):
    """Per-template float32 scalars derived on host exactly as the driver
    does (``demod_binary.c:1208-1238``): float casts, ``Omega = 2.0*M_PI/P``
    in double narrowed once, ``S0 = tau * sinf(Psi0) * step_inv`` as an
    all-float32 chain through glibc's sinf (the reference compiles as
    C++, where sin(float) is the float overload; see
    oracle/resample.py::ResampleParams.from_template)."""
    from ..oracle.sincos import libm_sinf

    P32 = np.float32(P)
    tau32 = np.float32(tau)
    psi32 = np.float32(psi0)
    dt32 = np.float32(dt)
    step_inv = np.float32(1.0) / dt32
    omega = np.float32(np.float64(2.0) * np.pi / np.float64(P32))
    s0 = np.float32(np.float32(tau32 * libm_sinf(psi32)) * step_inv)
    return tau32, omega, psi32, s0


def bank_params_host(P, tau, psi0, dt) -> tuple[np.ndarray, ...]:
    """Vectorized :func:`template_params_host` over the whole bank.

    Same float32 operation chain as the scalar version — float casts,
    ``Omega`` narrowed once from double, ``S0`` through glibc's sinf
    (``oracle/sincos.py::libm_sinf_array``) — so the result is bit-for-bit
    ``np.stack([template_params_host(...) for t in bank])``, but the numpy
    work is array-at-a-time: deriving the shipped 6,662-template PALFA bank
    drops from a multi-second Python loop to milliseconds.  Returns
    ``(tau32, omega, psi32, s0)`` float32 arrays of bank length."""
    from ..oracle.sincos import libm_sinf_array

    tau32 = np.asarray(tau, dtype=np.float32)
    psi32 = np.asarray(psi0, dtype=np.float32)
    P32 = np.asarray(P, dtype=np.float32)
    dt32 = np.float32(dt)
    step_inv = np.float32(1.0) / dt32
    omega = (np.float64(2.0) * np.pi / P32.astype(np.float64)).astype(
        np.float32
    )
    s0 = ((tau32 * libm_sinf_array(psi32)).astype(np.float32) * step_inv).astype(
        np.float32
    )
    return tau32, omega, psi32, s0


# sentinel below any real summed power: padded batch slots are masked to
# this before the block reduction so they can never claim a bin
NEG_SENTINEL = np.float32(-3.0e38)

# bank device arrays are padded to at least this capacity so the compiled
# step's input shapes (and the persistent-cache key) are stable across
# banks: the shipped PALFA bank (6,662) plus the largest batch rung (128)
# fits, and tools/create_wisdom.py's placeholder bank compiles the same
# executable the production driver runs
_MIN_BANK_CAPACITY = 8192


def upload_bank(params: tuple[np.ndarray, ...], batch_size: int) -> tuple:
    """One-time device upload of the whole bank's ``(tau, omega, psi0, s0)``.

    The arrays are padded to a power-of-two capacity ``>= n + batch_size``
    (min ``_MIN_BANK_CAPACITY``) so (a) ``lax.dynamic_slice`` at any batch
    start in ``[0, n)`` stays in range without clamping — clamping would
    silently shift the slice onto earlier templates — and (b) the padded
    shape, which is part of the jit cache key, is stable across bank sizes.
    Pad slots carry the harmless ``(0, 1, 0, 0)`` template; the step masks
    them via its ``n_total`` operand, so their values never reach (M, T)."""
    n = len(params[0])
    cap = _MIN_BANK_CAPACITY
    while cap < n + batch_size:
        cap *= 2
    fills = (0.0, 1.0, 0.0, 0.0)  # tau, omega, psi0, s0
    out = []
    for a, fill in zip(params, fills):
        buf = np.full(cap, fill, dtype=np.float32)
        buf[:n] = a
        out.append(jnp.asarray(buf))
    return tuple(out)


def prepare_ts(geom: SearchGeometry, ts: np.ndarray) -> tuple:
    """Host-side device operands for the time series: the parity-split
    halves (even, odd) — a free numpy stride-2 view copy on host, never a
    device stride-2 op — or the whole series for the (odd-length) fallback
    pipeline."""
    ts = np.asarray(ts, dtype=np.float32)
    if geom.parity_split:
        return (jnp.asarray(ts[0::2].copy()), jnp.asarray(ts[1::2].copy()))
    return (jnp.asarray(ts),)


def template_ps_fn(geom: SearchGeometry):
    """Returns the pure per-template function
    ``(ts_args, tau, omega, psi0, s0[, n_steps, mean]) -> float32[L]``:
    the power spectrum of one resampled template — the chain up to (but
    not including) the harmonic fold, so batched callers can feed the
    fused fold kernel (``ops/pallas_sumspec.py``) one ``(B, L)`` array."""

    def fn(ts_args, tau, omega, psi0, s0, n_steps=None, mean=None):
        if geom.parity_split:
            ev, od = resample_split(
                ts_args[0],
                ts_args[1],
                tau,
                omega,
                psi0,
                s0,
                n_steps,
                mean,
                nsamples=geom.nsamples,
                n_unpadded=geom.n_unpadded,
                dt=geom.dt,
                use_lut=geom.use_lut,
                max_slope=geom.max_slope,
                lut_step=geom.lut_step,
                lut_tiles=geom.lut_tiles,
            )
            ps = power_spectrum_split(ev, od, nsamples=geom.nsamples)
        else:
            resamp = resample(
                ts_args[0],
                tau,
                omega,
                psi0,
                s0,
                n_steps,
                mean,
                nsamples=geom.nsamples,
                n_unpadded=geom.n_unpadded,
                dt=geom.dt,
                use_lut=geom.use_lut,
                max_slope=geom.max_slope,
                lut_step=geom.lut_step,
                lut_tiles=geom.lut_tiles,
            )
            ps = power_spectrum(resamp, nsamples=geom.nsamples)
        return ps

    return fn


def template_sumspec_fn(geom: SearchGeometry):
    """Returns the pure per-template function
    ``(ts_args, tau, omega, psi0, s0[, n_steps, mean]) -> float32[5, W]``
    where ``ts_args = prepare_ts(geom, ts)`` and the optional
    ``n_steps``/``mean`` are the host-exact serial-mean overrides
    (``geom.exact_mean``)."""
    per_ps = template_ps_fn(geom)

    def fn(ts_args, tau, omega, psi0, s0, n_steps=None, mean=None):
        return harmonic_sumspec(
            per_ps(ts_args, tau, omega, psi0, s0, n_steps, mean),
            window_2=geom.window_2,
            fund_hi=geom.fund_hi,
            harm_hi=geom.harm_hi,
            natural=False,  # phase-major device layout (ops/harmonic.py)
        )

    return fn


def host_exact_mean_params(
    ts: np.ndarray, chunk_params: list[tuple], geom: SearchGeometry
) -> tuple[np.ndarray, np.ndarray]:
    """Per-template (n_steps, mean) computed on host with the reference's
    exact semantics — LUT sine del_t, serial shrink loop, nearest-neighbour
    gather, serial float32 accumulation (``oracle/resample.py``). Only used
    when ``geom.exact_mean`` (unwhitened runs; see SearchGeometry)."""
    from ..oracle.resample import (
        ResampleParams,
        compute_n_steps,
        resample_stats,
        serial_mean_f32,
    )

    ts = np.asarray(ts, dtype=np.float32)
    n_steps_out = np.empty(len(chunk_params), dtype=np.int32)
    mean_out = np.empty(len(chunk_params), dtype=np.float32)
    for i, (tau, omega, psi0, s0) in enumerate(chunk_params):
        rp = ResampleParams(
            nsamples=geom.nsamples,
            nsamples_unpadded=geom.n_unpadded,
            fft_size=geom.fft_size,
            tau=np.float32(tau),
            omega=np.float32(omega),
            psi0=np.float32(psi0),
            dt=np.float32(geom.dt),
            step_inv=np.float32(1.0) / np.float32(geom.dt),
            s0=np.float32(s0),
        )
        if geom.use_lut:
            # the oracle IS the reference-semantics implementation —
            # reuse its (n_steps, mean) chain without materializing the
            # padded output array (per-template host pass on unwhitened
            # production runs; oracle/resample.py::resample_stats)
            n_steps, mean = resample_stats(ts, rp)
        else:
            # BEST-EFFORT (non-production) branch: mirrors the device's
            # exact-sine option with np.sin, but NumPy's float32 sine is
            # not guaranteed bit-identical to XLA's jnp.sin — an ulp
            # difference can flip a nearest-neighbour index or the n_steps
            # boundary, so the "host-exact" pair may disagree with the
            # device gather it overrides by one sample. Production runs
            # (use_lut=True) are unaffected; --exact-sin exists for
            # accuracy studies, not parity.
            i_f = np.arange(geom.n_unpadded, dtype=np.float32)
            ph = (rp.omega * (i_f * rp.dt).astype(np.float32) + rp.psi0).astype(
                np.float32
            )
            del_t = (
                rp.tau * np.sin(ph).astype(np.float32) * rp.step_inv - rp.s0
            ).astype(np.float32)
            n_steps = compute_n_steps(del_t, geom.n_unpadded)
            i_f = np.arange(n_steps, dtype=np.float32)
            idx = (i_f - del_t[:n_steps] + np.float32(0.5)).astype(np.int32)
            np.clip(idx, 0, geom.n_unpadded - 1, out=idx)
            mean = serial_mean_f32(ts[idx], n_steps)
        n_steps_out[i] = n_steps
        mean_out[i] = mean
    return n_steps_out, mean_out


def init_state(geom: SearchGeometry):
    """(M, T): per-bin maxima and first-achieving template index, in the
    phase-major device layout (``ops/harmonic.py``; convert for host reads
    with ``state_to_natural``)."""
    W = state_width(geom.fund_hi)
    M = jnp.zeros((5, W), dtype=jnp.float32)
    T = jnp.zeros((5, W), dtype=jnp.int32)
    return M, T


def state_to_natural(arr, geom: SearchGeometry) -> np.ndarray:
    """Host: phase-major (5, W) M or T -> natural bin order (5, fund_hi)."""
    return to_natural_order(np.asarray(arr), geom.fund_hi)


def state_from_natural(arr: np.ndarray, geom: SearchGeometry) -> np.ndarray:
    """Host: natural (5, fund_hi) -> phase-major (5, W)."""
    return from_natural_order(np.asarray(arr), geom.fund_hi)


def use_pallas_resample(geom: SearchGeometry) -> bool:
    """Opt-in gate for the fused Pallas resampler
    (``ops/pallas_resample.py``): ``ERP_PALLAS_RESAMPLE=1`` AND the
    geometry fits the kernel's static contracts.  Off by default pending
    the on-chip A/B (``tools/pallas_ab.py``)."""
    import os

    if os.environ.get("ERP_PALLAS_RESAMPLE") != "1":
        return False
    if not (geom.parity_split and geom.use_lut and not geom.exact_mean):
        return False
    from ..ops.pallas_resample import pallas_applicable

    return pallas_applicable(geom.max_slope, geom.lut_step, geom.lut_tiles)


def use_pallas_resident(geom: SearchGeometry) -> bool:
    """Opt-in gate for the resident resample->FFT-prep chain
    (``ops/pallas_resample.py::resample_fftprep_pallas_batch``):
    ``ERP_PALLAS_RESIDENT=1`` AND the same geometry contract as the
    two-stage fused resampler.  Supersedes ``ERP_PALLAS_RESAMPLE`` when
    both are set (the resident chain contains the resampler).  Off by
    default pending the on-chip A/B — same rollout shape as
    :func:`use_pallas_resample`."""
    import os

    if os.environ.get("ERP_PALLAS_RESIDENT") != "1":
        return False
    if not (geom.parity_split and geom.use_lut and not geom.exact_mean):
        return False
    from ..ops.pallas_resample import pallas_applicable

    return pallas_applicable(geom.max_slope, geom.lut_step, geom.lut_tiles)


def resident_defers_renorm(geom: SearchGeometry) -> bool:
    """Whether the driver should run whitening with ``defer_renorm=True``
    for this geometry: the resident chain is gated on AND the whitening
    epilogue actually runs the packed device-split path whose renorm the
    kernel can absorb (``backend_has_native_fft()`` False and even
    lengths — the latter is implied by the resident gate's parity_split
    requirement).  Callers that defer must then flip
    ``geom.ts_prescaled`` to False via ``dataclasses.replace``."""
    from ..ops.fft import backend_has_native_fft

    return use_pallas_resident(geom) and not backend_has_native_fft()


def use_pallas_sumspec(geom: SearchGeometry) -> bool:
    """Opt-in gate for the fused resident-spectrum fold kernel
    (``ops/pallas_sumspec.py``): ``ERP_PALLAS_SUMSPEC=1`` AND the
    geometry fits the kernel's static contract.  Off by default pending
    the on-chip A/B — same rollout shape as :func:`use_pallas_resample`."""
    import os

    if os.environ.get("ERP_PALLAS_SUMSPEC") != "1":
        return False
    from ..ops.pallas_sumspec import sumspec_applicable

    return sumspec_applicable(geom.fund_hi, geom.harm_hi)


def _pallas_interpret() -> bool:
    """Whether Pallas kernels should lower in interpret mode.  Mosaic
    compiles only for TPU; on CPU (tests, oracle runs) interpret mode is
    bit-equal, just slow.  The backend test guesses wrong in exactly one
    place — the deviceless AOT tools compile *for* a TPU topology from a
    CPU backend — so ``ERP_PALLAS_INTERPRET=0`` (or ``=1``) overrides."""
    import os

    v = os.environ.get("ERP_PALLAS_INTERPRET")
    if v in ("0", "1"):
        return v == "1"
    return jax.default_backend() != "tpu"


# ERP_PRECISION modes -> spectrum-path dtype; bf16 is reserved for the
# reduced-precision follow-up (ROADMAP item 2, arXiv 2206.12205) so the
# env contract and its error shape are pinned before the kernels exist
_PRECISION_DTYPES = {"f32": jnp.float32}


def erp_precision() -> str:
    """The ``ERP_PRECISION`` spectrum-path precision mode: ``f32`` (the
    default and only implemented mode) or ``bf16`` (reserved).  Called at
    step-construction time so a bf16 request fails loudly up front, not
    mid-run."""
    import os

    v = os.environ.get("ERP_PRECISION", "f32").strip().lower()
    if v == "f32":
        return v
    if v == "bf16":
        raise NotImplementedError(
            "ERP_PRECISION=bf16 is scaffolding for the reduced-precision "
            "spectrum path (ROADMAP item 2); only f32 is implemented — "
            "unset ERP_PRECISION or set it to f32"
        )
    raise ValueError(
        f"ERP_PRECISION must be 'f32' or 'bf16', got {v!r}"
    )


def _fused_sums_fn(geom: SearchGeometry, interpret: bool):
    """Batched ``(B, L) power spectra -> (B, 5, W)`` via the fused Pallas
    fold kernel — the resident-spectrum replacement for the vmapped
    ``harmonic_sumspec`` (whose per-template while loop round-trips
    spectrum-sized accumulators through HBM)."""
    from ..ops.pallas_sumspec import sumspec_pallas_batch

    def sums(ps_batch):
        return sumspec_pallas_batch(
            ps_batch,
            window_2=geom.window_2,
            fund_hi=geom.fund_hi,
            harm_hi=geom.harm_hi,
            interpret=interpret,
        )

    return sums


def _ts_renorm(geom: SearchGeometry) -> float | None:
    """The deferred whitening renormalization scalar for this geometry, or
    None when the series already carries it.  ``float(np.sqrt(np.float32(
    nsamples)))`` is the same correctly-rounded IEEE f32 sqrt XLA computes
    in ``whiten_and_zap``, so folding the multiply downstream (Pallas
    ``renorm=`` or the XLA prescale) reproduces the prescaled series
    bit-for-bit."""
    if geom.ts_prescaled:
        return None
    return float(np.sqrt(np.float32(geom.nsamples)))


def _prep_ts_fn(geom: SearchGeometry):
    """Identity for a prescaled series; otherwise a traced function that
    applies the deferred whitening renormalization to every time-series
    operand inside the step, so the XLA branches — including the
    degradation ladder's ``allow_pallas=False`` fallback rung — gather
    from exactly the bits ``whiten_and_zap`` would have produced (an
    elementwise f32 multiply commutes bitwise through the resampler's
    select/slice ladder)."""
    r = _ts_renorm(geom)
    if r is None:
        return lambda ts_args: ts_args

    def prep(ts_args):
        with stage_scope("whiten"):
            s = jnp.float32(r)
            return tuple(a * s for a in ts_args)

    return prep


def make_batch_step(geom: SearchGeometry):
    """Jitted (ts_args, tau[B], omega[B], psi0[B], s0[B], t_offset, M, T
    [, n_steps[B], mean[B]]) -> (M, T) with the batch folded in.
    ``ts_args = prepare_ts(geom, ts)``; the trailing overrides exist iff
    ``geom.exact_mean``.

    This is the per-batch-upload formulation: the caller h2d-copies each
    batch's parameters.  The production dispatch loop (``run_bank``) uses
    :func:`make_bank_step` instead — bank-resident parameters sliced on
    device — and keeps this step as the synchronous reference for the
    equivalence tests (``tests/test_async_pipeline.py``) and the A/B
    tooling (bench legacy mode, ``tools/pallas_ab.py``).  No state
    donation here: A/B callers reuse one (M, T) across step variants."""

    erp_precision()  # bf16 requests fail at construction, not mid-run
    per_template = template_sumspec_fn(geom)
    per_ps = template_ps_fn(geom)
    fused = use_pallas_sumspec(geom)
    interpret = _pallas_interpret()
    batch_sums = _fused_sums_fn(geom, interpret) if fused else None

    resident = use_pallas_resident(geom)
    if resident or use_pallas_resample(geom):
        from ..ops.pallas_resample import (
            resample_fftprep_pallas_batch,
            resample_split_pallas_batch,
        )

        # the resident chain emits the padded mean-filled series straight
        # from VMEM (bitwise identical to the two-stage form); both fold
        # the deferred whitening renorm into the gather when the driver
        # shipped an unscaled series (geom.ts_prescaled=False)
        resample_fn = (
            resample_fftprep_pallas_batch
            if resident
            else resample_split_pallas_batch
        )
        renorm = _ts_renorm(geom)

        @jax.jit
        def step(ts_args, tau, omega, psi0, s0, t_offset, M, T):
            ev, od = resample_fn(
                ts_args[0],
                ts_args[1],
                tau,
                omega,
                psi0,
                s0,
                nsamples=geom.nsamples,
                n_unpadded=geom.n_unpadded,
                dt=geom.dt,
                max_slope=geom.max_slope,
                lut_step=geom.lut_step,
                lut_tiles=geom.lut_tiles,
                renorm=renorm,
                interpret=interpret,
            )
            if fused:
                ps = jax.vmap(
                    lambda e, o: power_spectrum_split(
                        e, o, nsamples=geom.nsamples
                    )
                )(ev, od)
                sums = batch_sums(ps)  # (B, 5, W)
            else:
                sums = jax.vmap(
                    lambda e, o: harmonic_sumspec(
                        power_spectrum_split(e, o, nsamples=geom.nsamples),
                        window_2=geom.window_2,
                        fund_hi=geom.fund_hi,
                        harm_hi=geom.harm_hi,
                        natural=False,
                    )
                )(ev, od)  # (B, 5, W)
            with stage_scope("merge"):
                bmax = jnp.max(sums, axis=0)
                barg = jnp.argmax(sums, axis=0).astype(jnp.int32)
                better = bmax > M
                T = jnp.where(better, t_offset + barg, T)
                M = jnp.where(better, bmax, M)
            return M, T

        return step

    prep = _prep_ts_fn(geom)

    if geom.exact_mean:

        @jax.jit
        def step(ts_args, tau, omega, psi0, s0, t_offset, M, T, n_steps, mean):
            ts_args = prep(ts_args)
            if fused:
                ps = jax.vmap(
                    lambda a, b, c, d, ns, mn: per_ps(
                        ts_args, a, b, c, d, ns, mn
                    )
                )(tau, omega, psi0, s0, n_steps, mean)
                sums = batch_sums(ps)  # (B, 5, W)
            else:
                sums = jax.vmap(
                    lambda a, b, c, d, ns, mn: per_template(
                        ts_args, a, b, c, d, ns, mn
                    )
                )(tau, omega, psi0, s0, n_steps, mean)  # (B, 5, W)
            with stage_scope("merge"):
                bmax = jnp.max(sums, axis=0)
                barg = jnp.argmax(sums, axis=0).astype(jnp.int32)
                better = bmax > M
                T = jnp.where(better, t_offset + barg, T)
                M = jnp.where(better, bmax, M)
            return M, T

        return step

    @jax.jit
    def step(ts_args, tau, omega, psi0, s0, t_offset, M, T):
        ts_args = prep(ts_args)
        if fused:
            ps = jax.vmap(lambda a, b, c, d: per_ps(ts_args, a, b, c, d))(
                tau, omega, psi0, s0
            )
            sums = batch_sums(ps)  # (B, 5, W)
        else:
            sums = jax.vmap(
                lambda a, b, c, d: per_template(ts_args, a, b, c, d)
            )(tau, omega, psi0, s0)  # (B, 5, W)
        with stage_scope("merge"):
            bmax = jnp.max(sums, axis=0)
            barg = jnp.argmax(sums, axis=0).astype(jnp.int32)  # first max in batch
            better = bmax > M
            T = jnp.where(better, t_offset + barg, T)
            M = jnp.where(better, bmax, M)
        return M, T

    return step


def batch_health_vec(sums, valid, M_new):
    """Device health scalars for one batch, as a float32[4] vector:
    ``[nonfinite_batch, nonfinite_state, finite_max, finite_min]``.

    Computed from the batch's summed spectra BEFORE the max-merge — the
    only place a NaN is still visible: ``NaN > M`` is False, so poisoned
    templates never reach (M, T) and the run would otherwise finish with
    a silently wrong toplist (runtime/health.py).  Padded slots are
    excluded via ``valid``; the finite max/min fall back to the
    sentinels when a batch has no finite valid value (the non-finite
    count flags it first)."""
    with stage_scope("health"):
        validb = valid[:, None, None]
        fin = jnp.isfinite(sums)
        nf_batch = jnp.sum((validb & ~fin).astype(jnp.int32))
        ok = validb & fin
        fmax = jnp.max(jnp.where(ok, sums, NEG_SENTINEL))
        fmin = jnp.min(jnp.where(ok, sums, -NEG_SENTINEL))
        nf_state = jnp.sum((~jnp.isfinite(M_new)).astype(jnp.int32))
        return jnp.stack(
            [
                nf_batch.astype(jnp.float32),
                nf_state.astype(jnp.float32),
                fmax,
                fmin,
            ]
        )


def bank_step_layouts(geom: SearchGeometry, with_health: bool, device):
    """Explicit device layouts for :func:`make_bank_step`'s operand and
    result pytrees on ``device``: row-major (major_to_minor descending)
    for every array, placement-only for the scalar operands.

    Without these the compiler is free to pick a different layout per
    dispatch-window executable for the SAME persistent buffers — the (M,
    T) state and the bank arrays — and reconciles its choices with
    inserted copies, the 2.5 GB/template "compiler-generated" bucket the
    r05 ledger attributes to no stage.  Pinning one explicit layout on
    both sides of the donation makes every window executable agree, so
    the buffers alias through unchanged.  Chip-free verifiable: the
    layouts compile against a deviceless TPU topology
    (tests/test_tpu_compile.py)."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(device)
    v1 = Format(Layout(major_to_minor=(0,)), sh)
    m2 = Format(Layout(major_to_minor=(0, 1)), sh)
    ts = tuple(v1 for _ in range(2 if geom.parity_split else 1))
    in_sh = [ts, v1, v1, v1, v1, sh, sh, m2, m2]
    if geom.exact_mean:
        in_sh += [v1, v1]
    out_sh = (m2, m2, v1) if with_health else (m2, m2)
    return tuple(in_sh), out_sh


def make_bank_step(
    geom: SearchGeometry,
    batch_size: int,
    with_health: bool = False,
    allow_pallas: bool = True,
):
    """The production dispatch step: bank-resident parameters, on-device
    batch slicing, donated state.

    Jitted ``(ts_args, btau, bomega, bpsi0, bs0, t_offset, n_total, M, T
    [, n_steps[B], mean[B]]) -> (M, T)`` where ``btau``.. are the
    :func:`upload_bank` device arrays of the WHOLE bank: the step slices
    its ``batch_size`` window with ``lax.dynamic_slice`` from ``t_offset``,
    so the steady-state loop performs no per-batch parameter h2d at all.
    Slots at global index ``>= n_total`` (the final partial batch) are
    masked to :data:`NEG_SENTINEL` before the block reduction — they can
    never claim a bin, which is bit-equivalent to the legacy
    duplicate-first-template padding (``make_batch_step``): in both
    schemes ``bmax`` is the exact max over the real templates and
    ``argmax`` resolves ties to the smallest batch index.

    (M, T) are donated (``donate_argnums``): the maxima state updates in
    place on device, halving its HBM footprint and letting XLA alias the
    update.  Callers must treat the passed-in state as consumed — the
    dispatch loop rebinds ``M, T = step(...)`` every call.  The trailing
    ``n_steps``/``mean`` host-exact overrides exist iff ``geom.exact_mean``
    and stay per-batch operands (they are data-dependent host work, fed by
    the prefetch thread in ``run_bank``).

    With ``with_health`` the step additionally returns the
    :func:`batch_health_vec` float32[4] device scalars — the numerical-
    health watchdog's per-batch feed (``runtime/health.py``); donation
    and the (M, T) contract are unchanged.  ``allow_pallas=False`` forces
    the XLA path even when the Pallas resampler and/or the fused
    sumspec fold are enabled and applicable — the degradation ladder's
    fallback rung (``runtime/resilience.py``).

    On TPU the jitted step additionally pins explicit row-major device
    layouts on every array operand and result (:func:`bank_step_layouts`):
    the donated (M, T) state and the bank arrays flow between dispatch
    windows without compiler-inserted layout copies — the
    "compiler-generated" bucket of ``COST_LEDGER.json``."""
    B = int(batch_size)
    erp_precision()  # bf16 requests fail at construction, not mid-run
    per_template = template_sumspec_fn(geom)
    per_ps = template_ps_fn(geom)
    fused = allow_pallas and use_pallas_sumspec(geom)
    interpret = _pallas_interpret()
    batch_sums = _fused_sums_fn(geom, interpret) if fused else None

    def _jit(step):
        donate = (7, 8)
        if jax.default_backend() != "tpu":
            # explicit layouts exist to stop TPU relayout copies; on CPU
            # they would only constrain the compiler for no gain
            return jax.jit(step, donate_argnums=donate)
        in_sh, out_sh = bank_step_layouts(
            geom, with_health, jax.devices()[0]
        )
        return jax.jit(
            step,
            donate_argnums=donate,
            in_shardings=in_sh,
            out_shardings=out_sh,
        )

    def merge(sums, valid, t_offset, M, T):
        with stage_scope("merge"):
            masked = jnp.where(valid[:, None, None], sums, NEG_SENTINEL)
            bmax = jnp.max(masked, axis=0)
            barg = jnp.argmax(masked, axis=0).astype(jnp.int32)  # first max in batch
            better = bmax > M
            Mn = jnp.where(better, bmax, M)
            Tn = jnp.where(better, t_offset + barg, T)
        if with_health:
            return Mn, Tn, batch_health_vec(sums, valid, Mn)
        return Mn, Tn

    def slice_bank(btau, bomega, bpsi0, bs0, t_offset):
        with stage_scope("bank-slice"):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, t_offset, B)
            return sl(btau), sl(bomega), sl(bpsi0), sl(bs0)

    resident = allow_pallas and use_pallas_resident(geom)
    if resident or (allow_pallas and use_pallas_resample(geom)):
        from ..ops.pallas_resample import (
            resample_fftprep_pallas_batch,
            resample_split_pallas_batch,
        )

        # resident chain: the resampled series goes straight to FFT-prep
        # layout in VMEM (ERP_PALLAS_RESIDENT=1); both variants fold the
        # deferred whitening renorm into the gather when the driver
        # shipped an unscaled series (geom.ts_prescaled=False)
        resample_fn = (
            resample_fftprep_pallas_batch
            if resident
            else resample_split_pallas_batch
        )
        renorm = _ts_renorm(geom)

        def step(ts_args, btau, bomega, bpsi0, bs0, t_offset, n_total, M, T):
            tau, omega, psi0, s0 = slice_bank(btau, bomega, bpsi0, bs0, t_offset)
            valid = t_offset + jnp.arange(B, dtype=jnp.int32) < n_total
            ev, od = resample_fn(
                ts_args[0],
                ts_args[1],
                tau,
                omega,
                psi0,
                s0,
                nsamples=geom.nsamples,
                n_unpadded=geom.n_unpadded,
                dt=geom.dt,
                max_slope=geom.max_slope,
                lut_step=geom.lut_step,
                lut_tiles=geom.lut_tiles,
                renorm=renorm,
                interpret=interpret,
            )
            if fused:
                ps = jax.vmap(
                    lambda e, o: power_spectrum_split(
                        e, o, nsamples=geom.nsamples
                    )
                )(ev, od)
                sums = batch_sums(ps)  # (B, 5, W)
            else:
                sums = jax.vmap(
                    lambda e, o: harmonic_sumspec(
                        power_spectrum_split(e, o, nsamples=geom.nsamples),
                        window_2=geom.window_2,
                        fund_hi=geom.fund_hi,
                        harm_hi=geom.harm_hi,
                        natural=False,
                    )
                )(ev, od)  # (B, 5, W)
            return merge(sums, valid, t_offset, M, T)

        return _jit(step)

    prep = _prep_ts_fn(geom)

    if geom.exact_mean:

        def step(
            ts_args, btau, bomega, bpsi0, bs0, t_offset, n_total, M, T,
            n_steps, mean,
        ):
            ts_args = prep(ts_args)
            tau, omega, psi0, s0 = slice_bank(btau, bomega, bpsi0, bs0, t_offset)
            valid = t_offset + jnp.arange(B, dtype=jnp.int32) < n_total
            if fused:
                ps = jax.vmap(
                    lambda a, b, c, d, ns, mn: per_ps(
                        ts_args, a, b, c, d, ns, mn
                    )
                )(tau, omega, psi0, s0, n_steps, mean)
                sums = batch_sums(ps)  # (B, 5, W)
            else:
                sums = jax.vmap(
                    lambda a, b, c, d, ns, mn: per_template(
                        ts_args, a, b, c, d, ns, mn
                    )
                )(tau, omega, psi0, s0, n_steps, mean)  # (B, 5, W)
            return merge(sums, valid, t_offset, M, T)

        return _jit(step)

    def step(ts_args, btau, bomega, bpsi0, bs0, t_offset, n_total, M, T):
        ts_args = prep(ts_args)
        tau, omega, psi0, s0 = slice_bank(btau, bomega, bpsi0, bs0, t_offset)
        valid = t_offset + jnp.arange(B, dtype=jnp.int32) < n_total
        if fused:
            ps = jax.vmap(lambda a, b, c, d: per_ps(ts_args, a, b, c, d))(
                tau, omega, psi0, s0
            )
            sums = batch_sums(ps)  # (B, 5, W)
        else:
            sums = jax.vmap(
                lambda a, b, c, d: per_template(ts_args, a, b, c, d)
            )(tau, omega, psi0, s0)  # (B, 5, W)
        return merge(sums, valid, t_offset, M, T)

    return _jit(step)


class ExactMeanPrefetch:
    """Background host pass for the reference-exact per-template
    ``(n_steps, mean)`` pair (``host_exact_mean_params``) of UPCOMING
    batches, so unwhitened runs overlap the serial host oracle chain with
    device compute instead of serializing before every dispatch.

    One worker thread (the host pass is CPU-serial anyway; a second
    worker would fight the dispatch thread for the GIL), ``depth``
    batches of lookahead.  ``get(start)`` blocks only when the device has
    outrun the host — the steady state on fast chips is the reverse."""

    def __init__(self, ts_np, params, geom, starts, batch_size, depth=2):
        from concurrent.futures import ThreadPoolExecutor

        self._ts = ts_np
        self._params = params  # (tau32, omega, psi32, s0) bank arrays
        self._geom = geom
        self._starts = list(starts)
        self._B = int(batch_size)
        self._n = len(params[0])
        self._pool = ThreadPoolExecutor(max_workers=1)
        self._futures: dict[int, object] = {}
        self._next = 0
        for _ in range(max(1, depth)):
            self._submit_next()

    def _submit_next(self) -> None:
        if self._next >= len(self._starts):
            return
        start = self._starts[self._next]
        self._next += 1
        # the submitting thread's trace context (the window whose `get`
        # opened this prefetch slot) rides along so the worker's span
        # correlates with it on the timeline (runtime/tracing.py)
        self._futures[start] = self._pool.submit(
            self._compute, start, tracing.context()
        )

    def _compute(self, start: int, trace_ctx=None):
        tracing.set_context(trace_ctx)
        with tracing.span("prefetch-compute", tid="prefetch", start=start):
            return self._compute_inner(start)

    def _compute_inner(self, start: int):
        tau32, omega, psi32, s0 = self._params
        stop = min(start + self._B, self._n)
        chunk = list(
            zip(tau32[start:stop], omega[start:stop],
                psi32[start:stop], s0[start:stop])
        )
        ns, mn = host_exact_mean_params(self._ts, chunk, self._geom)
        pad = self._B - len(chunk)
        if pad:
            # pad with the chunk's first element, mirroring the legacy
            # duplicate-first-template batch padding; the device masks
            # these slots regardless (make_bank_step n_total operand)
            ns = np.concatenate([ns, np.full(pad, ns[0], dtype=ns.dtype)])
            mn = np.concatenate([mn, np.full(pad, mn[0], dtype=mn.dtype)])
        return ns, mn

    def get(self, start: int):
        """(n_steps[B], mean[B]) for the batch at ``start``; keeps the
        prefetch window full by queueing the next batch."""
        fut = self._futures.pop(start)
        self._submit_next()
        return fut.result()

    def close(self) -> None:
        self._pool.shutdown(wait=False, cancel_futures=True)


def step_cache_key(
    geom: SearchGeometry,
    batch_size: int,
    with_health: bool,
    allow_pallas: bool,
) -> tuple:
    """Residency key for a :func:`make_bank_step` instance.

    Two searches with equal keys lower to the same executable: the key
    folds in everything ``make_bank_step`` reads besides its arguments —
    spectrum precision, the Pallas opt-in gates (env-dependent), the FFT
    path choice (``ERP_FORCE_CASCADE`` flips ``backend_has_native_fft``
    at trace time), and the backend (layout pinning differs on TPU).
    ``geom`` is a frozen dataclass of scalars — including
    ``ts_prescaled``, the deferred-renorm flag — so the whole key is
    hashable.  A resident scheduler (``runtime/scheduler.py``) keys its
    step cache on this so same-geometry workunits reuse one jitted
    instance — the mechanism behind zero recompiles after warmup
    (``docs/serving.md``).  Every env consulted during step construction
    MUST appear here: a missing component would let the fleet server
    silently serve a stale executable across differently-gated WUs
    (pinned by tests/test_pallas_resample.py::test_step_cache_key_folds_gates).
    """
    from ..ops.fft import backend_has_native_fft

    return (
        "erp-bank-step/2",
        geom,
        int(batch_size),
        bool(with_health),
        bool(allow_pallas),
        erp_precision(),
        bool(allow_pallas and use_pallas_resample(geom)),
        bool(allow_pallas and use_pallas_resident(geom)),
        bool(allow_pallas and use_pallas_sumspec(geom)),
        _pallas_interpret(),
        backend_has_native_fft(),
        jax.default_backend(),
    )


def run_bank(
    ts: np.ndarray,
    bank_P: np.ndarray,
    bank_tau: np.ndarray,
    bank_psi0: np.ndarray,
    geom: SearchGeometry,
    batch_size: int = 16,
    state=None,
    start_template: int = 0,
    stop_template: int | None = None,
    progress_cb=None,
    lookahead: int = 2,
    step_cache=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Resilient wrapper around the async dispatch loop; returns (M, T).

    ``stop_template`` bounds the covered range to ``[start_template,
    stop_template)`` — the driver uses it to dispatch around quarantined
    poison ranges (``runtime/watchdog.py``); the device ``n_total``
    operand becomes the window end, so templates past it are masked
    exactly like final-batch padding (traced scalar, no recompile).

    Failures classified transient (``runtime/resilience.py``) re-enter
    the loop from the last host-side snapshot instead of killing the
    run, spending from the per-run retry budget: device OOM halves the
    batch and re-dispatches, repeated Pallas-resampler failures fall
    back to the XLA path, anything else is a plain backoff-retry.
    ``ERP_RETRY_BUDGET=0`` disables the wrapper AND the snapshot d2h —
    the loop then runs exactly as before.  See :func:`_run_bank_attempt`
    for the dispatch-loop contract the wrapper preserves.

    ``step_cache`` (any mutable mapping keyed by :func:`step_cache_key`)
    makes the jitted step survive this call: a resident scheduler passes
    one cache across workunits so same-geometry searches skip both the
    retrace and the compile.  ``None`` (the default, and the one-process-
    per-WU driver path) rebuilds the step per call, exactly as before.
    """
    from ..runtime import resilience

    pol = resilience.policy()
    if pol is None:
        return _run_bank_attempt(
            ts, bank_P, bank_tau, bank_psi0, geom, batch_size=batch_size,
            state=state, start_template=start_template,
            stop_template=stop_template,
            progress_cb=progress_cb, lookahead=lookahead,
            step_cache=step_cache,
        )
    snap = resilience.DispatchSnapshot(state, start_template)
    ladder = resilience.DegradationLadder(
        pol, batch_size,
        pallas_active=use_pallas_resample(geom)
        or use_pallas_resident(geom)
        or use_pallas_sumspec(geom),
    )
    cur_state, cur_start = state, start_template
    while True:
        try:
            return _run_bank_attempt(
                ts, bank_P, bank_tau, bank_psi0, geom,
                batch_size=ladder.batch_size, state=cur_state,
                start_template=cur_start, stop_template=stop_template,
                progress_cb=progress_cb,
                lookahead=lookahead, allow_pallas=ladder.allow_pallas,
                snapshot=snap, step_cache=step_cache,
            )
        except Exception as e:
            if not ladder.record_failure("dispatch", e):
                raise
            ladder.sleep()
            # a failed step may have consumed its donated (M, T) inputs:
            # rebuild device state from the snapshot's host copies and
            # re-dispatch from the last committed template
            host_state, cur_start = snap.restore()
            cur_state = (
                None
                if host_state is None
                else (jnp.asarray(host_state[0]), jnp.asarray(host_state[1]))
            )
            flightrec.record(
                "redispatch", start=cur_start,
                batch_size=ladder.batch_size, attempt=ladder.attempt,
            )


def _run_bank_attempt(
    ts: np.ndarray,
    bank_P: np.ndarray,
    bank_tau: np.ndarray,
    bank_psi0: np.ndarray,
    geom: SearchGeometry,
    batch_size: int = 16,
    state=None,
    start_template: int = 0,
    stop_template: int | None = None,
    progress_cb=None,
    lookahead: int = 2,
    allow_pallas: bool = True,
    snapshot=None,
    step_cache=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The async double-buffered dispatch loop; returns (M, T).

    The whole bank's parameters are derived vectorized
    (:func:`bank_params_host`) and uploaded once (:func:`upload_bank`);
    each step slices its batch on device (:func:`make_bank_step`), so the
    steady-state loop does no per-batch host parameter work and no h2d
    beyond two int32 scalars.  Dispatch runs ahead of the device through
    JAX's async dispatch, bounded to ``lookahead`` in-flight steps: after
    ``lookahead`` consecutive dispatches the loop blocks until the newest
    state is ready before continuing, so quit latency and queued work stay
    bounded while the device never waits on the host.  ``lookahead=1`` is
    the fully synchronous schedule (every step drained before the next).

    ``T`` holds *global* template indices (``start_template``-relative
    numbering is never used). ``progress_cb(done, total, M, T)`` is called
    after each dispatch with the LIVE device arrays — lazy handles whose
    mere receipt costs no d2h; only a consumer that actually reads them
    (checkpoint cadence, screensaver payload) synchronizes.  Returning
    ``False`` stops the loop early (quit request), leaving the state
    consistent with ``done`` templates merged — the returned (M, T) is the
    carried dependency chain through exactly the dispatched batches.
    Callbacks must read state before returning: the next dispatch donates
    the arrays (in-place device update).

    With ``geom.exact_mean`` the per-template host-exact ``(n_steps,
    mean)`` pass runs on a background prefetch thread
    (:class:`ExactMeanPrefetch`), ``lookahead`` batches deep.

    ``ts`` is either the host time series, or an already-prepared device
    operand tuple as returned by ``prepare_ts`` /
    ``whiten_and_zap(..., return_device_split=True)`` — the whitened
    parity halves then never round-trip the host.

    ``snapshot`` (a ``resilience.DispatchSnapshot``) is refreshed with
    host copies of (M, T) at drain boundaries, throttled to the snapshot
    interval — the recovery point :func:`run_bank` restarts from.
    """
    validate_bank_bounds(geom, bank_P, bank_tau, bank_psi0)
    # numerical-health watchdog (runtime/health.py): with ERP_HEALTH_EVERY
    # unset this is None and the plain (M, T)-returning step compiles —
    # the disabled path is byte-identical to before
    from ..runtime.health import watchdog as _make_watchdog

    wd = _make_watchdog()
    if step_cache is not None:
        # resident path: one jitted instance per step_cache_key survives
        # across workunits, so a same-key search costs zero retraces and
        # zero compiles (the serving tier's headline gate)
        key = step_cache_key(
            geom, batch_size, wd is not None, allow_pallas
        )
        step = step_cache.get(key)
        if step is None:
            step = make_bank_step(
                geom, batch_size, with_health=wd is not None,
                allow_pallas=allow_pallas,
            )
            step_cache[key] = step
    else:
        step = make_bank_step(
            geom, batch_size, with_health=wd is not None,
            allow_pallas=allow_pallas,
        )
    if state is None:
        state = init_state(geom)
    M, T = state
    if isinstance(ts, tuple):
        if geom.exact_mean:
            raise ValueError(
                "exact_mean requires the host time series (unwhitened runs "
                "never produce device-resident parity halves)"
            )
        ts_np = None
        ts_args = ts
    else:
        ts_np = np.asarray(ts, dtype=np.float32)
        ts_args = prepare_ts(geom, ts_np)

    n = len(bank_P)
    n_stop = n if stop_template is None else min(n, int(stop_template))
    params = bank_params_host(bank_P, bank_tau, bank_psi0, geom.dt)
    faultinject.fault_point("h2d", loop="run_bank")
    dev_bank = upload_bank(params, batch_size)
    # the device masks templates >= n_total like final-batch padding, so a
    # bounded window ends exactly at stop_template (traced, no recompile)
    n_total = jnp.int32(n_stop)
    lookahead = max(1, int(lookahead))
    starts = range(start_template, n_stop, batch_size)

    # metrics instruments are bound once outside the loop: shared no-op
    # nulls when disabled, so the steady-state cost is a few perf_counter
    # reads per batch either way (runtime/metrics.py)
    m_batches = metrics.counter("search.batches")
    m_templates = metrics.counter("search.templates")
    m_dispatch_s = metrics.counter("search.dispatch_wall_s", unit="s")
    m_stall_s = metrics.counter("search.drain_stall_s", unit="s")
    m_prefetch_s = metrics.counter("search.prefetch_wait_s", unit="s")
    m_h2d = metrics.counter("search.h2d_bytes", unit="B")
    m_dispatch_ms = metrics.histogram(
        "search.dispatch_ms", metrics.LATENCY_BUCKETS_MS, unit="ms"
    )
    m_stall_ms = metrics.histogram(
        "search.drain_stall_ms", metrics.LATENCY_BUCKETS_MS, unit="ms"
    )
    m_occupancy = metrics.histogram(
        "search.lookahead_occupancy", metrics.OCCUPANCY_BUCKETS
    )
    m_h2d.inc(sum(int(a.nbytes) for a in dev_bank))
    if ts_np is not None:
        m_h2d.inc(int(ts_np.nbytes))
    # measured step-time bracket (runtime/steptime.py): the shared no-op
    # when ERP_STEPTIME is off — two no-op calls per batch; when on, each
    # window is drained and its wall recorded (serializes the lookahead
    # pipeline by design: measuring is opt-in, the traced step and its
    # results are untouched either way)
    st = steptime.recorder()

    prefetch = None
    if geom.exact_mean:
        prefetch = ExactMeanPrefetch(
            ts_np, params, geom, starts, batch_size, depth=lookahead
        )
    inflight = 0
    try:
        for start in starts:
            stop = min(start + batch_size, n_stop)
            # one trace context per dispatch window: the prefetch /
            # rescore-feed spans this window triggers carry the same id
            tracing.new_context()
            args = [ts_args, *dev_bank, jnp.int32(start), n_total, M, T]
            if prefetch is not None:
                t0 = time.perf_counter()
                with tracing.span("prefetch-wait", start=start):
                    ns, mn = prefetch.get(start)
                m_prefetch_s.inc(time.perf_counter() - t0)
                ns, mn = np.asarray(ns), np.asarray(mn)
                m_h2d.inc(int(ns.nbytes) + int(mn.nbytes))
                args += [jnp.asarray(ns), jnp.asarray(mn)]
            st.begin()
            t0 = time.perf_counter()
            with hangdog.guard("dispatch", start=start, stop=stop):
                faultinject.fault_point("dispatch", start=start, stop=stop)
                with tracing.span("dispatch", start=start, stop=stop):
                    if wd is not None:
                        M, T, health_vec = step(*args)
                        wd.push(start, stop, health_vec)
                    else:
                        M, T = step(*args)
            dt_dispatch = time.perf_counter() - t0
            st.observe(M, start, stop)
            m_dispatch_s.inc(dt_dispatch)
            m_dispatch_ms.observe(dt_dispatch * 1e3)
            inflight += 1
            m_occupancy.observe(inflight)
            m_batches.inc()
            m_templates.inc(stop - start)
            flightrec.record(
                "dispatch", start=start, stop=stop,
                ms=round(dt_dispatch * 1e3, 3),
            )
            flightrec.note_dispatch(
                loop="run_bank", start=start, stop=stop, n_total=n,
                batch_size=batch_size, inflight=inflight,
                lookahead=lookahead,
            )
            if inflight >= lookahead:
                # bound the in-flight window: drain before running further
                # ahead (the device stays busy — the queue refills faster
                # than one step executes)
                t0 = time.perf_counter()
                with hangdog.guard("drain", stop=stop), tracing.span(
                    "drain", stop=stop
                ):
                    jax.block_until_ready(M)
                dt_stall = time.perf_counter() - t0
                m_stall_s.inc(dt_stall)
                m_stall_ms.observe(dt_stall * 1e3)
                flightrec.record(
                    "drain", stop=stop, stall_ms=round(dt_stall * 1e3, 3)
                )
                inflight = 0
                if snapshot is not None:
                    # the drained M is concrete: refresh the recovery
                    # point (throttled d2h; runtime/resilience.py)
                    snapshot.maybe_commit(M, T, stop)
            if wd is not None:
                # cadence check: fetching the pending health scalars syncs
                # the stream up to this batch, so it shares the drain
                # boundary's cost model (ERP_HEALTH_EVERY is the knob)
                wd.maybe_check("run_bank")
            if progress_cb is not None:
                with tracing.span("progress", stop=stop):
                    go_on = progress_cb(stop, n, M, T)
                if go_on is False:
                    break
        if wd is not None:
            wd.check("run_bank")
    finally:
        if prefetch is not None:
            prefetch.close()
    return M, T
