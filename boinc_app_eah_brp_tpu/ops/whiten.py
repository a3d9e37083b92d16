"""Whitening + RFI zapping on device (``demod_binary.c:856-1079``).

The reference keeps this stage CPU-only (FFTW even in CUDA builds). On TPU
the heavy parts — the 12.6M-point rfft/irfft and the window-1000 running
median over 6.3M bins — run on device; only the zap-noise stream (a serial
taus2 RNG, a few 10^4 draws) stays on host and is scattered into the
spectrum as an index/value pair.
"""

from __future__ import annotations

import os

import jax.numpy as jnp
import numpy as np

from ..oracle.gslrng import Taus2  # noqa: F401  (re-exported for callers)
from ..oracle.pipeline import DerivedParams, SearchConfig
from ..oracle.whiten import seed_from_samples, zap_noise
from ..runtime.devicecost import stage_scope
from .fft import (
    backend_has_native_fft,
    irfft_packed_split,
    irfft_split,
    rfft_packed_split,
    rfft_split,
)
from .median import running_median


def _native_median_overlapped(ps_dev, window: int, chunks: int = 4) -> np.ndarray:
    """Sliding median via the native walk with the device-to-host transfer
    OVERLAPPED against the computation: the d2h fetch of chunk c+1 runs on
    the main thread while the native walk (which releases the GIL through
    ctypes) processes chunk c on a worker.  Chunks carry the window-1
    overlap their medians need, so the concatenated output is bit-identical
    to the whole-array call (tests/test_native_median.py).  Saves most of
    the serial d2h cost of the 25 MB spectrum (VERDICT r03 weak #2)."""
    from concurrent.futures import ThreadPoolExecutor

    from .native_median import running_median_native

    n = int(ps_dev.shape[0])
    n_out = n - window + 1
    edges = np.linspace(0, n_out, chunks + 1).astype(np.int64)
    outs: list = []
    with ThreadPoolExecutor(max_workers=1) as pool:
        fut = None
        for c in range(chunks):
            a, b = int(edges[c]), int(edges[c + 1])
            if b <= a:
                continue
            seg = np.asarray(ps_dev[a : b + window - 1])  # blocking d2h
            if fut is not None:
                outs.append(fut.result())
            fut = pool.submit(running_median_native, seg, window)
        if fut is not None:
            outs.append(fut.result())
    return np.concatenate(outs)


def whiten_and_zap(
    samples: np.ndarray,  # float32[n_unpadded]
    derived: DerivedParams,
    cfg: SearchConfig,
    zap_ranges: np.ndarray,
    median_block: int = 4096,
    timings: dict | None = None,
    return_device_split: bool = False,
    packed_payload: np.ndarray | None = None,
    packed_scale: float = 1.0,
    defer_renorm: bool = False,
) -> np.ndarray | tuple:
    """``timings`` (diagnostic): when a dict is passed, each stage is
    synced and its wall-clock recorded under a stage key — serializes the
    device pipeline, so only for ``tools/stagebench.py --whiten``.

    ``return_device_split``: when the packed parity-split path is active
    (TPU), skip the output d2h + host interleave entirely and return the
    device-resident ``(even, odd)`` halves of the whitened series — exactly
    the operands ``models.search.prepare_ts`` would re-upload, so the
    search starts from resident data (VERDICT r03 #7: the d2h/h2d
    round-trip was ~3.5 s warm per WU).  On the non-packed path (CPU/GPU
    native FFT, or odd lengths) the flag is ignored and the host array is
    returned; callers dispatch on the return type.

    ``packed_payload``/``packed_scale``: the raw 4-bit workunit bytes
    (``io.workunit.Workunit.raw``) and the header scale.  When given and
    the parity-split path is active, the upload ships these ~2.1 MB of
    packed nibbles instead of ~17 MB of unpacked float halves and the
    device splits them through a host-exact 16-entry table
    (``ops/unpack.py``) — bit-identical operands, ~8x less H2D.
    ``samples`` must still be the host unpack of the same payload (it
    seeds the zap RNG and serves the non-packed fallback).

    ``defer_renorm``: skip the final ``sqrt(nsamples)`` renormalization of
    the returned device halves so the resident resample chain
    (``ops/pallas_resample.py::resample_fftprep_pallas_batch``) can fold
    the multiply into its gather instead of booking a full extra (M, N)
    HBM pass — f32 multiply commutes bitwise through the resampler's
    select/slice ladder, so results stay bit-identical.  Only meaningful
    together with ``return_device_split`` on the packed parity-split
    path; requesting it anywhere else raises (a silent no-op here would
    ship un-renormalized data into the plain search path)."""
    import time

    def _mark(label, *sync):
        if timings is None:
            return
        for arr in sync:
            # one-element host fetch as the barrier (execution is
            # in-order, so it fences everything queued before it)
            if hasattr(arr, "ravel"):
                np.asarray(arr.ravel()[:1])
        now = time.perf_counter()
        timings[label] = now - _mark.t0
        _mark.t0 = now

    _mark.t0 = time.perf_counter()

    n_unpadded = derived.n_unpadded
    nsamples = derived.nsamples
    fft_size = derived.fft_size
    window = cfg.window
    window_2 = int(0.5 * window + 0.5)
    if fft_size < window:
        raise ValueError(
            f"Running median window ({window} bins) is too wide for data set ({fft_size} bins)!"
        )

    seed = seed_from_samples(samples)

    # On TPU, ship the series as parity-split halves and use the packed
    # half-length cascade (ops/fft.py::rfft_packed_split) — half the
    # matmul FLOPs, with the stride-2 split done by numpy on HOST where
    # it is free. CPU/GPU keep the native full-length XLA FFT.
    use_packed = (
        not backend_has_native_fft()
        and nsamples % 2 == 0
        and n_unpadded % 2 == 0
    )
    if use_packed:
        half = nsamples // 2
        # upload only the unpadded data and zero-pad on device: the pad
        # is nsamples/n_unpadded-1 (2x at production padding 3.0) dead
        # zeros (50 MB padded vs 17 MB unpadded vs 2.1 MB packed per WU)
        pad = jnp.zeros(half - n_unpadded // 2, dtype=jnp.float32)
        if (
            packed_payload is not None
            and 2 * len(packed_payload) == n_unpadded
        ):
            # 4-bit path: ship the packed nibbles, split on device via a
            # host-exact table — byte b is (even=b>>4, odd=b&15), i.e.
            # the parity halves directly (ops/unpack.py)
            from .unpack import nibble_lut, unpack_4bit_split_device

            raw_d = jnp.asarray(np.asarray(packed_payload, dtype=np.uint8))
            lut_d = jnp.asarray(nibble_lut(packed_scale))
            ev_u, od_u = unpack_4bit_split_device(raw_d, lut_d)
            ev_d = jnp.concatenate([ev_u, pad])
            od_d = jnp.concatenate([od_u, pad])
        else:
            samples32 = np.asarray(samples, dtype=np.float32)
            ev_d = jnp.concatenate([jnp.asarray(samples32[0::2].copy()), pad])
            od_d = jnp.concatenate([jnp.asarray(samples32[1::2].copy()), pad])
        _mark("h2d+pad", ev_d, od_d)
        re, im = rfft_packed_split(ev_d, od_d)
    else:
        padded = jnp.zeros(nsamples, dtype=jnp.float32).at[:n_unpadded].set(
            jnp.asarray(samples, dtype=jnp.float32)
        )
        _mark("h2d+pad", padded)
        # split (real, imag) spectrum: complex64 never touches the device
        # (the TPU backend here has neither XLA FFT nor complex64; ops/fft.py)
        re, im = rfft_split(padded)
    _mark("rfft", re, im)

    with stage_scope("power"):
        ps = (re**2 + im**2).astype(jnp.float32)
        ps = ps.at[0].set(0.0)
    _mark("powerspectrum", ps)

    white_size = fft_size - window + 1
    # The sliding median is the one inherently serial stage: native C++ on
    # the host when built (sub-second), blocked device sort otherwise.
    # ERP_MEDIAN=device forces the fallback. The two differ by 1 ulp for
    # even windows (double vs float32 midpoint average) — log the choice so
    # cross-host result comparisons can account for it.
    from ..runtime import logging as erplog
    from .native_median import native_available, running_median_native

    requested = os.environ.get("ERP_MEDIAN", "")
    if requested == "native" and not native_available():
        # an explicit request must not silently degrade: the two paths
        # differ by 1 ulp for even windows, which matters to cross-host
        # result validation. RadpulError keeps run_search's exit-code
        # contract (mapped to its code, not a raw traceback).
        from ..runtime.errors import RADPUL_EVAL, RadpulError

        raise RadpulError(
            RADPUL_EVAL,
            "ERP_MEDIAN=native requested but liberp_rngmed.so is not built "
            "(run `make -C native`)",
        )
    use_native = requested != "device" and native_available()
    erplog.info(
        "Running median path: %s\n", "native C++" if use_native else "device"
    )
    if use_native:
        rm = jnp.asarray(_native_median_overlapped(ps, window))
    else:
        rm = running_median(ps, bsize=window, block=median_block)
    _mark("running median", rm)

    with stage_scope("whiten"):
        factor = jnp.sqrt(jnp.float32(np.log(2.0)) / rm)
        scale = jnp.ones(fft_size, dtype=jnp.float32)
        scale = scale.at[window_2 : window_2 + white_size].set(factor)
        re = re * scale
        im = im * scale
    _mark("whiten scale", re, im)

    # host-side GSL-compatible zap noise, scattered on device
    t_obs = derived.t_obs
    bin_ranges = (np.asarray(zap_ranges) * t_obs + 0.5).astype(np.uint32)
    sigma = float(np.sqrt(0.5) * np.sqrt(cfg.padding))
    idx, vals = zap_noise(seed, bin_ranges, sigma, fft_size)
    if len(idx):
        with stage_scope("whiten"):
            idx_dev = jnp.asarray(idx)
            re = re.at[idx_dev].set(
                jnp.asarray(np.real(vals).astype(np.float32))
            )
            im = im.at[idx_dev].set(
                jnp.asarray(np.imag(vals).astype(np.float32))
            )
    _mark("zap scatter", re, im)

    with stage_scope("whiten"):
        edge = jnp.zeros(window_2, dtype=jnp.float32)
        re = re.at[:window_2].set(edge).at[fft_size - window_2 :].set(edge)
        im = im.at[:window_2].set(edge).at[fft_size - window_2 :].set(edge)
    _mark("edge zero", re, im)

    if defer_renorm and not (use_packed and return_device_split):
        raise ValueError(
            "defer_renorm requires the packed device-split path "
            "(return_device_split=True on a backend without native FFT "
            "and even lengths); the host-array paths always renormalize"
        )
    renorm = jnp.sqrt(jnp.float32(nsamples))
    if use_packed:
        ev_b, od_b = irfft_packed_split(re, im, n=nsamples)
        if not defer_renorm:
            ev_b = ev_b * renorm
            od_b = od_b * renorm
        _mark("irfft", ev_b, od_b)
        if return_device_split:
            return ev_b[: n_unpadded // 2], od_b[: n_unpadded // 2]
        out = np.empty(n_unpadded, dtype=np.float32)
        out[0::2] = np.asarray(ev_b[: n_unpadded // 2])
        out[1::2] = np.asarray(od_b[: n_unpadded // 2])
    else:
        back = irfft_split(re, im, nsamples) * renorm
        _mark("irfft", back)
        out = np.asarray(back[:n_unpadded], dtype=np.float32)
    _mark("d2h")
    return out
