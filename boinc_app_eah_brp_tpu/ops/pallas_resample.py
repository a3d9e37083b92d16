"""Fused parity-stream resampler as a single Pallas TPU kernel.

The XLA formulation (``ops/resample.py::resample_split``) builds the
modulated index map, the per-block windows (vmapped dynamic slices) and the
shifted-select accumulation as separate HLO ops; XLA fuses the elementwise
chains, but the window tensor and the select accumulator still materialize
in HBM per template.  This kernel fuses the ENTIRE per-block chain — phase,
blocked LUT sine, ``del_t``, nearest index, window fetch, shifted select,
trailing-run scan — into one ``pallas_call``: per block of ``B`` outputs it
DMAs one window from each parity half of the time series into VMEM and
never touches HBM again until the output store.  HBM traffic per template
drops to ~read-ts-once + write-out-once.

Status: the resident chain (``resample_fftprep_pallas_batch``) is the
production step's resampler on a TPU backend wherever the geometry fits
(``models/search.py::use_pallas_resident``); other backends keep the XLA
resampler unless ``ERP_PALLAS_RESIDENT=1`` forces the chain, and the XLA
path stays the degradation ladder's fallback rung and the path of
geometries the gates below refuse.  The numerics transcribe
``_blocked_select_gather_split`` + ``_parity_stream`` op for op (same
float32 sequence), and ``tests/test_pallas_resample.py`` proves
bit-parity against the XLA path in interpret mode.  Mosaic's codegen on
the chip contracts a little differently from XLA-TPU: on one v5e the
batch's (M, T) agreed with the XLA step's to 5.7e-7 relative, with
identical candidates.

Applicability gates (checked by ``pallas_applicable``): the fixed kernel
block ``B_BLK`` must honor the select-window and LUT-window contracts for
the geometry's static bounds, and the tiled sine table must fit VMEM.

Template batching: pass 1 (``_launch_stream_batch``) runs the whole
batch as one launch over the grid (T, parity, block).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime.devicecost import scoped, stage_scope
from .sincos import (
    _TABLE_K,
    _tiled_tables,
)
from ..oracle.sincos import (
    ERP_SINCOS_LUT_RES_F,
    ERP_SINCOS_LUT_RES_F_INV,
    ERP_TWO_PI,
    ERP_TWO_PI_INV,
)

B_BLK = 4096  # outputs per kernel block (lane-aligned: 32 x 128)
SUB = B_BLK // 128  # sublane rows per output block in the (SUB, 128) tiling
LUT_W = 2048  # SMEM slab per LUT window DMA (tile-aligned: 2 x 1024)


def _tiled_lut_tables(lut_tiles: int):
    """The sincos tiled tables, zero-padded so every 1024-aligned LUT_W
    slab DMA stays in bounds (``base_l <= lut_limit`` rounded down to a
    tile, plus the LUT_W fetch).  The pad values are reachable only by the
    never-selected arms of the K-way select ladder."""
    sin_np, cos_np = _tiled_tables(lut_tiles)
    lut_len = (((lut_tiles * 64) >> 10) << 10) + LUT_W
    if sin_np.size < lut_len:
        pad = lut_len - sin_np.size
        sin_np = np.pad(sin_np, (0, pad))
        cos_np = np.pad(cos_np, (0, pad))
    return sin_np, cos_np


def _select_span(max_slope: float) -> int:
    """Residual span E for the fixed kernel block (the XLA path's formula
    at B = B_BLK): e in [0, E] wherever the slope contract holds."""
    return int(np.ceil(B_BLK * 2.0 * max_slope)) + 4


def pallas_applicable(
    max_slope: float, lut_step: float | None, lut_tiles: int
) -> bool:
    """True when the geometry's static bounds fit the kernel's fixed block:
    select span bounded (<= 96 shifted selects), LUT index drift within the
    K-wide table window, tiled table small enough for VMEM residency."""
    if lut_step is None:
        return False  # exact-sine path not transcribed
    if _select_span(max_slope) > 96:
        return False
    if B_BLK * 2.0 * lut_step + 2.0 > float(_TABLE_K - 1):
        return False
    if lut_tiles * 64 * 4 * 2 > 4 << 20:  # sin+cos tables <= 4 MiB VMEM
        return False
    return True


def _window_rows() -> int:
    """Rows (of 128 lanes) per aligned ts-window fetch.  The select ladder
    consumes flat elements [0, (SUB + 1) * 128) of the residual-normalized
    window (max static offset E//2 <= 48 plus the B_BLK block), and the
    1024-aligned DMA base can sit up to 1023 elements before the true
    window start, so the fetch rounds the sum up to whole 1024-element
    tiles (Mosaic only proves tile-aligned DMA slices legal)."""
    need = (SUB + 1) * 128 + 1023
    return (-(-need // 1024) * 1024) // 128


def _reduce_scalar(x, op):
    """Full f32 reduce of a (rows, 128) tile to a scalar: lane axis last —
    reducing the sublane axis first leaves a (1, 128) value whose
    replicated sublane Mosaic can reduce over lanes (the inverse order
    trips its no-replicated-axis-reductions rule).  Exact for min/max
    regardless of order."""
    return op(op(x, axis=0, keepdims=True), axis=-1)[0]


def _flat_shift(x, rows, lane_m, row_q, lane_iota):
    """Left-shift the row-major (rows, 128) tile ``x`` by
    ``row_q * 128 + lane_m`` flat elements: out_flat[i] = x_flat[i + s]
    wherever i + s < rows * 128.  Three ``tpu.dynamic_rotate``s plus one
    lane-masked select — pure data movement, so every surviving element
    keeps its exact source bits.  ``lane_m``/``row_q`` may be traced
    (residual normalization) or static (select-ladder offsets)."""
    from jax.experimental.pallas import tpu as pltpu

    if isinstance(lane_m, int) and isinstance(row_q, int) and not (
        lane_m or row_q
    ):
        return x
    if isinstance(lane_m, int):
        a = pltpu.roll(x, (128 - lane_m) % 128, 1) if lane_m else x
    else:
        a = pltpu.roll(x, (128 - lane_m) & 127, 1)
    if isinstance(row_q, int):
        b1 = pltpu.roll(a, (rows - row_q) % rows, 0) if row_q else a
    else:
        b1 = pltpu.roll(a, jax.lax.rem(rows - row_q, rows), 0)
    b2 = pltpu.roll(a, rows - 1 - row_q, 0)
    return jnp.where(lane_iota < 128 - lane_m, b1, b2)


def _stream_block_body(
    b,  # block index within the parity stream (traced scalar)
    tau, omega, psi0, s0, dt, parity, edge_lo, edge_hi,  # f32 scalars
    sin_ref,
    cos_ref,
    ts_e_ref,
    ts_o_ref,
    out_ref,
    lf_ref,
    win_e,
    win_o,
    sem_e,
    sem_o,
    sin_win,
    cos_win,
    sem_s,
    sem_c,
    *,
    E: int,
    lpad: int,
    half: int,
    n_unpadded: int,
    lut_limit: int,
    renorm: float | None = None,
):
    """Shared per-block computation: phase -> LUT sine -> del_t -> index ->
    window DMA -> shifted select -> output + trailing-run scalar, for one
    block of the batched kernel (template/parity/block from a 3-d grid).

    The block computes in the native (SUB, 128) tiling (flat output index
    j = row * 128 + lane).  Both dynamic windows — the ts parity streams
    and the K-wide LUT slabs — are DMA'd at 1024-aligned bases (Mosaic
    rejects DMA slices it cannot prove tile-aligned); the sub-tile residual
    is then shifted out in-register (``_flat_shift``) for the ts windows
    and absorbed into dynamic SMEM scalar offsets for the LUT slabs.

    ``renorm`` (trace-time constant) folds the whitening renormalization
    into the output store: with ``whiten_and_zap(defer_renorm=True)`` the
    time series arrives unscaled and every gathered sample (and both edge
    values) is multiplied by sqrt(nsamples) here instead — bitwise equal to
    gathering a prescaled series, since the scale commutes elementwise
    through the select ladder."""
    from jax.experimental.pallas import tpu as pltpu
    import jax.experimental.pallas as pl

    rows_l = _window_rows()
    # int32 iota + convert: Mosaic only lowers integer iota; the convert is
    # exact (j < 2^24) so the f32 flat indices are bit-identical
    jint = (
        jax.lax.broadcasted_iota(jnp.int32, (SUB, 128), 0) * 128
        + jax.lax.broadcasted_iota(jnp.int32, (SUB, 128), 1)
    )
    j = jint.astype(jnp.float32)
    m0 = (b * B_BLK).astype(jnp.float32)
    # i_f = 2*(m0 + j) + parity: global interleaved index, exact in f32
    i_f = (m0 + j) * jnp.float32(2.0) + parity
    t = i_f * dt
    phase = omega * t + psi0

    # --- blocked LUT sine (ops/sincos.py::sincos_lut_lookup, max_step path)
    scaled = jnp.float32(ERP_TWO_PI_INV) * phase
    iu = (scaled * jnp.float32(ERP_SINCOS_LUT_RES_F) + jnp.float32(0.5)).astype(
        jnp.int32
    )
    d = jnp.float32(ERP_TWO_PI) * (
        scaled - jnp.float32(ERP_SINCOS_LUT_RES_F_INV) * iu.astype(jnp.float32)
    )
    # Mosaic has no integer reductions: take the min in f32 (the pre-trunc
    # values; trunc-toward-zero is monotonic so trunc(min(x)) == min(trunc(x)),
    # and |iu| << 2^24 keeps every value exact)
    iu_min = _reduce_scalar(
        scaled * jnp.float32(ERP_SINCOS_LUT_RES_F) + jnp.float32(0.5), jnp.min
    ).astype(jnp.int32)
    start_l = jnp.clip(iu_min, 0, lut_limit)
    c = jnp.clip(iu - start_l, 0, _TABLE_K - 1)
    # stream the K-wide table windows through SMEM: Mosaic cannot lower
    # dynamically-indexed scalar loads from VMEM, and DMA slices must be
    # tile-aligned — so fetch the whole 1024-aligned LUT_W slab around the
    # window and read it at the dynamic residual offset (SMEM scalar reads
    # at traced indices are plain scalar ops)
    base_l = pl.multiple_of((start_l >> 10) << 10, 1024)
    rl = start_l - base_l
    cp_s = pltpu.make_async_copy(
        sin_ref.at[pl.ds(base_l, LUT_W)], sin_win, sem_s
    )
    cp_c = pltpu.make_async_copy(
        cos_ref.at[pl.ds(base_l, LUT_W)], cos_win, sem_c
    )
    cp_s.start()
    cp_c.start()
    cp_s.wait()
    cp_c.wait()
    ts_v = jnp.zeros_like(d)
    tc_v = jnp.zeros_like(d)
    for k in range(_TABLE_K):
        sel = c == k
        ts_v = jnp.where(sel, sin_win[rl + k], ts_v)
        tc_v = jnp.where(sel, cos_win[rl + k], tc_v)
    d2 = d * (jnp.float32(0.5) * d)
    s = ts_v + d * tc_v - d2 * ts_v

    step_inv = jnp.float32(1.0) / dt
    del_t = tau * s * step_inv - s0
    cond = (i_f - del_t) >= jnp.float32(n_unpadded - 1)
    idx = jnp.clip(
        (i_f - del_t + jnp.float32(0.5)).astype(jnp.int32), 0, n_unpadded - 1
    )

    # --- shifted-select gather (ops/resample.py::_blocked_select_gather_split)
    g = idx - (jnp.int32(b * B_BLK * 2) + jint * 2)
    # f32 max of exact small ints (|g| < n_unpadded << 2^24), cast back:
    # bitwise identical to the int reduction Mosaic can't lower
    g_max = _reduce_scalar(g.astype(jnp.float32), jnp.max).astype(jnp.int32)
    starts = (g_max - jnp.int32(E - 2)) & ~jnp.int32(1)
    e = g - starts

    # ts window fetch: 1024-aligned base (provably tile-aligned via the
    # shift arithmetic + multiple_of hint), residual normalized in-register
    s2 = (starts >> 1) + jnp.int32(b * B_BLK) + jnp.int32(lpad)
    row_base = pl.multiple_of((s2 >> 10) << 3, 8)
    sh = s2 - (row_base << 7)  # flat residual in [0, 1024)
    cp_e = pltpu.make_async_copy(
        ts_e_ref.at[pl.ds(row_base, rows_l)], win_e, sem_e
    )
    cp_o = pltpu.make_async_copy(
        ts_o_ref.at[pl.ds(row_base, rows_l)], win_o, sem_o
    )
    cp_e.start()
    cp_o.start()
    cp_e.wait()
    cp_o.wait()
    lane_l = jax.lax.broadcasted_iota(jnp.int32, (rows_l, 128), 1)
    q = sh >> 7
    m = sh & 127
    # normalized windows: flat element i == ts_parity[s2 + i]; slice to the
    # rows the ladder consumes (rounded to whole 8-sublane tiles —
    # tpu.dynamic_rotate rejects unaligned shapes) before the static shifts
    we = jax.lax.slice(
        _flat_shift(win_e[...], rows_l, m, q, lane_l), (0, 0), (SUB + 8, 128)
    )
    wo = jax.lax.slice(
        _flat_shift(win_o[...], rows_l, m, q, lane_l), (0, 0), (SUB + 8, 128)
    )

    lane_s = jax.lax.broadcasted_iota(jnp.int32, (SUB + 8, 128), 1)
    out = jnp.zeros((SUB, 128), dtype=jnp.float32)
    for off in range(E // 2 + 1):
        for par in (0, 1):
            r = 2 * off + par
            if r > E:
                break
            w = _flat_shift(we if par == 0 else wo, SUB + 8, off, 0, lane_s)
            out = jnp.where(
                e == r, jax.lax.slice(w, (0, 0), (SUB, 128)), out
            )
    oob = (e < 0) | (e > E)
    edge = jnp.where(idx <= 0, edge_lo, edge_hi)
    res = jnp.where(oob, edge, out)
    if renorm is not None:
        res = res * jnp.float32(renorm)
    out_ref[...] = res

    # trailing-run info: local index of the last False in cond (-1 if none),
    # masked to the real stream length (the tail block's lane padding runs
    # past `half` and must not contribute)
    valid = (jnp.int32(b * B_BLK) + jint) < jnp.int32(half)
    lf = _reduce_scalar(
        jnp.where(
            (~cond) & valid, jint.astype(jnp.float32), jnp.float32(-1.0)
        ),
        jnp.max,
    )
    lf_ref[0, :] = jnp.full((128,), lf)


def _batched_stream_kernel(
    params_ref,  # SMEM float32[T, 16]: whole params table, row per template
    sin_ref,
    cos_ref,
    ts_e_ref,
    ts_o_ref,
    out_ref,  # VMEM float32[1, 1, 1, SUB, 128]
    lf_ref,  # VMEM float32[1, 1, 1, 1, 128]
    win_e,
    win_o,
    sem_e,
    sem_o,
    sin_win,
    cos_win,
    sem_s,
    sem_c,
    **geom_kw,
):
    """The stream kernel: grid = (T, 2, n_blocks); the parity comes from
    the grid (program_id(1)), not from the params row, so one launch
    covers the whole batch.  The params table stays whole-array resident in SMEM and
    the kernel rows into it with program_id(0): a (1, 16) block window over
    a (T, 16) SMEM operand violates Mosaic's block-divisibility rule, so
    per-template scalar streaming must index, not window."""
    import jax.experimental.pallas as pl
    import jax.numpy as jnp

    t = pl.program_id(0)
    parity = pl.program_id(1).astype(jnp.float32)
    _stream_block_body(
        pl.program_id(2),
        params_ref[t, 0], params_ref[t, 1], params_ref[t, 2],
        params_ref[t, 3], params_ref[t, 4], parity,
        params_ref[t, 6], params_ref[t, 7],
        sin_ref, cos_ref, ts_e_ref, ts_o_ref,
        out_ref.at[0, 0, 0], lf_ref.at[0, 0, 0],
        win_e, win_o, sem_e, sem_o, sin_win, cos_win, sem_s, sem_c,
        **geom_kw,
    )


def _launch_stream_batch(
    ts_even,
    ts_odd,
    tau,
    omega,
    psi0,
    s0,
    *,
    n_unpadded: int,
    dt: float,
    max_slope: float,
    lut_tiles: int,
    renorm: float | None,
    interpret: bool,
):
    """Shared pass-1 launch for the batched entries: one pallas_call over
    the grid (T, parity, block) producing the raw blocked streams
    float32[T, 2, n_blocks, SUB, 128] plus the per-block trailing-run lanes
    float32[T, 2, n_blocks, 1, 128].  Per-template scalars travel as one
    (T, 16) whole-array SMEM table (streamed, never broadcast to (T, N))."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T = tau.shape[0]
    half = n_unpadded // 2
    E = _select_span(max_slope)
    rows_l = _window_rows()
    lpad = B_BLK + 2
    n_blocks = -(-half // B_BLK)
    rpad = n_blocks * B_BLK - half + rows_l * 128 + 2
    rpad += -(lpad + half + rpad) % 1024

    sin_np, cos_np = _tiled_lut_tables(lut_tiles)
    lut_limit = lut_tiles * 64

    ts_e_pad = jnp.pad(ts_even.astype(jnp.float32), (lpad, rpad)).reshape(
        -1, 128
    )
    ts_o_pad = jnp.pad(ts_odd.astype(jnp.float32), (lpad, rpad)).reshape(
        -1, 128
    )
    edge_lo = jnp.broadcast_to(ts_even[0], (T,))
    edge_hi = jnp.broadcast_to(ts_odd[(n_unpadded - 1) >> 1], (T,))
    params = jnp.stack(
        [
            tau.astype(jnp.float32),
            omega.astype(jnp.float32),
            psi0.astype(jnp.float32),
            s0.astype(jnp.float32),
            jnp.full((T,), jnp.float32(dt)),
            jnp.zeros((T,), jnp.float32),  # parity slot unused (grid-driven)
            edge_lo.astype(jnp.float32),
            edge_hi.astype(jnp.float32),
        ]
        + [jnp.zeros((T,), jnp.float32)] * 8,
        axis=1,
    )  # (T, 16)

    kern = functools.partial(
        _batched_stream_kernel,
        E=E,
        lpad=lpad,
        half=half,
        n_unpadded=n_unpadded,
        lut_limit=lut_limit,
        renorm=renorm,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(T, 2, n_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            # LUT tables live in ANY (HBM): the K-wide windows are DMA'd
            # into SMEM at arbitrary dynamic offsets, which VMEM-resident
            # memrefs cannot serve (slices must be tile-aligned)
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            # blocks whose trailing dims equal the array's (SUB, 128) /
            # (1, 128) trailing dims satisfy Mosaic's
            # (8, 128)-divisible-or-equal block rule
            pl.BlockSpec(
                (1, 1, 1, SUB, 128), lambda t, p, b: (t, p, b, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, 1, 1, 128), lambda t, p, b: (t, p, b, 0, 0)
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows_l, 128), jnp.float32),
            pltpu.VMEM((rows_l, 128), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SMEM((LUT_W,), jnp.float32),
            pltpu.SMEM((LUT_W,), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    out, lf = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((T, 2, n_blocks, SUB, 128), jnp.float32),
            jax.ShapeDtypeStruct((T, 2, n_blocks, 1, 128), jnp.float32),
        ],
        interpret=interpret,
    )(params, jnp.asarray(sin_np), jnp.asarray(cos_np), ts_e_pad, ts_o_pad)
    return out, lf, n_blocks


def _batch_stats(out, lf, *, T: int, half: int, n_blocks: int):
    """Global per-template stream statistics from the pass-1 outputs: the
    exact float32 op sequence of the XLA resampler's mean
    (``ops/resample.py``), so the resident chain's mean/n_steps bits match
    it.  Returns (g_e, g_o, n_steps, mask_e, mask_o, mean); the chain needs
    only (n_steps, mean) and lets XLA DCE the rest."""
    g = out.reshape(T, 2, n_blocks * B_BLK)[:, :, :half]  # (T, 2, half)
    lf_local = lf[:, :, :, 0, 0].astype(jnp.int32)  # (T, 2, n_blocks)
    offs = jnp.arange(n_blocks, dtype=jnp.int32)[None, None, :] * B_BLK
    lf_glob = jnp.max(
        jnp.where(lf_local >= 0, offs + lf_local, -1), axis=2
    )  # (T, 2)
    n_steps = jnp.maximum(2 * lf_glob[:, 0], 2 * lf_glob[:, 1] + 1)  # (T,)

    m2 = jnp.arange(half, dtype=jnp.int32) * 2
    mask_e = m2[None, :] < n_steps[:, None]
    mask_o = (m2 + 1)[None, :] < n_steps[:, None]
    g_e = g[:, 0]
    g_o = g[:, 1]
    total = jnp.sum(jnp.where(mask_e, g_e, 0.0), axis=1) + jnp.sum(
        jnp.where(mask_o, g_o, 0.0), axis=1
    )
    mean = total / n_steps.astype(jnp.float32)  # (T,)
    return g_e, g_o, n_steps, mask_e, mask_o, mean


def _fftprep_kernel(
    stats_ref,  # SMEM float32[T, 2]: [n_steps, mean] per template
    raw_ref,  # ANY float32[T, 2, n_blocks_raw, SUB, 128]: pass-1 streams
    out_ref,  # VMEM float32[1, 1, 1, SUB, 128]
    slab,  # VMEM float32[SUB, 128] scratch
    sem,
    *,
    n_blocks_raw: int,
):
    """Finalize pass of the resident chain: grid = (T, parity, out_block)
    over the padded FFT length.  Per block it DMAs one raw slab (when the
    block overlaps the unpadded stream), applies the head mask / mean fill
    in VMEM, and stores the series in its final FFT-prep layout — the
    masked-select + broadcast ladder the XLA epilogue used to book against
    HBM never materializes."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t = pl.program_id(0)
    p = pl.program_id(1)
    b = pl.program_id(2)

    @pl.when(b < n_blocks_raw)
    def _fetch():
        cp = pltpu.make_async_copy(raw_ref.at[t, p, b], slab, sem)
        cp.start()
        cp.wait()

    n_steps = stats_ref[t, 0].astype(jnp.int32)
    mean = stats_ref[t, 1]
    jloc = (
        jax.lax.broadcasted_iota(jnp.int32, (SUB, 128), 0) * 128
        + jax.lax.broadcasted_iota(jnp.int32, (SUB, 128), 1)
    )
    m = b * B_BLK + jloc
    # head mask: interleaved index 2m+p below the real stream length; the
    # lane padding past `half` and every block >= n_blocks_raw fall outside
    # (2m+p >= n_unpadded > n_steps) so the same select does the mean fill
    mask = (m * 2 + p) < n_steps
    out_ref[0, 0, 0] = jnp.where(mask, slab[...], mean)


@functools.partial(
    jax.jit,
    static_argnames=(
        "nsamples",
        "n_unpadded",
        "dt",
        "max_slope",
        "lut_step",
        "lut_tiles",
        "renorm",
        "interpret",
    ),
)
@scoped("resample")
def resample_fftprep_pallas_batch(
    ts_even: jnp.ndarray,
    ts_odd: jnp.ndarray,
    tau: jnp.ndarray,  # float32[T]
    omega: jnp.ndarray,
    psi0: jnp.ndarray,
    s0: jnp.ndarray,
    *,
    nsamples: int,
    n_unpadded: int,
    dt: float,
    max_slope: float,
    lut_step: float,
    lut_tiles: int = 1024,
    renorm: float | None = None,
    interpret: bool = False,
):
    """Resident resample -> FFT-prep chain, the bank step's resampler on
    TPU where ``models/search.py::use_pallas_resident`` admits the
    geometry: pass 1 is the batched stream launch
    (``_launch_stream_batch``); the only XLA ops between the kernels are
    the O(T) stream statistics (n_steps, mean), and pass 2
    (``_fftprep_kernel``) re-reads each raw tile once to emit the padded,
    mean-filled series directly in FFT-prep layout.  Returns (even, odd)
    float32[T, nsamples//2], bitwise identical at every geometry to a vmap
    of the XLA ``resample_split`` with the device (pairwise) mean: the
    head is the same select between the same gathered bits, the mean
    fill and the tail the same mean."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if not pallas_applicable(max_slope, lut_step, lut_tiles):
        raise ValueError("geometry outside the pallas kernel's gates")
    if n_unpadded % 2 or nsamples % 2:
        raise ValueError("resample_fftprep_pallas_batch requires even lengths")
    T = tau.shape[0]
    half = n_unpadded // 2
    half_out = nsamples // 2
    out, lf, n_blocks = _launch_stream_batch(
        ts_even, ts_odd, tau, omega, psi0, s0,
        n_unpadded=n_unpadded, dt=dt, max_slope=max_slope,
        lut_tiles=lut_tiles, renorm=renorm, interpret=interpret,
    )

    with stage_scope("fftprep"):
        _, _, n_steps, _, _, mean = _batch_stats(
            out, lf, T=T, half=half, n_blocks=n_blocks
        )
        stats = jnp.stack(
            [n_steps.astype(jnp.float32), mean], axis=1
        )  # (T, 2)

        n_blocks_out = -(-half_out // B_BLK)
        kern = functools.partial(_fftprep_kernel, n_blocks_raw=n_blocks)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(T, 2, n_blocks_out),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec(
                    (1, 1, 1, SUB, 128), lambda t, p, b: (t, p, b, 0, 0)
                ),
            ],
            scratch_shapes=[
                pltpu.VMEM((SUB, 128), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
            ],
        )
        (res,) = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct(
                    (T, 2, n_blocks_out, SUB, 128), jnp.float32
                ),
            ],
            interpret=interpret,
        )(stats, out)
        res = res.reshape(T, 2, n_blocks_out * B_BLK)[:, :, :half_out]
    return res[:, 0], res[:, 1]
