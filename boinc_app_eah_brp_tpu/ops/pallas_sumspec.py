"""Fused harmonic fold: one Pallas kernel for the four summed levels, fed
by dense per-multiplier views of the spectrum.

``ops/harmonic.py`` reads the spectrum only through the per-multiplier
deinterleave ``D_l[c, q] = ps[l*q + c]``: the term of multiplier l at
index ``i = 16q + r`` is ``D_l[off_l(r), q]`` with ``off_l(r) = (l*r + 8)
>> 4`` (``off_l(r) == l`` reads ``D_l[0, q+1]``).  Rearranging a spectrum
by strides along the 128-wide lane axis is what a TPU does worst, so this
path never does:

* **Chunked columns.**  Column ``q = t + Ao*b`` puts t (``0 <= t < Ao``)
  on sublanes and the chunk b on the 128 lanes.  Then
  ``V_l[a, b] = ps[l*Ao*b + a]`` is one reshape of a spectrum prefix and
  one plain 2D transpose (``_views``), and row ``(l, c)`` of the fold over
  output rows ``[t0, t0 + n)`` is the sublane-strided load
  ``V_l[pl.ds(l*t0 + c, n, stride=l), :]``: every vreg has all 128 lanes
  live.  The 16 views are separate operands, never concatenated.

* **The kernel** (grid ``(templates, row tiles)``, sequential) DMAs one
  ``(l*TT [+8], 128)`` window of every view per step, double-buffered by
  hand (the next step's windows are in flight while this one folds), and
  folds it 8 rows at a time: the 16 phases' running sums are ``(8, 128)``
  tiles, levels accumulate in ``_ACCUM_ORDER`` with the reference's
  group-then-add association, the ``i < harm_hi`` mask is computed from
  the chunked q, and the run maxima are ``jnp.maximum`` trees over the
  phase tiles.  Bit parity with ``harmonic_sumspec`` is pinned by
  tests/test_pallas_sumspec.py.

* **Halos.**  The ``off_l(r) == l`` term of a tile's last row is the first
  row of the next window: the 8 rows after each window of ``l <= 8`` (no
  larger l has such a term) come in with it, and for the last tile they
  are the views' first rows one lane on (``nxt``).  The wrap of phase 0
  into column ``q - 1`` is a one-row sublane shift, carried from tile to
  tile; at a chunk's first row it is the previous chunk's last row, which
  the first tile of each template folds from an 8-row halo window of the
  views' last rows and shifts one lane on (lane 0, column -1, reads 0).

* **Output.**  Levels 1-4 leave the kernel in the chunked ``(Ao, 128)``
  layout and are transposed back to the phase-major ``(5, W)`` state rows
  (``level_layout``); level 0 is the spectrum's first ``fund_hi`` bins.

``Ao`` comes from the geometry: the Q + 1 columns over 128 lanes, rounded
up to whole row tiles of at most ``TT_MAX`` rows, so a short band pays
only a few rows of padding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime.devicecost import scoped
from .harmonic import _ACCUM_ORDER, level_layout, state_width

LANES = 128
SUB = 8  # rows folded at a time: one (8, 128) tile per phase
TT_MAX = 128  # most output rows of one grid step
# the multipliers with an off_l(r) == l term (r >= 16 - 8/l): 1..8
SPILL_L = 8


def sumspec_applicable(fund_hi: int, harm_hi: int) -> bool:
    """Geometry fits the kernel's static contract.  The layout itself is
    size-generic (tiles are masked/sliced); only degenerate spectra are
    refused."""
    return fund_hi >= 1 and harm_hi >= 1


def fold_geometry(fund_hi: int, harm_hi: int) -> tuple[int, int]:
    """(Ao, TT): the rows of a chunk, 128 * Ao > Q (the column count of
    ops/harmonic.py), so that the last real column's q + 1 term lies
    inside the views; and the rows of one grid step, a multiple of 8 of
    which Ao is a whole number."""
    Q = max(-(-harm_hi // 16), fund_hi)
    a = -(-(Q + 1) // LANES)
    n_t = -(-a // TT_MAX)
    tt = -(-a // n_t)
    tt = -(-tt // SUB) * SUB
    return n_t * tt, tt


def _spill(l: int) -> int:
    return SUB if l <= SPILL_L else 0


def _views(ps: jnp.ndarray, Ao: int) -> list[jnp.ndarray]:
    """The 16 dense views ``V_l[t, a, b] = ps[t, l*Ao*b + a]``, (T, l*Ao,
    128) each: a reshape of a spectrum prefix and one 2D transpose (the
    spectrum is zero-padded where it is shorter, as ``_phase_major_upsample``
    pads it)."""
    T, n = ps.shape
    need = LANES * 16 * Ao
    if n < need:
        ps = jnp.pad(ps, ((0, 0), (0, need - n)))
    return [
        ps[:, : LANES * l * Ao].reshape(T, LANES, l * Ao).transpose(0, 2, 1)
        for l in range(1, 17)
    ]


def _fold(load, q, harm_hi: int, emit) -> None:
    """Fold 8 rows of all 16 phases: ``load(l, c)`` gives the (8, 128)
    rows ``D_l[c, q]`` (``c == l`` the ``D_l[0, q + 1]`` rows), ``q`` the
    (8, 128) column index; ``emit(k, masked)`` takes each level's 16
    masked running sums in turn."""
    # i = 16q + r < harm_hi  <=>  q < ceil((harm_hi - r) / 16)
    valid = [q < -(-(harm_hi - r) // 16) for r in range(16)]
    running = [load(16, r) for r in range(16)]  # off_16(r) = r
    for k in range(1, 5):
        L = 16 >> k
        new_ls = [l for l in _ACCUM_ORDER if l % L == 0 and l % (L * 2) != 0]
        rows: dict[tuple[int, int], jnp.ndarray] = {}

        def term(l, r):
            c = (l * r + 8) >> 4
            if (l, c) not in rows:
                rows[(l, c)] = load(l, c)
            return rows[(l, c)]

        for r in range(16):
            # C adds each level's terms as one left-to-right group
            # (hs_common.c:86,107,125,145) — keep that association
            level = None
            for l in new_ls:
                level = term(l, r) if level is None else level + term(l, r)
            running[r] = running[r] + level
        emit(k, [jnp.where(valid[r], running[r], jnp.float32(0.0))
                 for r in range(16)])


def _rows_max(vs):
    out = vs[0]
    for v in vs[1:]:
        out = jnp.maximum(out, v)
    return out


def _wrap_max(masked, k):
    """Level k's maxima over phase 0's wrapped rows (column q - 1's)."""
    return _rows_max(masked[16 - (1 << (k - 1)):])


def _fold_kernel(*refs, harm_hi: int, Ao: int, TT: int):
    """Grid step (template t, row tile j): rows [j*TT, (j+1)*TT) of every
    chunk, all four summed levels."""
    views = refs[:16]
    outs = refs[16:20]
    bufs = refs[20:36]
    halos = refs[36:52]
    nxt, pcar, sem = refs[52:]
    n_t = Ao // TT
    t = pl.program_id(0)
    j = pl.program_id(1)
    step = t * n_t + j
    slot = step % 2

    def dma(tt, jj, sl, op):
        """Start or wait for grid step (tt, jj)'s windows in slot sl."""
        last = jj == n_t - 1

        def copy(l, src_row, n, dst, i):
            return pltpu.make_async_copy(
                views[l - 1].at[tt, pl.ds(src_row, n)],
                dst.at[sl, pl.ds(0, n)], sem.at[sl, i])

        for l in range(1, 17):
            row = l * jj * TT
            if _spill(l) and n_t > 1:
                @pl.when(jnp.logical_not(last))
                def _(l=l, row=row):
                    getattr(copy(l, row, l * TT + _spill(l), bufs[l - 1],
                                 l - 1), op)()

                @pl.when(last)
                def _(l=l, row=row):
                    getattr(copy(l, row, l * TT, bufs[l - 1], l - 1), op)()
            else:
                getattr(copy(l, row, l * TT, bufs[l - 1], l - 1), op)()

        @pl.when(jj == 0)
        def _():
            for l in range(1, 17):
                getattr(copy(l, l * (Ao - SUB), l * SUB, halos[l - 1],
                             15 + l), op)()

    @pl.when(step == 0)
    def _():
        dma(t, j, slot, "start")

    @pl.when(step + 1 < pl.num_programs(0) * n_t)
    def _():
        wrap = j + 1 == n_t
        dma(jnp.where(wrap, t + 1, t), jnp.where(wrap, 0, j + 1), 1 - slot,
            "start")

    dma(t, j, slot, "wait")

    row_i = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 0)
    lane_i = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 1)
    q0 = row_i + Ao * lane_i  # column of row t = 0 + sublane

    @pl.when(j == 0)
    def _():
        # the views' first rows one lane on: D_l[0, Ao*(b + 1)], the
        # off_l(r) == l term of each chunk's last row (lane 127 reads a
        # column past Q, which the mask zeroes)
        for l in range(1, SPILL_L + 1):
            nxt[l - 1] = pltpu.roll(bufs[l - 1][slot, 0:SUB, :], LANES - 1, 1)
            halos[l - 1][slot, l * SUB : l * SUB + 1, :] = nxt[l - 1, 0:1, :]

        # the wrap into a chunk's first row: the previous chunk's last row
        def emit(k, masked):
            w = pltpu.roll(_wrap_max(masked, k), 1, 1)
            pcar[k - 1] = jnp.where(lane_i == 0, jnp.float32(0.0), w)

        _fold(lambda l, c: halos[l - 1][slot, pl.ds(c, SUB, stride=l), :],
              q0 + (Ao - SUB), harm_hi, emit)

    @pl.when(j == n_t - 1)
    def _():
        for l in range(1, SPILL_L + 1):
            bufs[l - 1][slot, l * TT : l * TT + 1, :] = nxt[l - 1, 0:1, :]

    def sub_block(s, carry):
        r0 = pl.multiple_of(s * SUB, SUB)
        carry = list(carry)

        def load(l, c):
            return bufs[l - 1][slot, pl.ds(l * r0 + c, SUB, stride=l), :]

        def emit(k, masked):
            m = 1 << k
            h = m >> 1
            wrap = _wrap_max(masked, k)
            # the wrapped rows at column q - 1: one row up, the first row
            # from the previous block's last
            prev = jnp.where(row_i == 0, pltpu.roll(carry[k - 1], 1, 0),
                             pltpu.roll(wrap, 1, 0))
            carry[k - 1] = wrap
            o = outs[k - 1]
            o[0, 0, pl.ds(r0, SUB), :] = jnp.maximum(prev, _rows_max(masked[:h]))
            for p in range(1, 16 // m):
                o[0, p, pl.ds(r0, SUB), :] = _rows_max(
                    masked[m * p - h : m * p + h])

        _fold(load, q0 + (j * TT + r0), harm_hi, emit)
        return tuple(carry)

    carry = jax.lax.fori_loop(
        0, TT // SUB, sub_block, tuple(pcar[k] for k in range(4)))
    for k in range(4):
        pcar[k] = carry[k]


def _fold_planes(views, harm_hi: int, Ao: int, TT: int, interpret: bool):
    """The kernel over the 16 views: levels 1-4 as (T, n_ph, Ao, 128)
    planes in the chunked layout."""
    T = views[0].shape[0]
    phases = [16 >> k for k in range(1, 5)]
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_fold_kernel, harm_hi=harm_hi, Ao=Ao, TT=TT),
        grid=(T, Ao // TT),
        in_specs=[any_spec] * 16,
        out_specs=[
            pl.BlockSpec((1, n_ph, TT, LANES), lambda t, j: (t, 0, j, 0))
            for n_ph in phases
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, n_ph, Ao, LANES), jnp.float32)
            for n_ph in phases
        ],
        scratch_shapes=[
            *(pltpu.VMEM((2, l * TT + _spill(l), LANES), jnp.float32)
              for l in range(1, 17)),
            *(pltpu.VMEM((2, l * SUB + _spill(l), LANES), jnp.float32)
              for l in range(1, 17)),
            pltpu.VMEM((SPILL_L, SUB, LANES), jnp.float32),  # nxt
            pltpu.VMEM((4, SUB, LANES), jnp.float32),  # pcar
            pltpu.SemaphoreType.DMA((2, 32)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(TT),
        ),
        interpret=interpret,
    )(*views)


@functools.partial(
    jax.jit, static_argnames=("window_2", "fund_hi", "harm_hi", "interpret")
)
@scoped("sumspec")
def sumspec_pallas_batch(
    ps: jnp.ndarray,  # float32[T, L] batched power spectra
    *,
    window_2: int,
    fund_hi: int,
    harm_hi: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused batched replacement for
    ``vmap(harmonic_sumspec(..., natural=False))``: float32[T, 5, W]
    phase-major run-maxima of the 1/2/4/8/16-harmonic sums, bit for bit.
    ``window_2`` is unused (same observable-result argument as
    ``harmonic_sumspec``) but kept so both paths share a signature."""
    del window_2
    T = ps.shape[0]
    Ao, TT = fold_geometry(fund_hi, harm_hi)
    layout = level_layout(fund_hi)
    W = state_width(fund_hi)
    planes = _fold_planes(_views(ps, Ao), harm_hi, Ao, TT, interpret)
    rows = [jnp.pad(ps[:, :fund_hi], ((0, 0), (0, W - fund_hi)))]
    for plane, (n_ph, Qk) in zip(planes, layout[1:]):
        r = plane.transpose(0, 1, 3, 2).reshape(T, n_ph, LANES * Ao)
        r = r[:, :, :Qk].reshape(T, n_ph * Qk)
        rows.append(jnp.pad(r, ((0, 0), (0, W - n_ph * Qk))))
    return jnp.stack(rows, axis=1)


def _vmem_bytes(TT: int) -> int:
    """The kernel's scoped VMEM: the double-buffered windows, halos and
    output blocks, plus room for the compiler's own."""
    rows = sum(2 * (l * TT + _spill(l) + l * SUB + _spill(l))
               for l in range(1, 17))
    rows += 2 * 15 * TT + SPILL_L * SUB + 4 * SUB
    return rows * LANES * 4 + (16 << 20)
