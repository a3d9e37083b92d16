"""Fused resident-spectrum harmonic fold: one Pallas kernel for all levels.

The XLA path materializes the harmonic stage per template: the vmapped
``harmonic_sumspec`` lowers to a while loop whose spectrum-sized
dynamic-update-slice accumulators round-trip HBM once per row per level —
the 2.5 GB/template "compiler-generated" bucket in ``COST_LEDGER.json``,
on top of the ~0.44 GB/template the attributed harmonic+power stages move
themselves.  This kernel replaces everything after the power spectrum
with ONE pass: every 512-bin output tile is produced from a single
VMEM-resident slab of the deinterleaved spectrum, folding all 16
multipliers and all 5 run-max levels before anything goes back to HBM.

Layout (and why the deinterleave happens in XLA, not in-kernel):

* ``ops/harmonic.py`` reads the spectrum exclusively through the
  per-multiplier deinterleave ``D_l[c, q] = ps[l*q + c]``.  Mosaic
  rejects the lane<->sublane reshape that computes ``D_l`` from a flat
  spectrum inside a kernel ("unsupported shape cast", probed on the v5e
  lowering), and strided vector slices are likewise unsupported — so the
  deinterleave stays in XLA, as 136 strided ``lax.slice`` rows fused
  with the |X|^2 power epilogue into the kernel's producer (see
  ``_deinterleave`` for why not transposes and why not a gather).  All
  16 ``D_l`` stack into ONE ``(T, 136, P)`` operand (sum l = 136 rows —
  exactly 17 sublane tiles, so every slab DMA is tile-aligned).

* The kernel's grid is ``(templates, column tiles)``.  Each step DMAs a
  ``(136, TQ+128)`` slab — all multipliers, one column window plus the
  halo the wrap/shift terms need — then the whole fold is static
  sublane slices and lane-shifted windows: row ``(l, r)`` of the
  running sum is ``slab[base_l + off_l(r)]`` (or the ``+1``-shifted row
  0 when ``off_l(r) == l``), levels accumulate in the C order
  ``_ACCUM_ORDER`` with the reference's group-sum-then-add association,
  and the per-phase run maxima become ``jnp.maximum`` trees over row
  windows (``cur = v[:, 1:TQ+1]``, ``prev = v[:, 0:TQ]`` for the
  negative-row wrap).  Bit parity with ``harmonic_sumspec`` is pinned by
  tests/test_pallas_sumspec.py.

* Outputs are five full-width planes ``(T, n_ph_k, Qpad)`` — every grid
  step writes a valid block, junk columns >= Q_k are sliced off in the
  XLA epilogue that reassembles the phase-major ``(T, 5, W)`` state.

Traffic: the deinterleaved operand is ~8.5x the spectrum (sum l / 16),
written once and read once (plus a 128/TQ halo), with the five planes
~1x back — ~20x spectrum-sized transfers per template in total versus
the XLA path's several hundred, and nothing left for the compiler to
re-layout.  Column coordinates: the operand carries one leading zero
column (padded index p = q + 1), so tile j's DMA starts at the
128-aligned p = j*TQ and lane i covers global column q = j*TQ + i - 1 —
the q = -1 lane reads the zero column, which is exactly the reference's
"column -1 reads 0" wrap semantics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime.devicecost import scoped
from .harmonic import _ACCUM_ORDER, level_layout, state_width

# column-tile width (lanes); multiple of 128 so every slab DMA start and
# extent stays tile-aligned
TQ = 512
# slab width: TQ output columns + halo for the previous-column wrap (-1)
# and the off_l(r)==l row shift (+1), rounded up to the 128 boundary
TQW = TQ + 128
# rows of the combined deinterleave: sum of multipliers 1..16
N_ROWS = sum(range(1, 17))  # 136 == 17 sublane tiles of 8


def _base(l: int) -> int:
    """First row of multiplier ``l`` in the combined deinterleave."""
    return l * (l - 1) // 2


def sumspec_applicable(fund_hi: int, harm_hi: int) -> bool:
    """Geometry fits the kernel's static contract.  The layout itself is
    size-generic (tiles are masked/sliced); only degenerate spectra are
    refused."""
    return fund_hi >= 1 and harm_hi >= 1


def _fold_geometry(fund_hi: int, harm_hi: int):
    """(Q, n_tiles, Qpad, P): column count of ops/harmonic.py, the tile
    grid over it, and the padded operand width."""
    Q = max(-(-harm_hi // 16), fund_hi)
    n_tiles = -(-Q // TQ)
    Qpad = n_tiles * TQ
    return Q, n_tiles, Qpad, Qpad + TQW


def _deinterleave(ps: jnp.ndarray, Q: int, P: int) -> jnp.ndarray:
    """Batched combined deinterleave: (T, L) spectra -> (T, 136, P) with
    rows ``base(l) + c`` holding ``D_l[c, q] = ps[l*q + c]`` at padded
    column ``p = q + 1`` (column 0 is the wrap zero; the tail is zero
    padding, exactly ``_phase_major_upsample``'s ``jnp.pad``).

    136 strided ``lax.slice`` rows, not reshape+transposes and not one
    gather: at production widths (Q ~ 2^17) XLA's layout assignment on a
    concat of 16 differently shaped transposes does not converge in any
    useful time (>15 min compiling for the v5e topology, probed), and
    the index-computed gather equivalent compiles fast but its TPU
    lowering books ~74 GB/template in the cost model.  Row-per-(l, c)
    strided slices compile in ~35 s and cost what the data actually is:
    the operand read once, the output written once (0.445 GB/template,
    same probe)."""
    T = ps.shape[0]
    need = 16 * (Q + 1)
    pad = max(0, need - ps.shape[1])
    ps_pad = jnp.pad(ps, ((0, 0), (0, pad)))[:, :need] if pad else ps[:, :need]
    parts = []
    for l in range(1, 17):
        for c in range(l):
            row = jax.lax.slice(
                ps_pad, (0, c), (T, c + (Q + 1 - 1) * l + 1), (1, l)
            )
            parts.append(row[:, None, :])  # (T, 1, Q+1)
    C = jnp.concatenate(parts, axis=1)  # (T, 136, Q+1)
    return jnp.pad(C, ((0, 0), (0, 0), (1, P - (Q + 1) - 1)))


def _fold_kernel_body(harm_hi: int, refs):
    """One grid step: fold the slab into the five level blocks."""
    c_ref, o0, o1, o2, o3, o4, slab, sem = refs
    outs = (o0, o1, o2, o3, o4)
    t = pl.program_id(0)
    j = pl.program_id(1)
    qa = j * TQ
    cp = pltpu.make_async_copy(c_ref.at[t, :, pl.ds(qa, TQW)], slab, sem)
    cp.start()
    cp.wait()

    TQV = TQ + 2  # lanes 0..TQ+1 <=> global columns qa-1 .. qa+TQ

    def row(l: int, r: int) -> jnp.ndarray:
        c = (l * r + 8) >> 4
        if c < l:
            return slab[_base(l) + c : _base(l) + c + 1, 0:TQV]
        return slab[_base(l) : _base(l) + 1, 1 : TQV + 1]

    # running sum init: multiplier 16 contributes off_16(r) = r
    running = [row(16, r) for r in range(16)]
    # per-row validity i = 16q + r < harm_hi at global column q = qa+i-1
    q_idx = (
        jax.lax.broadcasted_iota(jnp.int32, (1, TQV), 1) + (qa - 1)
    ) * 16
    valid = [q_idx + r < harm_hi for r in range(16)]

    def rows_max(vs):
        out = vs[0]
        for v in vs[1:]:
            out = jnp.maximum(out, v)
        return out

    # level 0: the raw spectrum row (multiplier 1, offset 0)
    outs[0][0, 0, :] = slab[0:1, 1 : TQ + 1][0, :]

    for k in range(1, 5):
        L = 16 >> k
        new_ls = [l for l in _ACCUM_ORDER if l % L == 0 and l % (L * 2) != 0]
        # C adds each level's terms as one left-to-right group
        # (hs_common.c:86,107,125,145) — keep that association
        for r in range(16):
            level = None
            for l in new_ls:
                term = row(l, r)
                level = term if level is None else level + term
            running[r] = running[r] + level
        masked = [
            jnp.where(valid[r], running[r], jnp.float32(0.0))
            for r in range(16)
        ]
        m = 1 << k
        h = m >> 1
        n_ph = 16 // m
        for p in range(n_ph):
            lo = m * p - h
            hi = m * p + h
            if lo < 0:
                prev = rows_max(masked[16 + lo :])[:, 0:TQ]
                cur = rows_max(masked[:hi])[:, 1 : TQ + 1]
                out_p = jnp.maximum(prev, cur)
            else:
                out_p = rows_max(masked[lo:hi])[:, 1 : TQ + 1]
            outs[k][0, p, :] = out_p[0, :]


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
    phase-major run-maxima of the 1/2/4/8/16-harmonic sums.  ``window_2``
    is unused (same observable-result argument as ``harmonic_sumspec``)
    but kept so both paths share a signature."""
    del window_2
    T = ps.shape[0]
    Q, n_tiles, Qpad, P = _fold_geometry(fund_hi, harm_hi)
    layout = level_layout(fund_hi)
    W = state_width(fund_hi)

    C = _deinterleave(ps, Q, P)

    out_shapes = [
        jax.ShapeDtypeStruct((T, n_ph, Qpad), jnp.float32)
        for n_ph, _ in layout
    ]
    out_specs = [
        pl.BlockSpec((1, n_ph, TQ), lambda t, j: (t, 0, j))
        for n_ph, _ in layout
    ]
    planes = pl.pallas_call(
        lambda *refs: _fold_kernel_body(harm_hi, refs),
        grid=(T, n_tiles),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((N_ROWS, TQW), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=interpret,
    )(C)

    rows = []
    for k, (n_ph, Qk) in enumerate(layout):
        if k == 0:
            r = planes[0][:, 0, :fund_hi]
        else:
            r = planes[k][:, :, :Qk].reshape(T, n_ph * Qk)
        rows.append(jnp.pad(r, ((0, 0), (0, W - r.shape[1]))))
    return jnp.stack(rows, axis=1)
