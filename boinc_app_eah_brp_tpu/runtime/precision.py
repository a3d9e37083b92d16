"""Precision observatory: per-stage numerical-error attribution, ULP
histograms, and candidate-recall scoring against the f64 oracle.

The platform can attribute device time per stage (the profiler trace
through ``runtime/devicecost.py``'s scopes) and wall time per stage
(``tools/step_report.py``); this module adds the third axis — WHERE
ERROR ENTERS.  It runs the real jitted pipeline and a
float64 reference over the same workunit slice, taps every registered
stage boundary (the ``runtime/devicecost.py`` stage registry is the
single source of stage names), and scores the final toplist against the
oracle's with the validator's exact matching semantics
(``io/validate.py``).  Reduced-precision pulsar searches are only
trustworthy when recall is measured against a high-precision oracle
(arXiv:2206.12205) and accelerator ports treat such error budgets as
first-class gates (arXiv:2211.13517) — ROADMAP item 2 (the bf16 fast
path) is explicitly gated on the numbers this module commits.

Three dtype lanes through one harness:

* **f32** — the production path itself: the lane's end-to-end output is
  the byte-identical ``run_bank`` result (the tap is observation-only,
  proven per audit by re-running the untapped loop and comparing bytes +
  recompile counters).
* **bf16 shadow** — the production stage functions with a
  round-to-nearest-even bfloat16 quantization applied at every
  spectrum-path stage boundary (resampled series, power spectrum,
  harmonic sums) INSIDE THE AUDIT ONLY.  This simulates bf16 *storage*
  with f32 accumulation — exactly the ROADMAP-item-2 porting plan —
  while the ``ERP_PRECISION=bf16`` production scaffold keeps raising
  NotImplementedError (pinned by tests/test_pallas_sumspec.py).
* **f64 oracle** — the reference algorithm carried out in float64.

**Decision pinning.** The pipeline's discrete decisions — LUT-sine
``del_t``, the ``n_steps`` shrink loop, nearest-neighbour gather indices
— are part of the *search definition* (the reference C computes them in
f32), not rounding error.  The f64 oracle therefore pins them to the
production f32 chain (``oracle/resample.py``) and carries only the VALUE
arithmetic (gathered samples, padding mean, FFT, powers, harmonic
accumulation, whitening factors) in f64.  A bf16 port would keep index
math in f32/int as well, so the lanes measure precisely the quantity
that gates it: rounding-error growth at fixed decisions.

**Error-growth waterfall.**  For each stage the audit reports

* ``cumulative`` — lane chain vs f64 chain at that tap (error carried
  in from upstream included), and
* ``introduced`` — the lane stage re-run ON THE F64 REFERENCE'S INPUT
  (hybrid substitution), isolating the error this stage adds.

The attribution block names the stage with the largest introduced error
— the stage that loses the candidates if precision is reduced.

Relative errors use a scaled denominator ``max(|ref|,
REL_FLOOR * max|ref|)`` so near-zero bins (zeroed DC, whitened edges)
cannot blow up the statistic; ULP distances are measured on the lane's
own dtype grid after rounding the f64 reference onto it.

This module is import-light: no jax at import time, so chip-free tools
(``tools/metrics_report.py``) can load the validators.  The harness
functions import jax lazily.
"""

from __future__ import annotations

import numpy as np

from . import devicecost, metrics

PRECISION_SCHEMA = "erp-precision-audit/1"
PRECISION_BASELINE_SCHEMA = "erp-precision-baseline/1"

# scaled-relative-error floor: |lane-ref| is divided by
# max(|ref|, REL_FLOOR * max|ref|) per compared array
REL_FLOOR = 1e-3

# ULP-distance histogram bucket upper bounds (first matching bound wins;
# anything beyond the last lands in the "inf" overflow)
ULP_BUCKETS = (0, 1, 2, 4, 8, 16, 64, 256, 1024, 4096)

# the audited numeric stage boundaries, in dataflow order.  Names ARE the
# devicecost ledger buckets; scopes are the erp.* named scopes that feed
# each bucket — devicecost.STAGES stays the single source of truth
# (checked by stage_registry_problems / tests/test_precision.py).
AUDIT_STAGES = (
    ("unpack", ("unpack",)),
    ("whiten", ("whiten", "median")),
    ("resample", ("resample", "fftprep")),
    ("fft+power", ("fft", "power")),
    ("harmonic-sum", ("harmonic", "sumspec")),
)
# the candidate-selection boundary: scored by recall/rank/Jaccard rather
# than elementwise error; its scope collapses into the merge bucket
TOPLIST_STAGE = ("toplist", ("merge",))

STAGE_NAMES = tuple(name for name, _ in AUDIT_STAGES)


def stage_registry_problems() -> list[str]:
    """Cross-check the audit's stage table against the devicecost
    registry; non-empty means the two observability layers disagree on
    stage names (a drift bug)."""
    problems = []
    for name, scopes in AUDIT_STAGES:
        for sc in scopes:
            if sc not in devicecost.STAGES:
                problems.append(f"audit scope {sc!r} not in devicecost.STAGES")
            elif devicecost.STAGES[sc] != name:
                problems.append(
                    f"audit stage {name!r} != ledger bucket "
                    f"{devicecost.STAGES[sc]!r} for scope {sc!r}"
                )
    for sc in TOPLIST_STAGE[1]:
        if sc not in devicecost.STAGES:
            problems.append(f"toplist scope {sc!r} not in devicecost.STAGES")
    return problems


# ---------------------------------------------------------------------------
# dtype grids: software bfloat16 + ordered-int ULP distance
# ---------------------------------------------------------------------------


def _bf16_bits(x: np.ndarray) -> np.ndarray:
    """int64[...] bfloat16 bit patterns of float32 input, rounded to
    nearest even (the hardware f32->bf16 conversion)."""
    f = np.asarray(x, dtype=np.float32)
    u = f.view(np.uint32).astype(np.uint64)
    rounded = (u + np.uint64(0x7FFF) + ((u >> np.uint64(16)) & np.uint64(1))) >> np.uint64(
        16
    )
    bits = (rounded & np.uint64(0xFFFF)).astype(np.int64)
    # keep NaN a NaN: rounding may carry a NaN mantissa into the inf
    # encoding; force a quiet-NaN pattern instead
    bits = np.where(np.isnan(f), np.int64(0x7FC1 | (bits & 0x8000)), bits)
    return bits


def quantize_bf16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded onto the bfloat16 grid (round to nearest
    even) — the bf16 shadow lane's per-stage storage quantization."""
    bits = _bf16_bits(x).astype(np.uint64) << np.uint64(16)
    return bits.astype(np.uint32).view(np.float32).reshape(np.shape(x))


def _ordered_ints(x: np.ndarray, dtype: str) -> np.ndarray:
    """Monotone int64 encoding of floats on the given grid: adjacent
    representable values differ by 1, so |a - b| is the ULP distance."""
    if dtype == "bf16":
        bits = _bf16_bits(x)
        sign = np.int64(1) << 15
        mask = (np.int64(1) << 16) - 1
    elif dtype == "f32":
        bits = (
            np.asarray(x, dtype=np.float32).view(np.uint32).astype(np.int64)
        )
        sign = np.int64(1) << 31
        mask = (np.int64(1) << 32) - 1
    else:
        raise ValueError(f"unknown ULP grid dtype {dtype!r}")
    return np.where(bits & sign, mask - bits, bits + sign)


def ulp_histogram(lane: np.ndarray, ref: np.ndarray, dtype: str) -> dict:
    """ULP-distance histogram of ``lane`` vs the f64 ``ref`` rounded onto
    the lane's grid.  Keys are stringified ULP_BUCKETS bounds plus
    ``"inf"`` overflow; values are counts (first matching bound wins)."""
    ref_on_grid = (
        quantize_bf16(np.asarray(ref, dtype=np.float32))
        if dtype == "bf16"
        else np.asarray(ref, dtype=np.float32)
    )
    d = np.abs(
        _ordered_ints(lane, dtype) - _ordered_ints(ref_on_grid, dtype)
    ).ravel()
    hist: dict[str, int] = {}
    remaining = d
    for b in ULP_BUCKETS:
        take = remaining <= b
        hist[str(b)] = int(np.count_nonzero(take))
        remaining = remaining[~take]
    hist["inf"] = int(len(remaining))
    return hist


def error_stats(lane: np.ndarray, ref: np.ndarray, dtype: str = "f32") -> dict:
    """Scaled relative-error statistics + ULP histogram of a lane array
    against its f64 reference."""
    lv = np.asarray(lane, dtype=np.float64).ravel()
    rv = np.asarray(ref, dtype=np.float64).ravel()
    if lv.shape != rv.shape:
        raise ValueError(f"shape mismatch {lv.shape} vs {rv.shape}")
    absdiff = np.abs(lv - rv)
    scale = float(np.max(np.abs(rv))) if len(rv) else 0.0
    if scale > 0.0:
        rel = absdiff / np.maximum(np.abs(rv), REL_FLOOR * scale)
    else:
        rel = absdiff  # all-zero reference: abs error IS the statistic
    return {
        "max_rel_err": float(np.max(rel)) if len(rel) else 0.0,
        "mean_rel_err": float(np.mean(rel)) if len(rel) else 0.0,
        "max_abs_err": float(np.max(absdiff)) if len(absdiff) else 0.0,
        "n_values": int(len(lv)),
        "ulp_hist": ulp_histogram(lane, ref, dtype),
    }


class _StatAcc:
    """Merges per-template error_stats into one per-stage aggregate."""

    def __init__(self):
        self.max_rel = 0.0
        self.max_abs = 0.0
        self.rel_sum = 0.0
        self.n = 0
        self.ulp: dict[str, int] = {}

    def add(self, stats: dict) -> None:
        self.max_rel = max(self.max_rel, stats["max_rel_err"])
        self.max_abs = max(self.max_abs, stats["max_abs_err"])
        self.rel_sum += stats["mean_rel_err"] * stats["n_values"]
        self.n += stats["n_values"]
        for k, v in stats["ulp_hist"].items():
            self.ulp[k] = self.ulp.get(k, 0) + v

    def result(self) -> dict:
        return {
            "max_rel_err": self.max_rel,
            "mean_rel_err": (self.rel_sum / self.n) if self.n else 0.0,
            "max_abs_err": self.max_abs,
            "n_values": self.n,
            "ulp_hist": dict(self.ulp),
        }


# ---------------------------------------------------------------------------
# the f64 reference chain (pure numpy; decisions pinned to the f32 path)
# ---------------------------------------------------------------------------


def _running_median_f64(x: np.ndarray, bsize: int) -> np.ndarray:
    """Sliding-window median in float64 — the high-precision counterpart
    of ``oracle/median.py::running_median`` (same definition, no f32
    casts)."""
    x = np.asarray(x, dtype=np.float64)
    n_out = len(x) - bsize + 1
    if n_out <= 0:
        raise ValueError("window larger than input")
    windows = np.lib.stride_tricks.sliding_window_view(x, bsize)
    half = bsize // 2
    if bsize % 2:
        return np.partition(windows, half, axis=1)[:, half]
    part = np.partition(windows, (half - 1, half), axis=1)
    return (part[:, half - 1] + part[:, half]) / 2.0


def whiten_f64(samples64: np.ndarray, derived, cfg) -> np.ndarray:
    """float64 whitening reference: the ``oracle/whiten.py`` algorithm
    (pad, rfft, periodogram, running median, sqrt(ln2/median) scale, edge
    zero, scaled irfft) with every value computation in float64.  The
    audit harness passes no zap ranges, so the taus2 noise stream (an
    algorithmic constant, not arithmetic) never enters."""
    n_unpadded = len(samples64)
    nsamples = derived.nsamples
    fft_size = derived.fft_size
    window = cfg.window
    window_2 = derived.window_2
    padded = np.zeros(nsamples, dtype=np.float64)
    padded[:n_unpadded] = samples64
    fft = np.fft.rfft(padded)
    ps = np.zeros(fft_size, dtype=np.float64)
    ps[1:] = fft.real[1:] ** 2 + fft.imag[1:] ** 2
    white_size = fft_size - window + 1
    rm = _running_median_f64(ps, window)
    factor = np.sqrt(np.log(2.0) / rm)
    fft[window_2 : window_2 + white_size] *= factor
    fft[:window_2] = 0.0
    if window_2 > 0:
        fft[fft_size - window_2 :] = 0.0
    back = np.fft.irfft(fft, n=nsamples) * np.sqrt(float(nsamples))
    return back[:n_unpadded]


def resample_f64(ts64: np.ndarray, rp) -> tuple[np.ndarray, int]:
    """float64 resample reference with PINNED f32 decisions: ``del_t``,
    ``n_steps`` and the nearest-neighbour indices come from the exact
    production chain (``oracle/resample.py``); the gathered values and
    the padding mean are float64."""
    from ..oracle.resample import compute_del_t, compute_n_steps

    del_t = compute_del_t(rp)
    n_steps = compute_n_steps(del_t, rp.nsamples_unpadded)
    i_f = np.arange(n_steps, dtype=np.float32)
    idx = (i_f - del_t[:n_steps] + np.float32(0.5)).astype(np.int32)
    np.clip(idx, 0, rp.nsamples_unpadded - 1, out=idx)
    gathered = ts64[idx]
    mean = float(np.mean(gathered)) if n_steps > 0 else 0.0
    out = np.full(rp.nsamples, mean, dtype=np.float64)
    out[:n_steps] = gathered
    return out, n_steps


def power_spectrum_f64(resampled64: np.ndarray, nsamples: int) -> np.ndarray:
    """float64 power-spectrum reference (rfft periodogram, 1/nsamples
    norm, zeroed DC — ``oracle/spectrum.py`` without the f32 casts)."""
    fft = np.fft.rfft(resampled64)
    ps = (fft.real**2 + fft.imag**2) / float(nsamples)
    ps[0] = 0.0
    return ps


def _level_sums_any(ps: np.ndarray, i: np.ndarray, k: int) -> np.ndarray:
    """``oracle/harmonic.py::_level_sums`` generalized over dtype: the
    same C association order, accumulating in the input's dtype."""
    levels = [(16,), (8,), (12, 4), (14, 10, 6, 2), (15, 13, 11, 9, 7, 5, 3, 1)]
    s = None
    for ls in levels[: 1 + k]:
        level = None
        for l in ls:
            term = ps[(i * l + 8) >> 4]
            level = term if level is None else (level + term).astype(ps.dtype)
        s = level if s is None else (s + level).astype(ps.dtype)
    return s


def harmonic_maxima(
    ps: np.ndarray, window_2: int, fund_hi: int, harm_hi: int
) -> np.ndarray:
    """(5, fund_hi) per-bin harmonic-sum run-maxima in the input's dtype
    — the natural-order sumspec (``oracle/harmonic.py``) without f32
    casts, so a float64 ps yields the float64 reference."""
    out = np.zeros((5, fund_hi), dtype=ps.dtype)
    out[0] = ps[:fund_hi]
    i = np.arange(window_2, harm_hi, dtype=np.int64)
    if len(i) == 0:
        return out
    for k in range(1, 5):
        S = _level_sums_any(ps, i, k)
        j = (i * (16 >> k) + 8) >> 4
        valid = j < fund_hi
        Sv, jv = S[valid], j[valid]
        if len(jv) == 0:
            continue
        starts = np.concatenate([[0], np.flatnonzero(np.diff(jv)) + 1])
        out[k][jv[starts]] = np.maximum.reduceat(Sv, starts)
    return out


def merge_maxima(sums_stack: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(M, T) from per-template natural-order sumspecs: strict ``>`` so
    earlier templates win ties — the device merge semantics
    (``models/search.py``), starting from the zero state."""
    M = np.zeros(sums_stack.shape[1:], dtype=sums_stack.dtype)
    T = np.zeros(sums_stack.shape[1:], dtype=np.int32)
    for t in range(sums_stack.shape[0]):
        better = sums_stack[t] > M
        M = np.where(better, sums_stack[t], M)
        T = np.where(better, np.int32(t), T)
    return M, T


def toplist_rows(
    M_nat: np.ndarray,
    T_nat: np.ndarray,
    bank_P: np.ndarray,
    bank_tau: np.ndarray,
    bank_psi0: np.ndarray,
    base_thr: np.ndarray,
    window_2: int,
    t_obs: float,
) -> list[tuple]:
    """Finalized candidate rows (validator column order: f0 Hz, P_b, tau,
    psi, power, fA, n_harm) from natural-order per-bin maxima — the exact
    production tie-break semantics (``oracle/toplist.py``).  float64
    maxima narrow to f32 at the toplist boundary, exactly where the
    CP_cand checkpoint record narrows them."""
    from ..io.checkpoint import empty_candidates
    from ..oracle.toplist import finalize_candidates, update_toplist_from_maxima

    cands = update_toplist_from_maxima(
        empty_candidates(),
        M_nat,
        T_nat,
        bank_P,
        bank_tau,
        bank_psi0,
        base_thr,
        window_2,
    )
    out = finalize_candidates(cands, t_obs)
    return [
        (
            float(c["f0"]) / float(t_obs),
            float(c["P_b"]),
            float(c["tau"]),
            float(c["Psi"]),
            float(c["power"]),
            float(c["fA"]),
            int(c["n_harm"]),
        )
        for c in out
    ]


def candidate_scores(
    rows_ref: list[tuple],
    rows_lane: list[tuple],
    t_obs: float,
    power_rtol: float = 1.5e-2,
) -> dict:
    """recall@tol / rank-stability / toplist-Jaccard of a lane's
    finalized candidates against the f64 oracle's, using the BOINC
    validator's matching semantics (``io/validate.py::CandidateDiff``:
    (bin, n_harm) identity, top-k strict, near-threshold tail tolerated
    as ``boundary``).

    * ``recall_at_tol``: fraction of the oracle's non-boundary candidates
      the lane recovers with power within ``power_rtol``.
    * ``rank_stability``: pairwise concordance (Kendall-style) of the
      matched candidates' power ordering.
    * ``jaccard``: |keys_ref ∩ keys_lane| / |keys_ref ∪ keys_lane| over
      ALL emitted candidates (boundary wobble included — the strictest
      set-level view).
    """
    from ..io.validate import _key, compare_candidate_rows

    diff = compare_candidate_rows(
        rows_ref, rows_lane, t_obs, power_rtol=power_rtol
    )
    keys_ref = {_key(r, t_obs) for r in rows_ref}
    keys_lane = {_key(r, t_obs) for r in rows_lane}
    union = keys_ref | keys_lane
    inter = keys_ref & keys_lane
    power_mism = {m[0] for m in diff.mismatches if m[1] == "power"}
    n_ref = diff.matched + len(diff.missing)
    recovered = diff.matched - sum(1 for k in power_mism if k in inter)
    recall = 1.0 if n_ref == 0 else recovered / n_ref

    ref_map = {_key(r, t_obs): r for r in rows_ref}
    lane_map = {_key(r, t_obs): r for r in rows_lane}
    matched = sorted(inter)
    conc = tot = 0
    max_power_rel = 0.0
    for idx_a in range(len(matched)):
        ka = matched[idx_a]
        pa_r, pa_l = ref_map[ka][4], lane_map[ka][4]
        max_power_rel = max(
            max_power_rel,
            abs(pa_l - pa_r) / max(abs(pa_r), 1e-30),
        )
        for idx_b in range(idx_a + 1, len(matched)):
            kb = matched[idx_b]
            dr = ref_map[ka][4] - ref_map[kb][4]
            dl = lane_map[ka][4] - lane_map[kb][4]
            if dr == 0.0 and dl == 0.0:
                conc += 1
            elif dr * dl > 0.0:
                conc += 1
            tot += 1
    rank_stability = 1.0 if tot == 0 else conc / tot
    return {
        "recall_at_tol": float(recall),
        "power_rtol": float(power_rtol),
        "rank_stability": float(rank_stability),
        "jaccard": 1.0 if not union else len(inter) / len(union),
        "oracle_n": len(rows_ref),
        "lane_n": len(rows_lane),
        "matched": diff.matched,
        "missing": len(diff.missing),
        "extra": len(diff.extra),
        "boundary": len(diff.boundary),
        "max_power_rel_err": float(max_power_rel),
    }


# ---------------------------------------------------------------------------
# oracle intermediates (chip-free; shared with tools/golden_ref.py --stages)
# ---------------------------------------------------------------------------


def oracle_stage_intermediates(
    ts_raw: np.ndarray,
    bank_P: np.ndarray,
    bank_tau: np.ndarray,
    bank_psi0: np.ndarray,
    cfg,
    derived,
) -> dict[str, np.ndarray]:
    """Per-stage f64 oracle intermediates for a (small) workunit slice:
    whitened series, per-template resampled series / power spectra /
    harmonic sumspecs, merged (M, T) maxima.  Pure numpy — no
    accelerator — so ``tools/golden_ref.py --stages`` can dump one
    committed reference the audit harness and future bf16 tests share."""
    from ..oracle.resample import ResampleParams

    ts64 = np.asarray(ts_raw, dtype=np.float64)
    white64 = whiten_f64(ts64, derived, cfg)
    n_t = len(bank_P)
    res = np.zeros((n_t, derived.nsamples), dtype=np.float64)
    ps = np.zeros((n_t, derived.fft_size), dtype=np.float64)
    sums = np.zeros((n_t, 5, derived.fundamental_idx_hi), dtype=np.float64)
    for t in range(n_t):
        rp = ResampleParams.from_template(
            bank_P[t],
            bank_tau[t],
            bank_psi0[t],
            derived.dt,
            derived.nsamples,
            derived.n_unpadded,
        )
        res[t], _ = resample_f64(white64, rp)
        ps[t] = power_spectrum_f64(res[t], derived.nsamples)
        sums[t] = harmonic_maxima(
            ps[t],
            derived.window_2,
            derived.fundamental_idx_hi,
            derived.harmonic_idx_hi,
        )
    M64, T64 = merge_maxima(sums)
    return {
        "ts_raw": np.asarray(ts_raw, dtype=np.float32),
        "whitened": white64,
        "resampled": res,
        "power": ps,
        "sumspec": sums,
        "maxima_M": M64,
        "maxima_T": T64,
    }


# ---------------------------------------------------------------------------
# the audit harness (imports jax lazily)
# ---------------------------------------------------------------------------


def _stage_fns(geom):
    """Separately-jitted production stage functions for one geometry —
    the audit's taps.  They call the SAME ops the production step traces
    (``ops/resample.py``, ``ops/spectrum.py``, ``ops/harmonic.py``), but
    as their own executables: the production ``run_bank`` dispatch window
    is never modified (observation-only tap)."""
    import jax

    from ..ops.harmonic import harmonic_sumspec
    from ..ops.resample import resample_split
    from ..ops.spectrum import power_spectrum_split

    if not geom.parity_split:
        raise ValueError("precision audit requires the parity-split pipeline")

    def rs(ev, od, tau, omega, psi0, s0):
        return resample_split(
            ev,
            od,
            tau,
            omega,
            psi0,
            s0,
            nsamples=geom.nsamples,
            n_unpadded=geom.n_unpadded,
            dt=geom.dt,
            use_lut=geom.use_lut,
            max_slope=geom.max_slope,
            lut_step=geom.lut_step,
            lut_tiles=geom.lut_tiles,
        )

    def ps(ev, od):
        return power_spectrum_split(ev, od, nsamples=geom.nsamples)

    def hs(spec):
        return harmonic_sumspec(
            spec,
            window_2=geom.window_2,
            fund_hi=geom.fund_hi,
            harm_hi=geom.harm_hi,
            natural=True,
        )

    return jax.jit(rs), jax.jit(ps), jax.jit(hs)


def _interleave(ev: np.ndarray, od: np.ndarray) -> np.ndarray:
    out = np.empty(len(ev) + len(od), dtype=np.float32)
    out[0::2] = ev
    out[1::2] = od
    return out


def _split(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    x = np.asarray(x, dtype=np.float32)
    return x[0::2].copy(), x[1::2].copy()


def _recompile_count() -> int | None:
    snap = metrics.snapshot()
    c = snap.get("counters", {}).get("jax.recompiles")
    return None if c is None else int(c["value"])


def _pack_nibbles(ts_raw: np.ndarray) -> np.ndarray:
    """uint8 packed payload from a 4-bit-quantized series (even nibble
    high, odd nibble low — ``ops/unpack.py`` byte order)."""
    v = np.asarray(np.round(ts_raw), dtype=np.int64)
    if v.min() < 0 or v.max() > 15 or len(v) % 2:
        raise ValueError("unpack stage needs an even-length 4-bit series")
    return ((v[0::2] << 4) | v[1::2]).astype(np.uint8)


def run_audit(
    ts_raw: np.ndarray,
    bank_P: np.ndarray,
    bank_tau: np.ndarray,
    bank_psi0: np.ndarray,
    cfg,
    derived,
    geom,
    lanes: tuple[str, ...] = ("f32", "bf16"),
    batch_size: int = 3,
) -> dict:
    """Run the full precision audit and return the ``erp-precision-audit/1``
    document.  ``ts_raw`` is the raw (4-bit-quantized, unwhitened)
    detector series; the harness whitens it (device f32 vs f64), runs
    every lane's per-template chain through the production stage
    functions, merges maxima, finalizes toplists and scores recall —
    plus the observation-only tap proof on the f32 lane (two ``run_bank``
    passes sharing one step cache: byte-identical (M, T), zero
    recompiles in the second dispatch window)."""
    import time

    import jax

    from ..io.checkpoint import empty_candidates  # noqa: F401 (toplist_rows)
    from ..models import search as msearch
    from ..ops import whiten as ops_whiten
    from ..ops.unpack import nibble_lut, unpack_4bit_split_device
    from ..oracle.resample import ResampleParams
    from ..oracle.stats import base_thresholds

    unknown = [ln for ln in lanes if ln not in ("f32", "bf16")]
    if unknown:
        raise ValueError(f"unknown audit lanes {unknown}")
    problems = stage_registry_problems()
    if problems:
        raise RuntimeError("; ".join(problems))

    ts_raw = np.asarray(ts_raw, dtype=np.float32)
    ts64 = ts_raw.astype(np.float64)
    base_thr = base_thresholds(cfg.fA, derived.fft_size)

    # --- WU-level stages: unpack + whiten (lane-independent: the bf16
    # shadow quantizes the per-template spectrum path only) -----------------
    payload = _pack_nibbles(ts_raw)
    ev_u, od_u = unpack_4bit_split_device(
        jax.numpy.asarray(payload), jax.numpy.asarray(nibble_lut(1.0))
    )
    unpacked = _interleave(np.asarray(ev_u), np.asarray(od_u))

    white32 = np.asarray(
        ops_whiten.whiten_and_zap(
            ts_raw, derived, cfg, np.zeros((0, 2), dtype=np.float64)
        ),
        dtype=np.float32,
    )
    white64 = whiten_f64(ts64, derived, cfg)

    # --- f64 oracle per-template chain -------------------------------------
    n_t = len(bank_P)
    rps = [
        ResampleParams.from_template(
            bank_P[t],
            bank_tau[t],
            bank_psi0[t],
            derived.dt,
            derived.nsamples,
            derived.n_unpadded,
        )
        for t in range(n_t)
    ]
    res64 = np.zeros((n_t, derived.nsamples), dtype=np.float64)
    ps64 = np.zeros((n_t, derived.fft_size), dtype=np.float64)
    sums64 = np.zeros((n_t, 5, geom.fund_hi), dtype=np.float64)
    for t in range(n_t):
        res64[t], _ = resample_f64(white64, rps[t])
        ps64[t] = power_spectrum_f64(res64[t], derived.nsamples)
        sums64[t] = harmonic_maxima(
            ps64[t], geom.window_2, geom.fund_hi, geom.harm_hi
        )
    M64, T64 = merge_maxima(sums64)
    rows64 = toplist_rows(
        M64, T64, bank_P, bank_tau, bank_psi0, base_thr, geom.window_2,
        derived.t_obs,
    )

    # --- lane chains through the jitted production stage taps --------------
    rs_fn, ps_fn, hs_fn = _stage_fns(geom)
    params = [
        msearch.template_params_host(
            bank_P[t], bank_tau[t], bank_psi0[t], geom.dt
        )
        for t in range(n_t)
    ]

    def dev_resample(ts32: np.ndarray, t: int) -> np.ndarray:
        ev, od = _split(ts32)
        tau, omega, psi, s0 = params[t]
        rev, rod = rs_fn(
            jax.numpy.asarray(ev), jax.numpy.asarray(od), tau, omega, psi, s0
        )
        return _interleave(np.asarray(rev), np.asarray(rod))

    def dev_ps(resampled32: np.ndarray) -> np.ndarray:
        ev, od = _split(resampled32)
        return np.asarray(ps_fn(jax.numpy.asarray(ev), jax.numpy.asarray(od)))

    def dev_hs(spec32: np.ndarray) -> np.ndarray:
        return np.asarray(hs_fn(jax.numpy.asarray(spec32)))

    eligible = slice(geom.window_2, None)
    lane_docs: dict[str, dict] = {}
    lane_sums32: dict[str, np.ndarray] = {}
    for lane in lanes:
        q = quantize_bf16 if lane == "bf16" else (lambda x: x)
        acc = {name: {"cum": _StatAcc(), "intro": _StatAcc()} for name, _ in AUDIT_STAGES}
        # WU-level stages (identical across lanes; the bf16 port keeps
        # the once-per-WU unpack/whiten chain in f32)
        st = error_stats(unpacked, ts64, dtype="f32")
        acc["unpack"]["cum"].add(st)
        acc["unpack"]["intro"].add(st)
        st = error_stats(white32, white64, dtype="f32")
        acc["whiten"]["cum"].add(st)
        acc["whiten"]["intro"].add(st)

        sums_lane = np.zeros((n_t, 5, geom.fund_hi), dtype=np.float32)
        for t in range(n_t):
            # cumulative chain: lane whiten -> lane stages, quantized at
            # every spectrum-path boundary for the bf16 shadow
            r_cum = q(dev_resample(white32, t))
            p_cum = q(dev_ps(r_cum))
            s_cum = q(dev_hs(p_cum))
            sums_lane[t] = s_cum
            acc["resample"]["cum"].add(error_stats(r_cum, res64[t], lane))
            acc["fft+power"]["cum"].add(
                error_stats(p_cum[1:], ps64[t][1:], lane)
            )
            acc["harmonic-sum"]["cum"].add(
                error_stats(
                    s_cum[:, eligible], sums64[t][:, eligible], lane
                )
            )
            # introduced: the lane stage on the f64 reference's input
            r_in = q(dev_resample(white64.astype(np.float32), t))
            acc["resample"]["intro"].add(error_stats(r_in, res64[t], lane))
            p_in = q(dev_ps(q(res64[t].astype(np.float32))))
            acc["fft+power"]["intro"].add(
                error_stats(p_in[1:], ps64[t][1:], lane)
            )
            s_in = q(dev_hs(q(ps64[t].astype(np.float32))))
            acc["harmonic-sum"]["intro"].add(
                error_stats(
                    s_in[:, eligible], sums64[t][:, eligible], lane
                )
            )
        lane_sums32[lane] = sums_lane

        stages = []
        for name, scopes in AUDIT_STAGES:
            row = acc[name]["cum"].result()
            row["stage"] = name
            row["scopes"] = list(scopes)
            row["introduced_rel_err"] = acc[name]["intro"].result()[
                "max_rel_err"
            ]
            stages.append(row)
        intro_sum = sum(s["introduced_rel_err"] for s in stages)
        waterfall = [
            {
                "stage": s["stage"],
                "introduced_rel_err": s["introduced_rel_err"],
                "cumulative_rel_err": s["max_rel_err"],
                "share": (
                    s["introduced_rel_err"] / intro_sum if intro_sum > 0 else 0.0
                ),
            }
            for s in stages
        ]
        worst = max(stages, key=lambda s: s["introduced_rel_err"])
        lane_docs[lane] = {
            "stages": stages,
            "waterfall": waterfall,
            "attribution": {
                "worst_stage": worst["stage"],
                "worst_introduced_rel_err": worst["introduced_rel_err"],
            },
        }

    # --- f32 lane: the production run itself + the observation-only tap
    # proof (two dispatch passes over one step cache) ------------------------
    step_cache: dict = {}
    M_ref, T_ref = msearch.run_bank(
        white32, bank_P, bank_tau, bank_psi0, geom,
        batch_size=batch_size, step_cache=step_cache,
    )
    M_ref, T_ref = np.asarray(M_ref), np.asarray(T_ref)
    rec_before = _recompile_count()
    M_tap, T_tap = msearch.run_bank(
        white32, bank_P, bank_tau, bank_psi0, geom,
        batch_size=batch_size, step_cache=step_cache,
    )
    rec_after = _recompile_count()
    M_tap, T_tap = np.asarray(M_tap), np.asarray(T_tap)
    byte_identical = (
        M_ref.tobytes() == M_tap.tobytes()
        and T_ref.tobytes() == T_tap.tobytes()
    )
    recompiles = (
        None
        if rec_before is None or rec_after is None
        else rec_after - rec_before
    )

    M32_nat = msearch.state_to_natural(M_tap, geom)
    T32_nat = msearch.state_to_natural(T_tap, geom)

    # tap-vs-production consistency: merging the per-template tap sums
    # must reproduce the production merge (same ops, same order)
    tap_vs_prod = 0.0
    if "f32" in lane_docs:
        M_tap_merge, _ = merge_maxima(lane_sums32["f32"])
        denom = np.maximum(
            np.abs(M32_nat),
            REL_FLOOR * max(float(np.max(np.abs(M32_nat))), 1e-30),
        )
        tap_vs_prod = float(
            np.max(np.abs(M_tap_merge - M32_nat) / denom)
        )
        lane_docs["f32"]["tap"] = {
            "byte_identical": bool(byte_identical),
            "recompiles_in_window": recompiles,
            "tap_vs_production_max_rel": tap_vs_prod,
        }

    # --- toplists + candidate scores ---------------------------------------
    for lane in lanes:
        if lane == "f32":
            rows_lane = toplist_rows(
                M32_nat, T32_nat, bank_P, bank_tau, bank_psi0, base_thr,
                geom.window_2, derived.t_obs,
            )
        else:
            M_l, T_l = merge_maxima(lane_sums32[lane])
            rows_lane = toplist_rows(
                M_l, T_l, bank_P, bank_tau, bank_psi0, base_thr,
                geom.window_2, derived.t_obs,
            )
        scores = candidate_scores(rows64, rows_lane, derived.t_obs)
        lane_docs[lane]["candidates"] = scores
        lane_docs[lane]["attribution"]["final_candidate_power_rel_err"] = (
            scores["max_power_rel_err"]
        )
        # per-stage gauges for the metrics registry (no-ops when the
        # metrics layer is disabled)
        for s in lane_docs[lane]["stages"]:
            metrics.gauge(
                metrics.labeled(
                    "precision.stage_rel_err", lane=lane, stage=s["stage"]
                )
            ).set(s["max_rel_err"])
        metrics.gauge(metrics.labeled("precision.recall", lane=lane)).set(
            scores["recall_at_tol"]
        )
        metrics.gauge(metrics.labeled("precision.jaccard", lane=lane)).set(
            scores["jaccard"]
        )

    return {
        "schema": PRECISION_SCHEMA,
        "generated_unix": int(time.time()),
        "backend": jax.default_backend(),
        "geometry": {
            "n_unpadded": int(derived.n_unpadded),
            "nsamples": int(derived.nsamples),
            "fft_size": int(derived.fft_size),
            "window_2": int(derived.window_2),
            "fund_hi": int(geom.fund_hi),
            "harm_hi": int(geom.harm_hi),
            "templates": int(n_t),
            "batch_size": int(batch_size),
        },
        "oracle": {"dtype": "f64", "decision_pinning": "f32"},
        "lanes": lane_docs,
    }


def attribute_template(
    ts: np.ndarray, geom, derived, P: float, tau: float, psi0: float
) -> dict:
    """Per-stage f32-vs-f64 error attribution for ONE template — the
    sentinel probe's drill-down (``runtime/health.py``): when a sentinel
    drifts beyond tolerance, this names the stage that introduced the
    error instead of just the template.  ``ts`` is the series the device
    actually searches (whitened or not); the reference recomputes each
    stage from the same input in float64 with pinned f32 decisions."""
    from ..oracle.resample import ResampleParams

    ts32 = np.asarray(ts, dtype=np.float32)
    ts64 = ts32.astype(np.float64)
    rp = ResampleParams.from_template(
        P, tau, psi0, derived.dt, derived.nsamples, derived.n_unpadded
    )
    r64, _ = resample_f64(ts64, rp)
    p64 = power_spectrum_f64(r64, derived.nsamples)
    s64 = harmonic_maxima(p64, geom.window_2, geom.fund_hi, geom.harm_hi)

    rs_fn, ps_fn, hs_fn = _stage_fns(geom)
    import jax.numpy as jnp

    from ..models.search import template_params_host

    tau32, omega, psi32, s0 = template_params_host(P, tau, psi0, geom.dt)
    ev, od = _split(ts32)
    rev, rod = rs_fn(jnp.asarray(ev), jnp.asarray(od), tau32, omega, psi32, s0)
    r32 = _interleave(np.asarray(rev), np.asarray(rod))
    rel = {}
    rel["resample"] = error_stats(r32, r64)["max_rel_err"]
    p_in = np.asarray(
        ps_fn(*(jnp.asarray(h) for h in _split(r64.astype(np.float32))))
    )
    rel["fft+power"] = error_stats(p_in[1:], p64[1:])["max_rel_err"]
    s_in = np.asarray(hs_fn(jnp.asarray(p64.astype(np.float32))))
    rel["harmonic-sum"] = error_stats(
        s_in[:, geom.window_2 :], s64[:, geom.window_2 :]
    )["max_rel_err"]
    worst = max(rel, key=rel.get)
    return {"stage_rel_err": rel, "worst_stage": worst}


# ---------------------------------------------------------------------------
# validators + baseline gate + regression diff (jax-free)
# ---------------------------------------------------------------------------


def _is_num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _validate_stats_row(s: dict, where: str, problems: list[str]) -> None:
    for f in ("max_rel_err", "mean_rel_err", "max_abs_err", "introduced_rel_err"):
        if not _is_num(s.get(f)) or s.get(f) < 0:
            problems.append(f"{where}: bad {f}")
    if not isinstance(s.get("n_values"), int) or s.get("n_values") < 0:
        problems.append(f"{where}: bad n_values")
    h = s.get("ulp_hist")
    if not isinstance(h, dict) or not h:
        problems.append(f"{where}: missing ulp_hist")
    elif any(
        not isinstance(v, int) or v < 0 for v in h.values()
    ) or "inf" not in h:
        problems.append(f"{where}: malformed ulp_hist")


def validate_precision_audit(doc: dict) -> list[str]:
    """Structural validation of an ``erp-precision-audit/1`` document;
    returns problems (empty = valid)."""
    problems: list[str] = []
    if not isinstance(doc, dict):
        return ["not a JSON object"]
    if doc.get("schema") != PRECISION_SCHEMA:
        problems.append(
            f"schema is {doc.get('schema')!r}, want {PRECISION_SCHEMA!r}"
        )
    if not isinstance(doc.get("backend"), str) or not doc.get("backend"):
        problems.append("missing backend")
    if not _is_num(doc.get("generated_unix")):
        problems.append("missing generated_unix")
    geo = doc.get("geometry")
    if not isinstance(geo, dict) or not all(
        isinstance(geo.get(k), int) and geo.get(k) > 0
        for k in ("n_unpadded", "nsamples", "fft_size", "templates")
    ):
        problems.append("malformed geometry")
    orc = doc.get("oracle")
    if not isinstance(orc, dict) or orc.get("dtype") != "f64":
        problems.append("oracle block must declare dtype f64")
    lanes = doc.get("lanes")
    if not isinstance(lanes, dict) or not lanes:
        return problems + ["missing lanes"]
    for lane, ld in lanes.items():
        if lane not in ("f32", "bf16"):
            problems.append(f"unknown lane {lane!r}")
            continue
        if not isinstance(ld, dict):
            problems.append(f"lane {lane}: not an object")
            continue
        stages = ld.get("stages")
        if not isinstance(stages, list) or [
            s.get("stage") for s in stages if isinstance(s, dict)
        ] != list(STAGE_NAMES):
            problems.append(
                f"lane {lane}: stages must cover {list(STAGE_NAMES)} in order"
            )
        else:
            for s in stages:
                _validate_stats_row(
                    s, f"lane {lane} stage {s.get('stage')}", problems
                )
        wf = ld.get("waterfall")
        if not isinstance(wf, list) or len(wf) != len(STAGE_NAMES):
            problems.append(f"lane {lane}: malformed waterfall")
        else:
            shares = [w.get("share") for w in wf]
            if not all(_is_num(v) and 0.0 <= v <= 1.0 for v in shares):
                problems.append(f"lane {lane}: waterfall shares out of range")
            elif sum(shares) > 0 and abs(sum(shares) - 1.0) > 1e-6:
                problems.append(f"lane {lane}: waterfall shares do not sum to 1")
        cand = ld.get("candidates")
        if not isinstance(cand, dict):
            problems.append(f"lane {lane}: missing candidates block")
        else:
            for f in ("recall_at_tol", "rank_stability", "jaccard"):
                v = cand.get(f)
                if not _is_num(v) or not 0.0 <= v <= 1.0:
                    problems.append(f"lane {lane}: bad candidates.{f}")
            for f in ("oracle_n", "lane_n", "matched", "missing", "extra"):
                if not isinstance(cand.get(f), int) or cand.get(f) < 0:
                    problems.append(f"lane {lane}: bad candidates.{f}")
        attr = ld.get("attribution")
        if not isinstance(attr, dict) or attr.get("worst_stage") not in STAGE_NAMES:
            problems.append(f"lane {lane}: malformed attribution")
        if lane == "f32":
            tap = ld.get("tap")
            if not isinstance(tap, dict) or not isinstance(
                tap.get("byte_identical"), bool
            ):
                problems.append("lane f32: missing observation-only tap proof")
            elif tap.get("recompiles_in_window") is not None and not isinstance(
                tap.get("recompiles_in_window"), int
            ):
                problems.append("lane f32: bad tap.recompiles_in_window")
    return problems


def validate_precision_baseline(doc: dict) -> list[str]:
    """Structural validation of ``erp-precision-baseline/1`` (the
    committed PRECISION_BASELINE.json); returns problems."""
    problems: list[str] = []
    if not isinstance(doc, dict):
        return ["not a JSON object"]
    if doc.get("schema") != PRECISION_BASELINE_SCHEMA:
        problems.append(
            f"schema is {doc.get('schema')!r}, want "
            f"{PRECISION_BASELINE_SCHEMA!r}"
        )
    if doc.get("lane") not in ("f32", "bf16"):
        problems.append("lane must be f32 or bf16")
    for f in ("recall_min", "jaccard_min", "rank_stability_min"):
        v = doc.get(f)
        if not _is_num(v) or not 0.0 <= v <= 1.0:
            problems.append(f"bad {f}")
    ceil = doc.get("stage_rel_err_max")
    if not isinstance(ceil, dict) or set(ceil) != set(STAGE_NAMES):
        problems.append(
            f"stage_rel_err_max must cover exactly {sorted(STAGE_NAMES)}"
        )
    elif any(not _is_num(v) or v <= 0 for v in ceil.values()):
        problems.append("stage_rel_err_max ceilings must be positive numbers")
    if "min_candidates" in doc and (
        not isinstance(doc["min_candidates"], int) or doc["min_candidates"] < 0
    ):
        problems.append("bad min_candidates")
    if "backend" in doc and (
        not isinstance(doc["backend"], str) or not doc["backend"]
    ):
        problems.append("bad backend")
    return problems


def evaluate_baseline(doc: dict, baseline: dict) -> list[str]:
    """Gate an audit document against the committed baseline: per-stage
    error ceilings, recall/Jaccard/rank floors, and the observation-only
    tap requirements.  Returns problems naming the offending stage or
    metric (empty = pass)."""
    problems = validate_precision_audit(doc)
    problems += validate_precision_baseline(baseline)
    if problems:
        return problems
    if baseline.get("backend") and baseline["backend"] != doc["backend"]:
        return []  # a cpu baseline says nothing about a TPU audit
    lane_name = baseline.get("lane", "f32")
    lane = doc["lanes"].get(lane_name)
    if lane is None:
        return [f"audit has no {lane_name} lane"]
    cand = lane["candidates"]
    for f, floor_key in (
        ("recall_at_tol", "recall_min"),
        ("jaccard", "jaccard_min"),
        ("rank_stability", "rank_stability_min"),
    ):
        if cand[f] < baseline[floor_key] - 1e-12:
            problems.append(
                f"candidates.{f} {cand[f]:.6g} below baseline floor "
                f"{baseline[floor_key]:.6g}"
            )
    floor_n = baseline.get("min_candidates", 1)
    if cand["oracle_n"] < floor_n:
        problems.append(
            f"oracle toplist has {cand['oracle_n']} candidates, need >= "
            f"{floor_n} for a meaningful recall score"
        )
    ceil = baseline["stage_rel_err_max"]
    for s in lane["stages"]:
        if s["max_rel_err"] > ceil[s["stage"]]:
            problems.append(
                f"stage {s['stage']}: max rel err {s['max_rel_err']:.3g} "
                f"exceeds baseline ceiling {ceil[s['stage']]:.3g}"
            )
    if lane_name == "f32":
        tap = lane["tap"]
        if not tap["byte_identical"]:
            problems.append(
                "tap proof failed: tapped run_bank output not byte-identical "
                "to the untapped reference"
            )
        rc = tap.get("recompiles_in_window")
        if rc is not None and rc != 0:
            problems.append(
                f"tap proof failed: {rc} recompiles in the tapped dispatch "
                "window (must be 0)"
            )
    return problems


def diff_docs(old: dict, new: dict, threshold: float = 0.25) -> list[str]:
    """Regression diff between two audit documents (same-backend only):
    any f32-lane stage whose cumulative max relative error grew beyond
    ``threshold`` (fractional), or any drop in recall/Jaccard, fails —
    naming the stage.  Returns problems (empty = no regression)."""
    problems = validate_precision_audit(old) + validate_precision_audit(new)
    if problems:
        return problems
    if old["backend"] != new["backend"]:
        return []  # cross-backend noise is not a regression signal
    o, n = old["lanes"].get("f32"), new["lanes"].get("f32")
    if o is None or n is None:
        return ["both documents need an f32 lane to diff"]
    o_stages = {s["stage"]: s for s in o["stages"]}
    for s in n["stages"]:
        base = o_stages[s["stage"]]["max_rel_err"]
        if s["max_rel_err"] > base * (1.0 + threshold) + 1e-12:
            problems.append(
                f"stage {s['stage']}: max rel err regressed "
                f"{base:.3g} -> {s['max_rel_err']:.3g} "
                f"(> {threshold:.0%} growth)"
            )
    for f in ("recall_at_tol", "jaccard", "rank_stability"):
        if n["candidates"][f] < o["candidates"][f] - 1e-12:
            problems.append(
                f"candidates.{f} regressed {o['candidates'][f]:.6g} -> "
                f"{n['candidates'][f]:.6g}"
            )
    return problems
