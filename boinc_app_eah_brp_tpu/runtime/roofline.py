"""FLOP/byte accounting and roofline model for the search pipeline.

The reference ships a GFLOPS model for exactly this purpose
(``cuda/app/cuda_utilities.c:163-182``: estimated per-template FLOPs over
measured wall to report device GFLOPS).  This module is the TPU analogue,
with the counts derived from the actual formulation (parity-split resample,
packed half-length MXU cascade, phase-major harmonic sum) instead of the
reference's kernel mix:

* per-stage FLOPs and HBM bytes per template, computed from the geometry
  and the FFT plan (``ops/fft.py::fft_plan``);
* chip peaks (MXU matmul throughput at the precision actually used, HBM
  bandwidth) from a small per-generation table;
* the attainable bound ``max(t_mxu, t_hbm)`` per stage and in total, and
  from a measured templates/sec the achieved MFU and the binding resource.

The MXU numbers are for ``Precision.HIGHEST`` (bf16x6 passes per float32
matmul — ``ops/fft.py::_PRECISION``): the cascade's matmul FLOPs cost 6x
their bf16 rate, which is the honest peak for this pipeline.

All byte counts assume float32 operands and count one HBM read of every
operand and one write of every result per pass, with elementwise chains
fused into the producing pass (XLA's observed behaviour); transposes are
counted as one read + one write.  This is a planning model, not a
simulator — its purpose is to name the binding resource and quantify the
gap, per VERDICT r03 ("no MFU or roofline accounting exists anywhere").
"""

from __future__ import annotations

from dataclasses import dataclass

from ..ops.fft import fft_plan

# Chip peaks per generation: (bf16 matmul FLOP/s, HBM bytes/s), from
# Google Cloud's TPU pages ("TPU v5e", "TPU v5p", "TPU v4", "TPU v6e").
# "cpu" labels CPU-backend runs only: its numbers are placeholders.
_CHIPS = {
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v4": (275e12, 1228e9),
    "v6e": (918e12, 1640e9),
    "cpu": (1e11, 50e9),
}

# ``jax.Device.device_kind`` -> generation key of ``_CHIPS``
_DEVICE_KINDS = {
    "TPU v5 lite": "v5e",
    "TPU v5": "v5p",
    "TPU v4": "v4",
    "TPU v6 lite": "v6e",
}

# Precision.HIGHEST on the MXU decomposes each float32 matmul into 6 bf16
# passes (bf16x6), so sustained f32 matmul peak is bf16 peak / 6.
_F32_MATMUL_PASSES = 6


def chip_generation(device=None) -> str:
    """The ``_CHIPS`` key of ``device`` (default: the first JAX device),
    from its ``device_kind``.  A CPU device is ``"cpu"``; an accelerator
    missing from the peak table raises rather than borrowing another
    chip's peaks."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return "cpu"
    try:
        return _DEVICE_KINDS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak-table entry for {device.platform} device_kind "
            f"{device.device_kind!r} (runtime/roofline.py::_DEVICE_KINDS)"
        ) from None


@dataclass(frozen=True)
class StageCost:
    name: str
    matmul_flops: float  # f32 matmul FLOPs (MXU, costed at bf16/6)
    vector_flops: float  # elementwise/VPU FLOPs (never binding here)
    hbm_bytes: float

    def t_mxu(self, peak_bf16: float) -> float:
        return self.matmul_flops * _F32_MATMUL_PASSES / peak_bf16

    def t_hbm(self, bw: float) -> float:
        return self.hbm_bytes / bw

    def bound(self, peak_bf16: float, bw: float) -> str:
        return "mxu" if self.t_mxu(peak_bf16) > self.t_hbm(bw) else "hbm"


def pipeline_costs(
    nsamples: int,
    n_unpadded: int,
    fund_hi: int,
    harm_hi: int,
    max_slope: float = 0.008,
) -> list[StageCost]:
    """Per-template stage costs for the production parity-split pipeline."""
    half_u = n_unpadded // 2  # per parity stream, unpadded
    half = nsamples // 2  # per parity stream, padded (= FFT length)
    f4 = 4.0  # float32 bytes

    # --- resample (ops/resample.py::resample_split): two parity streams.
    # Elementwise: phase + LUT sine + del_t + index (~12 flops/el).
    # Select: E+1 where-passes, each reading a window stream (~half_u els)
    # and rewriting the accumulator; windows of adjacent blocks overlap so
    # reads ~1x per pass. E = ceil(B*slope)+4 with B from the slope.
    from ..ops.resample import _select_block_size

    B = _select_block_size(2.0 * max_slope)
    E = int(B * 2.0 * max_slope + 0.999) + 4
    select_passes = E + 1
    resample = StageCost(
        "resample_split",
        matmul_flops=0.0,
        vector_flops=2 * half_u * (12 + select_passes),
        # per stream: ts read ~select_passes times (window streams), idx/e
        # intermediates, output write; plus the mean/mask pass
        hbm_bytes=2 * (select_passes + 3) * half_u * f4 + 2 * half * f4,
    )

    # --- packed half-length cascade (ops/fft.py::rfft_packed_split):
    # 4 real matmuls per stage over (re, im); first stage from real input
    # still runs the complex path (z = even + i*odd is already complex).
    stages = fft_plan(half)
    matmul_macs = half * sum(stages)  # complex MACs
    fft_matmul_flops = 8.0 * matmul_macs  # 4 real matmuls, 2 flops/MAC
    n_stage = len(stages)
    # passes over (re+im): n_stage matmul passes (read+write each) +
    # materialized transposes (the terminal inter-stage transpose is folded
    # into the last contraction's output permutation — ops/fft.py — so
    # n_stage-2 remain) + untangle (+flip reads) + power spectrum write.
    # Twiddles are computed on device from iotas (no table traffic).
    fft_bytes = (2 * n_stage + 2 * max(0, n_stage - 2) + 3) * 2 * half * f4
    fft = StageCost(
        "rfft_packed+power",
        matmul_flops=fft_matmul_flops,
        vector_flops=2 * 10.0 * half,  # twiddles + untangle + |X|^2
        hbm_bytes=fft_bytes,
    )

    # --- harmonic sum (ops/harmonic.py): 5 output spectra; the 2^k-harmonic
    # spectrum adds 2^k terms per fundamental bin (phase-major, no gathers).
    hs_adds = float(fund_hi) * (1 + 2 + 4 + 8 + 16)
    hs = StageCost(
        "harmonic_sum",
        matmul_flops=0.0,
        vector_flops=hs_adds,
        # reads the spectrum up to harm_hi once per harmonic order + writes
        hbm_bytes=(5 * harm_hi + 5 * fund_hi) * f4,
    )

    # --- batch merge: 5 x fund_hi max/argmax/where
    merge = StageCost(
        "merge(M,T)",
        matmul_flops=0.0,
        vector_flops=5.0 * fund_hi * 3,
        hbm_bytes=5 * fund_hi * f4 * 4,
    )
    return [resample, fft, hs, merge]


def roofline_report(
    nsamples: int,
    n_unpadded: int,
    fund_hi: int,
    harm_hi: int,
    max_slope: float = 0.008,
    measured_templates_per_sec: float | None = None,
    chip: str | None = None,
) -> dict:
    """The model as a JSON-serializable dict; fold into bench payloads."""
    chip = chip or chip_generation()
    peak_bf16, bw = _CHIPS[chip]
    costs = pipeline_costs(nsamples, n_unpadded, fund_hi, harm_hi, max_slope)
    stages = []
    t_total = 0.0
    mm_total = 0.0
    bytes_total = 0.0
    for c in costs:
        t_stage = max(c.t_mxu(peak_bf16), c.t_hbm(bw))
        t_total += t_stage
        mm_total += c.matmul_flops
        bytes_total += c.hbm_bytes
        stages.append(
            {
                "stage": c.name,
                "matmul_gflops": round(c.matmul_flops / 1e9, 2),
                "hbm_mbytes": round(c.hbm_bytes / 1e6, 1),
                "t_mxu_ms": round(c.t_mxu(peak_bf16) * 1e3, 3),
                "t_hbm_ms": round(c.t_hbm(bw) * 1e3, 3),
                "bound": c.bound(peak_bf16, bw),
            }
        )
    attainable = 1.0 / t_total if t_total > 0 else None
    out = {
        "chip": chip,
        "peak_bf16_tflops": peak_bf16 / 1e12,
        "f32_matmul_passes": _F32_MATMUL_PASSES,
        "hbm_gbytes_per_s": bw / 1e9,
        "per_template": stages,
        "attainable_templates_per_sec": round(attainable, 1),
        "model_bound": max(
            stages, key=lambda s: max(s["t_mxu_ms"], s["t_hbm_ms"])
        )["stage"],
    }
    # Cross-generation projection (BASELINE.md north star: "scale linearly
    # to v5p-64").  Template-bank parallelism is embarrassing: the only
    # cross-chip traffic is the recursive-doubling (M, T) max-merge
    # (parallel/sharded_search.py) — log2(n) rounds of 5*W float32+int32
    # (~10 MB) per *bank*, not per template — so n-chip throughput is
    # n * single-chip attainable to within that constant.
    def _attainable(p: float, b: float) -> float | None:
        t = sum(max(c.t_mxu(p), c.t_hbm(b)) for c in costs)
        return round(1.0 / t, 1) if t > 0 else None

    out["projection"] = {
        name: {"attainable_templates_per_sec_per_chip": _attainable(p, b)}
        for name, (p, b) in _CHIPS.items()
        if name != "cpu"
    }
    if measured_templates_per_sec:
        r = measured_templates_per_sec
        # MFU: achieved matmul FLOP rate (at the 6-pass f32 cost) over peak
        out["mfu"] = round(
            r * mm_total * _F32_MATMUL_PASSES / peak_bf16, 4
        )
        out["hbm_utilization"] = round(r * bytes_total / bw, 4)
        out["fraction_of_attainable"] = (
            round(r / attainable, 4) if attainable else None
        )
        # name the binding resource: if far below the model bound, the gap
        # is neither MXU nor HBM — it's layout/overhead (the thing to fix)
        out["bound"] = (
            out["model_bound"]
            if attainable and r > 0.5 * attainable
            else "layout/overhead (measured < 50% of model bound)"
        )
    return out
