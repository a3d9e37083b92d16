"""Per-stage timing microbenchmark on the production geometry.

Times each pipeline stage (resample, rfft+power, harmonic summing, running
median) in isolation on the current backend, batch like the real bench, to
show where the per-template milliseconds go. The TPU analogue of profiling
the reference's per-kernel debug logs (``demod_binary_cuda.cu:435,...``).

Usage: python tools/stagebench.py [--batch 16] [--repeat 5]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _force(out):
    """Synchronize via a host fetch of one element (execution is in-order,
    so it fences everything queued before it)."""
    import jax

    leaves = jax.tree_util.tree_leaves(out)
    for leaf in leaves:
        np.asarray(leaf.ravel()[:1])


def timed(label: str, fn, *args, repeat: int = 5):
    out = fn(*args)
    _force(out)  # compile + warmup
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args)
    _force(out)
    dt = (time.perf_counter() - t0) / repeat
    print(f"{label:40s} {dt * 1e3:10.2f} ms", flush=True)
    return out, dt


def whiten_decompose(repeat: int, json_path: str | None) -> int:
    """Per-stage decomposition of the whitening pass (``ops/whiten.py``) on
    the production geometry: one cold pass (includes compiles) and
    ``repeat`` warm passes. With the persistent compilation cache on
    (the driver's default), a worker's first pass looks like the warm
    column here."""
    import json

    import jax

    from boinc_app_eah_brp_tpu.ops.whiten import whiten_and_zap
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig
    from boinc_app_eah_brp_tpu.runtime.driver import enable_compilation_cache

    enable_compilation_cache()
    print(f"backend={jax.default_backend()}", flush=True)
    cfg = SearchConfig(f0=400.0, padding=3.0, fA=0.08, window=1000, white=True)
    derived = DerivedParams.derive(1 << 22, 65.476, cfg)
    rng = np.random.default_rng(0)
    # production-faithful input: a 4-bit packed payload (the real WU
    # format), host-unpacked the same way the driver does — the packed
    # bytes also feed the device-unpack upload path (ops/unpack.py)
    from boinc_app_eah_brp_tpu.io.workunit import unpack_4bit

    packed = rng.integers(0, 256, derived.n_unpadded // 2, dtype=np.uint8)
    wu_scale = 7.0
    samples = unpack_4bit(packed, wu_scale, derived.n_unpadded)
    # a realistic zaplist density (the shipped one has 213 lines)
    lo = np.sort(rng.uniform(0.5, 190.0, 213))
    zap_ranges = np.stack([lo, lo + 0.05], axis=1)

    passes = []
    for i in range(repeat + 1):
        t = {}
        t0 = time.perf_counter()
        whiten_and_zap(
            samples, derived, cfg, zap_ranges, timings=t,
            packed_payload=packed, packed_scale=wu_scale,
        )
        t["TOTAL"] = time.perf_counter() - t0
        passes.append(t)
        label = "cold (compile)" if i == 0 else f"warm {i}"
        print(f"-- {label}")
        for k, v in t.items():
            print(f"   {k:20s} {v * 1e3:10.1f} ms", flush=True)

    # the production path (driver single-device): packed upload + device
    # nibble split + device-resident parity halves, no output d2h / host
    # interleave — time it warm, end to end, syncing via a one-element
    # fetch of each half
    t0 = time.perf_counter()
    out = whiten_and_zap(
        samples, derived, cfg, zap_ranges, return_device_split=True,
        packed_payload=packed, packed_scale=wu_scale,
    )
    if isinstance(out, tuple):
        for h in out:
            np.asarray(h.ravel()[:1])
    device_split_s = time.perf_counter() - t0
    print(f"-- warm device-split (production path) "
          f"{device_split_s * 1e3:10.1f} ms", flush=True)
    if json_path:
        warm = passes[1:] or passes
        avg = {
            k: sum(p[k] for p in warm) / len(warm) for k in warm[0]
        }
        with open(json_path, "w") as f:
            json.dump(
                {
                    "what": "whitening per-stage wall (s), production geometry "
                    "2^22 samples padding 3.0 window 1000; stages synced",
                    "backend": jax.default_backend(),
                    "cold_s": {k: round(v, 3) for k, v in passes[0].items()},
                    "warm_avg_s": {k: round(v, 3) for k, v in avg.items()},
                    "warm_passes": len(warm),
                    "warm_device_split_total_s": round(device_split_s, 3),
                },
                f,
                indent=1,
            )
        print(f"wrote {json_path}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--median", action="store_true", help="include running median")
    ap.add_argument(
        "--whiten", action="store_true",
        help="decompose the whitening pass instead of the search pipeline",
    )
    ap.add_argument("--json", default=None, help="write summary JSON here")
    args = ap.parse_args()

    if args.whiten:
        return whiten_decompose(args.repeat, args.json)

    import jax
    import jax.numpy as jnp

    from boinc_app_eah_brp_tpu.runtime.driver import enable_compilation_cache

    enable_compilation_cache()

    from boinc_app_eah_brp_tpu.models.search import (
        SearchGeometry,
        prepare_ts,
        template_params_host,
    )
    from boinc_app_eah_brp_tpu.ops.harmonic import harmonic_sumspec_batch
    from boinc_app_eah_brp_tpu.ops.median import running_median
    from boinc_app_eah_brp_tpu.ops.resample import resample_split
    from boinc_app_eah_brp_tpu.ops.spectrum import power_spectrum_split
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig

    print(f"backend={jax.default_backend()}", flush=True)

    from boinc_app_eah_brp_tpu.models.search import (
        lut_step_for_bank,
        max_slope_for_bank,
    )

    cfg = SearchConfig(f0=400.0, padding=3.0, fA=0.08, window=1000, white=True)
    n = 1 << 22
    derived = DerivedParams.derive(n, 65.476, cfg)
    B = args.batch
    print(
        f"nsamples={derived.nsamples} fft_size={derived.fft_size} "
        f"fund_hi={derived.fundamental_idx_hi} harm_hi={derived.harmonic_idx_hi} "
        f"batch={B}",
        flush=True,
    )

    rng = np.random.default_rng(0)
    ts_np = rng.uniform(0, 15, n).astype(np.float32)
    # parameter ranges of the shipped PALFA bank (P 660-2231 s, tau <= 0.335)
    P = rng.uniform(660.0, 2231.0, B)
    tau = rng.uniform(0.0, 0.335, B)
    psi = rng.uniform(0.0, 2 * np.pi, B)
    geom = SearchGeometry.from_derived(
        derived,
        max_slope=max_slope_for_bank(P, tau),
        lut_step=lut_step_for_bank(P, derived.dt),
    )
    params = [template_params_host(P[t], tau[t], psi[t], geom.dt) for t in range(B)]
    tb = tuple(
        jnp.asarray(np.array([p[i] for p in params], dtype=np.float32))
        for i in range(4)
    )

    ts_args = prepare_ts(geom, ts_np)
    resamp_fn = jax.jit(
        jax.vmap(
            lambda a, b, c, d: resample_split(
                ts_args[0], ts_args[1], a, b, c, d,
                nsamples=geom.nsamples, n_unpadded=geom.n_unpadded,
                dt=geom.dt, use_lut=True,
                max_slope=geom.max_slope, lut_step=geom.lut_step,
            )
        )
    )
    resamp, dt_rs = timed("resample_split", resamp_fn, *tb, repeat=args.repeat)

    ps_fn = jax.jit(
        jax.vmap(
            lambda eo: power_spectrum_split(eo[0], eo[1], nsamples=geom.nsamples)
        )
    )
    ps, dt_ps = timed("packed rfft + power", ps_fn, resamp, repeat=args.repeat)

    hs_fn = jax.jit(
        lambda p: harmonic_sumspec_batch(
            p,
            window_2=geom.window_2,
            fund_hi=geom.fund_hi,
            harm_hi=geom.harm_hi,
            natural=False,  # the production model's phase-major layout
        )
    )
    hs, dt_hs = timed("harmonic_sumspec_batch", hs_fn, ps, repeat=args.repeat)

    total = dt_rs + dt_ps + dt_hs
    print(f"{'total per batch':40s} {total * 1e3:10.2f} ms")
    print(f"{'-> templates/sec (pipeline only)':40s} {B / total:10.2f}")

    if args.median:
        spec = ps[0][: geom.fft_size]
        med_fn = jax.jit(lambda x: running_median(x, bsize=cfg.window))
        timed("running_median (1 spectrum)", med_fn, spec, repeat=1)

    if args.json:
        import json

        with open(args.json, "w") as f:
            json.dump(
                {
                    "what": "search pipeline per-stage wall (s/batch), "
                    "production geometry 2^22 samples padding 3.0",
                    "backend": jax.default_backend(),
                    "batch": B,
                    "resample_s": round(dt_rs, 4),
                    "rfft_power_s": round(dt_ps, 4),
                    "harmonic_sum_s": round(dt_hs, 4),
                    "total_s": round(total, 4),
                    "templates_per_sec_pipeline": round(B / total, 2),
                },
                f,
                indent=1,
            )
        print(f"wrote {args.json}")

    return 0


if __name__ == "__main__":
    sys.exit(main())
