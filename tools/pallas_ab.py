"""On-chip A/B: fused Pallas resampler vs the production XLA formulation.

The measure-first bar for adopting ``ops/pallas_resample.py`` (the same bar
that retired the Pallas median in r03 with `tools/median_study.py`):

1. value parity on the real chip (interpret-mode bit-parity is already in
   tests; Mosaic codegen may contract float32 chains differently than
   XLA-TPU, so the chip check is tolerance + index-flip counting);
2. wall-clock per template at the production geometry, both paths.

Writes one JSON artifact; run with nothing else on the device (strictly
serial).

Usage: python tools/pallas_ab.py [--json PALLAS_AB.json] [--repeat 5]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _force(arrs):
    for a in arrs:
        np.asarray(a.ravel()[:1])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="PALLAS_AB.json")
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--n", type=int, default=1 << 22)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from boinc_app_eah_brp_tpu.models.search import template_params_host
    from boinc_app_eah_brp_tpu.ops.pallas_resample import (
        pallas_applicable,
        resample_split_pallas,
    )
    from boinc_app_eah_brp_tpu.ops.resample import resample_split
    from boinc_app_eah_brp_tpu.runtime.driver import enable_compilation_cache

    enable_compilation_cache()
    backend = jax.default_backend()
    print(f"pallas_ab: backend={backend}", flush=True)

    n = args.n
    nsamples = int(3.0 * n + 0.5)
    dt = 65.476e-6
    max_slope, lut_step = 0.00390625, 1.52587890625e-05  # PALFA pow2 bounds
    assert pallas_applicable(max_slope, lut_step, 1024)

    rng = np.random.default_rng(0)
    ts = rng.uniform(0, 15, n).astype(np.float32)
    ev = jnp.asarray(ts[0::2].copy())
    od = jnp.asarray(ts[1::2].copy())
    # a production-like template (P 725 s, tau 0.3)
    t32, om, ps0, s0 = template_params_host(725.88, 0.3, 1.7, dt)
    kw = dict(
        nsamples=nsamples, n_unpadded=n, dt=dt,
        max_slope=max_slope, lut_step=lut_step,
    )

    def run_xla():
        return resample_split(
            ev, od, t32, om, ps0, s0, use_lut=True, lut_tiles=1024, **kw
        )

    def run_pl():
        return resample_split_pallas(
            ev, od, t32, om, ps0, s0, lut_tiles=1024, **kw
        )

    out = {"backend": backend, "n": n}
    for name, fn in (("xla", run_xla), ("pallas", run_pl)):
        try:
            res = fn()
            _force(res)  # compile+warm
            t0 = time.perf_counter()
            for _ in range(args.repeat):
                res = fn()
            _force(res)
            wall = (time.perf_counter() - t0) / args.repeat
            out[f"{name}_ms"] = round(wall * 1e3, 3)
            out[f"{name}_result"] = [np.asarray(r) for r in res]
            print(f"pallas_ab: {name} {wall * 1e3:.2f} ms", flush=True)
        except Exception as e:
            out[f"{name}_error"] = f"{type(e).__name__}: {e}"[:500]
            print(f"pallas_ab: {name} FAILED: {out[f'{name}_error']}",
                  flush=True)

    # batched form at the model's scale: one launch over (T, parity, block)
    # vs the production vmapped XLA formulation
    B = int(os.environ.get("PALLAS_AB_BATCH", "16"))
    from boinc_app_eah_brp_tpu.ops.pallas_resample import (
        resample_split_pallas_batch,
    )

    rngb = np.random.default_rng(1)
    Ps = rngb.uniform(660.0, 2231.0, B)
    taus = rngb.uniform(0.0, 0.335, B)
    psis = rngb.uniform(0.0, 2 * np.pi, B)
    bp = [template_params_host(Ps[i], taus[i], psis[i], dt) for i in range(B)]
    tb = tuple(
        jnp.asarray(np.array([p[i] for p in bp], dtype=np.float32))
        for i in range(4)
    )

    def run_xla_batch():
        return jax.vmap(
            lambda a, b_, c, d: resample_split(
                ev, od, a, b_, c, d, use_lut=True, lut_tiles=1024, **kw
            )
        )(*tb)

    def run_pl_batch():
        return resample_split_pallas_batch(
            ev, od, *tb, lut_tiles=1024, **kw
        )

    for name, fn in (("xla_b", run_xla_batch), ("pallas_b", run_pl_batch)):
        try:
            res = fn()
            _force(res)
            t0 = time.perf_counter()
            for _ in range(args.repeat):
                res = fn()
            _force(res)
            wall = (time.perf_counter() - t0) / args.repeat
            out[f"{name}{B}_ms"] = round(wall * 1e3, 3)
            print(f"pallas_ab: {name} (batch {B}) {wall * 1e3:.2f} ms",
                  flush=True)
        except Exception as e:
            out[f"{name}{B}_error"] = f"{type(e).__name__}: {e}"[:500]
            print(f"pallas_ab: {name} FAILED: {out[f'{name}{B}_error']}",
                  flush=True)
    if f"xla_b{B}_ms" in out and f"pallas_b{B}_ms" in out:
        out["batch_speedup"] = round(
            out[f"xla_b{B}_ms"] / out[f"pallas_b{B}_ms"], 3
        )

    if "xla_result" in out and "pallas_result" in out:
        xe, xo = out.pop("xla_result")
        pe, po = out.pop("pallas_result")
        flips = int((xe != pe).sum() + (xo != po).sum())
        rel = float(
            max(
                np.abs(xe - pe).max() / (np.abs(xe).max() + 1e-30),
                np.abs(xo - po).max() / (np.abs(xo).max() + 1e-30),
            )
        )
        out["value_mismatch_count"] = flips
        out["max_rel_diff"] = rel
        out["speedup"] = round(out["xla_ms"] / out["pallas_ms"], 3)
        print(
            f"pallas_ab: mismatches={flips} max_rel={rel:.2e} "
            f"speedup={out['speedup']}x",
            flush=True,
        )
    else:
        out.pop("xla_result", None)
        out.pop("pallas_result", None)

    with open(args.json, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
