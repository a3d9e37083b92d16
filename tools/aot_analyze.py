"""Chip-free attribution of the layout/overhead gap (VERDICT r04 #2).

Compiles the production batched search step for the live TPU generation
via the deviceless topology path (see ``tools/aot_prewarm.py``) and
interrogates the COMPILER's view of the final v5e schedule:

* ``cost_analysis()`` — XLA's own FLOP and bytes-accessed totals for the
  optimized executable (its static performance model);
* the optimized HLO — per-opcode output-bytes histogram and
  source-attributed (``op_name`` metadata) copy / transpose /
  dynamic-update-slice hotspots, i.e. the layout ops the roofline's
  ideal-streaming model does not contain;
* ``memory_analysis()`` — the executable's static HBM footprint.

The point: the measured-vs-attainable gap (r02: 30.4 vs 686 t/s) was
bounded as "layout/overhead" with nothing naming the ops.  The compiler
names them without a chip: at batch 32 the roofline's ideal traffic is
~0.94 GB/template while XLA reports ~7.9 GB/template accessed (8.4x),
with the excess concentrated in harmonic-sum reshape/slice copies and
compiler-generated while loops carrying spectrum-sized tuples
(AOT_COST_r05.json).  Layout experiments iterate against these numbers
and land with a before/after in compiler-reported bytes; the chip then
confirms wall-clock.  (One such experiment — flattening the deinterleave
with an honest transpose — was evaluated and REJECTED this way: 8.27
GB/t, worse.)

Usage: python tools/aot_analyze.py [--batch 32] [--topology v5e:2x2]
           [--json AOT_COST.json] [--hlo-out FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _aot_common import (  # noqa: E402
    PRODUCTION_BANK,
    compile_step,
    production_geometry,
    topology_devices,
    use_cpu_backend,
)

use_cpu_backend()

_DT = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1, "u8": 1,
       "s8": 1, "f16": 2, "s64": 8, "u64": 8, "f64": 8}


def shape_bytes(s: str) -> int:
    total = 0
    for m in re.finditer(r"\b(\w+)\[([\d,]*)\]", s):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DT:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DT[dt]
    return total


def opcode_histogram(entry_text: str):
    by_op: dict = defaultdict(lambda: [0, 0])
    for line in entry_text.splitlines():
        line = line.strip()
        if " = " not in line:
            continue
        _, rhs = line.split(" = ", 1)
        m = re.match(r"(.*?)\s([\w\-]+)\(", rhs)
        if not m:
            continue
        b = shape_bytes(m.group(1))
        by_op[m.group(2)][0] += 1
        by_op[m.group(2)][1] += b
    return {
        op: {"count": c, "out_bytes": b}
        for op, (c, b) in sorted(by_op.items(), key=lambda kv: -kv[1][1])
    }


def layout_hotspots(module_text: str, top: int = 20):
    """copy/transpose/dynamic-update-slice by source op_name, module-wide
    (fusion and while bodies included); unattributed entries are
    compiler-generated (rolled loops etc.)."""
    agg: dict = defaultdict(lambda: [0, 0])
    for line in module_text.splitlines():
        line = line.strip()
        if " = " not in line:
            continue
        _, rhs = line.split(" = ", 1)
        m = re.match(r"(.*?)\s(copy|transpose|dynamic-update-slice)\(", rhs)
        if not m:
            continue
        b = shape_bytes(m.group(1))
        src = re.search(r'op_name="([^"]*)"', line)
        key = (m.group(2), src.group(1) if src else "<compiler-generated>")
        agg[key][0] += 1
        agg[key][1] += b
    rows = [
        {"op": op, "source": name, "count": c, "out_bytes": b}
        for (op, name), (c, b) in sorted(agg.items(), key=lambda kv: -kv[1][1])
    ]
    return rows[:top]


def main() -> int:
    ap = argparse.ArgumentParser(prog="aot_analyze")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--json", default=None)
    ap.add_argument("--hlo-out", default=None)
    ap.add_argument("--nsamples", type=int, default=1 << 22)
    ap.add_argument("--tsample-us", type=float, default=65.476)
    ap.add_argument("--bank", default=PRODUCTION_BANK)
    args = ap.parse_args()

    from boinc_app_eah_brp_tpu.runtime.driver import enable_compilation_cache

    enable_compilation_cache()

    devs = topology_devices(args.topology)
    geom, derived = production_geometry(
        args.nsamples, args.tsample_us, args.bank
    )
    comp = compile_step(geom, derived, args.batch, devs[0])

    ca = comp.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    accessed = float(ca.get("bytes accessed", 0.0))
    ma = comp.memory_analysis()
    txt = comp.as_text()
    if args.hlo_out:
        with open(args.hlo_out, "w") as f:
            f.write(txt)
    entry = txt[txt.index("ENTRY "):]

    from boinc_app_eah_brp_tpu.runtime.roofline import roofline_report

    roof = roofline_report(
        geom.nsamples, geom.n_unpadded, geom.fund_hi, geom.harm_hi,
        max_slope=geom.max_slope,
    )
    model_bytes_t = sum(
        s["hbm_mbytes"] for s in roof["per_template"]
    ) * 1e6

    out = {
        "what": (
            "XLA's own view of the optimized v5e search-step executable "
            "(deviceless AOT): FLOPs/bytes totals, per-opcode histogram, "
            "source-attributed layout ops"
        ),
        "batch": args.batch,
        "compiler": {
            "flops_per_template": flops / args.batch,
            "bytes_accessed_per_template": accessed / args.batch,
            "transcendentals": float(ca.get("transcendentals", 0.0)),
            "hbm_temp_bytes": ma.temp_size_in_bytes,
            "hbm_args_bytes": ma.argument_size_in_bytes,
            "hbm_output_bytes": ma.output_size_in_bytes,
        },
        "roofline_model": {
            "matmul_flops_per_template": sum(
                s["matmul_gflops"] for s in roof["per_template"]
            )
            * 1e9,
            "ideal_bytes_per_template": model_bytes_t,
        },
        "bytes_vs_model": round(accessed / args.batch / model_bytes_t, 2),
        "opcode_histogram": opcode_histogram(entry),
        "layout_hotspots": layout_hotspots(txt),
    }
    print(
        f"flops/t {flops / args.batch / 1e9:.1f} GF (model "
        f"{out['roofline_model']['matmul_flops_per_template'] / 1e9:.1f}), "
        f"bytes/t {accessed / args.batch / 1e9:.2f} GB (model "
        f"{model_bytes_t / 1e9:.2f}) -> {out['bytes_vs_model']}x model"
    )
    for row in out["layout_hotspots"][:8]:
        print(
            f"  {row['out_bytes'] / 1e9:8.3f} GB x{row['count']:3d} "
            f"{row['op']:22s} {row['source'][:70]}"
        )
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
            f.write("\n")
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
