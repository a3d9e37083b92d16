"""Named-scope HBM attribution of the optimized search-step HLO.

``tools/aot_analyze.py`` bounds the per-template HBM traffic and names the
layout hotspots it can see — but its source attribution only reads the
``op_name`` metadata XLA happens to keep, and before the pipeline stages
were instrumented the single largest ledger bucket was 2.5 GB/template of
"compiler-generated" copies attributed to nothing (COST_LEDGER.json r05).
This tool closes the loop with the stage registry
(``runtime/devicecost.py``): every pipeline stage now traces under a
``jax.named_scope`` whose name rides the op metadata through fusion, so
walking the WHOLE optimized module — fusion bodies and while bodies
included, not just the ENTRY computation — buckets every instruction's
output bytes by stage.

The artifact (``erp-hlo-attrib/1``) records per-stage totals, the
layout-class split (copy / transpose / dynamic-update-slice /
dynamic-slice — the ops an ideal streaming pipeline would not contain),
and the top still-unattributed offenders.  ``tools/cost_ledger.py``
consumes a round-numbered artifact (``HLO_ATTRIB_r<N>.json``) as the
source of its ``layout_gb_per_template`` stage rows, replacing the
hand-maintained source-path markers.

Two compile paths:

* default (``--platform topology``): the deviceless TPU topology compile,
  identical to ``aot_analyze`` — the numbers describe the real v5e
  schedule;
* ``--platform cpu``: compile for the local CPU backend.  The CPU
  schedule is NOT the TPU schedule, but scope attribution is a property
  of the metadata plumbing, not the backend — this is the chip-free CI
  gate (``make hlo-attrib``) proving the registry still covers the
  module (``--min-fraction``).

Usage:
  python tools/hlo_attrib.py [--batch 32] [--platform topology|cpu]
      [--nsamples N] [--json OUT.json] [--min-fraction 0.8] [--quiet]
  python tools/hlo_attrib.py --diff OLD.json NEW.json [--threshold 10]
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

from aot_analyze import shape_bytes  # noqa: E402
from boinc_app_eah_brp_tpu.runtime.devicecost import (  # noqa: E402
    ATTRIB_SCHEMA,
    STAGES,
    ledger_stage,
    stage_of_op_name,
    validate_hlo_attrib,
)

# opcodes that are pure plumbing, not executed dataflow: callers of
# separately-listed computations (their bytes are the bodies'), operand
# forwarding, and embedded literals
_SKIP_OPCODES = {
    "parameter",
    "constant",
    "tuple",
    "get-tuple-element",
    "fusion",  # body instructions are walked individually
    "while",  # condition/body computations are walked individually
    "conditional",
    "call",
    "bitcast",  # layout metadata change, no bytes move
    "after-all",
    "add-dependency",
}

# the layout classes the roofline's ideal-streaming model does not
# contain — tracked per stage so layout work is visible inside a stage
_LAYOUT_OPCODES = {
    "copy",
    "transpose",
    "dynamic-update-slice",
    "dynamic-slice",
    "reshape",
}

_INSTR_RE = re.compile(r"(.*?)\s([\w\-]+)\(")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def walk_module(module_text: str):
    """Per-instruction (opcode, out_bytes, op_name) over the WHOLE module
    text — every computation, so fusion and while bodies are counted at
    their own instructions (and the fusion/while caller lines skipped,
    avoiding double counting)."""
    for line in module_text.splitlines():
        line = line.strip()
        if " = " not in line:
            continue
        _, rhs = line.split(" = ", 1)
        m = _INSTR_RE.match(rhs)
        if not m:
            continue
        opcode = m.group(2)
        if opcode in _SKIP_OPCODES:
            continue
        b = shape_bytes(m.group(1))
        if b <= 0:
            continue
        src = _OP_NAME_RE.search(line)
        yield opcode, b, src.group(1) if src else None


def attribute_module(module_text: str, batch: int) -> dict:
    """Bucket every counted instruction byte by registry stage."""
    stages: dict = defaultdict(lambda: {"out_bytes": 0, "layout_bytes": 0,
                                        "count": 0, "ops": defaultdict(int)})
    unattributed: dict = defaultdict(lambda: [0, 0])  # (name) -> [count, bytes]
    total = 0
    attributed = 0
    for opcode, b, op_name in walk_module(module_text):
        total += b
        stage = stage_of_op_name(op_name)
        if stage is None:
            key = op_name or "<no-metadata>"
            unattributed[(opcode, key)][0] += 1
            unattributed[(opcode, key)][1] += b
            continue
        attributed += b
        row = stages[stage]
        row["out_bytes"] += b
        row["count"] += 1
        row["ops"][opcode] += b
        if opcode in _LAYOUT_OPCODES:
            row["layout_bytes"] += b

    def stage_row(scope):
        row = stages[scope]
        ops = dict(sorted(row["ops"].items(), key=lambda kv: -kv[1])[:8])
        return {
            "ledger_stage": ledger_stage(scope),
            "out_bytes": row["out_bytes"],
            "gb_per_template": round(row["out_bytes"] / batch / 1e9, 4),
            "layout_bytes": row["layout_bytes"],
            "count": row["count"],
            "ops": ops,
        }

    top_un = [
        {"op": op, "source": name, "count": c, "out_bytes": b}
        for (op, name), (c, b) in sorted(
            unattributed.items(), key=lambda kv: -kv[1][1]
        )[:20]
    ]
    return {
        "total_bytes": total,
        "attributed_bytes": attributed,
        "attributed_fraction": round(attributed / total, 4) if total else 0.0,
        "stages": {
            scope: stage_row(scope) for scope in STAGES if scope in stages
        },
        "unattributed_top": top_un,
        "unattributed_bytes": total - attributed,
    }


def ledger_stages(doc: dict) -> dict:
    """COST_LEDGER-shaped ``layout_gb_per_template`` rows from an
    attribution artifact: registry scopes collapse through
    ``ledger_stage`` and the remainder stays "compiler-generated"."""
    batch = doc.get("batch") or 1
    agg: dict = defaultdict(float)
    for scope, row in (doc.get("stages") or {}).items():
        agg[ledger_stage(scope)] += row.get("out_bytes", 0)
    agg["compiler-generated"] += doc.get("unattributed_bytes", 0)
    return {
        k: round(v / batch / 1e9, 4)
        for k, v in sorted(agg.items(), key=lambda kv: -kv[1])
        if v > 0
    }


def diff_artifacts(old: dict, new: dict, threshold_pct: float) -> list[str]:
    """Regression report between two attribution artifacts: attribution
    coverage shrinking, or any stage's per-template bytes growing by more
    than ``threshold_pct`` (and at least 0.01 GB absolute)."""
    problems = []
    of, nf = old.get("attributed_fraction", 0), new.get("attributed_fraction", 0)
    if nf < of - 0.02:
        problems.append(
            f"attributed_fraction fell {of:.3f} -> {nf:.3f}"
        )
    os_, ns = old.get("stages") or {}, new.get("stages") or {}
    for scope in sorted(set(os_) | set(ns)):
        a = (os_.get(scope) or {}).get("gb_per_template", 0.0)
        b = (ns.get(scope) or {}).get("gb_per_template", 0.0)
        if b - a < 0.01:
            continue
        if a > 0 and (b - a) / a * 100.0 <= threshold_pct:
            continue
        problems.append(
            f"stage {scope}: {a:.4f} -> {b:.4f} GB/template"
        )
    return problems


def render(doc: dict) -> str:
    lines = [
        f"hlo-attrib: batch {doc['batch']} platform {doc['platform']}  "
        f"total {doc['total_bytes'] / 1e9:.2f} GB  attributed "
        f"{doc['attributed_fraction'] * 100:.1f}%"
    ]
    for scope, row in doc["stages"].items():
        layout_pct = (
            100.0 * row["layout_bytes"] / row["out_bytes"]
            if row["out_bytes"]
            else 0.0
        )
        lines.append(
            f"  {scope:12s} {row['gb_per_template']:8.4f} GB/t  "
            f"x{row['count']:4d}  layout {layout_pct:4.1f}%  "
            f"-> {row['ledger_stage']}"
        )
    un = doc.get("unattributed_top") or []
    if un:
        lines.append("  top unattributed:")
        for row in un[:5]:
            lines.append(
                f"    {row['out_bytes'] / 1e9:8.3f} GB x{row['count']:4d} "
                f"{row['op']:20s} {str(row['source'])[:60]}"
            )
    return "\n".join(lines)


def build_artifact(args) -> dict:
    from boinc_app_eah_brp_tpu.runtime.driver import enable_compilation_cache

    enable_compilation_cache()

    geom, derived = production_geometry(
        args.nsamples, args.tsample_us, args.bank
    )
    if args.platform == "cpu":
        import jax

        device = jax.devices("cpu")[0]
        platform = "cpu"
    else:
        device = topology_devices(args.topology)[0]
        platform = getattr(device, "platform", "tpu")
    comp = compile_step(geom, derived, args.batch, device)
    txt = comp.as_text()
    if args.hlo_out:
        with open(args.hlo_out, "w") as f:
            f.write(txt)

    doc = {
        "schema": ATTRIB_SCHEMA,
        "what": (
            "per-stage HBM attribution of the optimized search-step "
            "module via the runtime/devicecost.py named-scope registry "
            "(whole-module walk: fusion and while bodies included)"
        ),
        "batch": args.batch,
        "platform": platform,
        "nsamples": args.nsamples,
    }
    doc.update(attribute_module(txt, args.batch))
    doc["ledger_stages"] = ledger_stages(doc)
    return doc


def main() -> int:
    ap = argparse.ArgumentParser(prog="hlo_attrib")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument(
        "--platform",
        choices=("topology", "cpu"),
        default="topology",
        help="deviceless TPU topology compile (default) or the local CPU "
        "backend (the chip-free CI gate)",
    )
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--nsamples", type=int, default=1 << 22)
    ap.add_argument("--tsample-us", type=float, default=65.476)
    ap.add_argument("--bank", default=PRODUCTION_BANK)
    ap.add_argument("--json", default=None)
    ap.add_argument("--hlo-out", default=None)
    ap.add_argument(
        "--min-fraction",
        type=float,
        default=None,
        help="exit 1 unless attributed_fraction >= this",
    )
    ap.add_argument(
        "--diff",
        nargs=2,
        metavar=("OLD", "NEW"),
        help="compare two artifacts; exit 1 on stage regression",
    )
    ap.add_argument("--threshold", type=float, default=10.0)
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args()

    if args.diff:
        docs = []
        for path in args.diff:
            with open(path) as f:
                doc = json.load(f)
            errs = validate_hlo_attrib(doc)
            if errs:
                print(f"hlo-attrib: {path}: {'; '.join(errs)}")
                return 2
            docs.append(doc)
        problems = diff_artifacts(docs[0], docs[1], args.threshold)
        for p in problems:
            print(f"hlo-attrib REGRESSION: {p}")
        if not problems:
            print("hlo-attrib: no regressions")
        return 1 if problems else 0

    doc = build_artifact(args)
    errs = validate_hlo_attrib(doc)
    if errs:  # the tool must never emit an artifact its own schema rejects
        print(f"hlo-attrib: internal schema violation: {'; '.join(errs)}")
        return 2
    if not args.quiet:
        print(render(doc))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"wrote {args.json}")
    if (
        args.min_fraction is not None
        and doc["attributed_fraction"] < args.min_fraction
    ):
        print(
            f"hlo-attrib FAIL: attributed_fraction "
            f"{doc['attributed_fraction']:.3f} < {args.min_fraction}"
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
