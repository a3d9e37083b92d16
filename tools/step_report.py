"""Model-vs-measured step-time reconciliation: ``erp-step-report/1``.

``devicecost.stage_time_model`` turns a roofline into per-stage time
FRACTIONS, which are not a measured number.  This tool joins them with
measured step times (``docs/observability.md`` layer 10):

1. **fresh measured run** (default): a chip-free fixture workunit
   (16-template bank, the 4096-sample soak geometry) runs through one
   resident :class:`~boinc_app_eah_brp_tpu.runtime.scheduler.Scheduler`
   with the ``runtime/steptime.py`` bracket force-armed, leaving an
   ``erp-steptime/1`` stream and in-memory per-window records;
2. **join**: measured per-window step times are joined against the
   roofline stage model — measured vs modeled templates/s, and a
   per-stage table ranked by measured/modeled discrepancy.  Chip-free there is no device plane to
   measure stages from, so the per-stage measured column is the
   measured window split by the model's fractions and the artifact says
   so (``device_lane: "modeled-split"``); with a chip,
   ``steptime.capture_profile`` records replace the split
   (``device_lane: "measured"``);
3. **gate**: ``--check`` schema-validates existing artifacts, ``--diff
   OLD NEW`` exits non-zero when the measured step slows past a
   threshold (same backend only), and ``--baseline
   STEPTIME_BASELINE.json`` holds a fresh run against the committed
   chip-free ceilings — ``make step-report`` wires all of it into
   ``make test``.

Usage:
    python tools/step_report.py                          # fresh run + join
    python tools/step_report.py --baseline STEPTIME_BASELINE.json
    python tools/step_report.py --check REPORT.json ...
    python tools/step_report.py --diff OLD.json NEW.json [--threshold 50]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))
sys.path.insert(0, os.path.join(REPO, "tools"))

from boinc_app_eah_brp_tpu.runtime.steptime import (  # noqa: E402
    BASELINE_SCHEMA,
    REPORT_SCHEMA,
    validate_step_report,
)

# the soak fixture class (shared with tools/fleet_bench.py), widened to
# a 16-template bank so one session yields 8 measured windows
N_TEMPLATES = 16
WINDOW = 200
BATCH = 2
TSAMPLE_US = 500.0
N_SAMPLES = 4096
RESULT_DATE = "2008-11-12T00:00:00+00:00"


def fail(msg: str) -> int:
    print(f"step-report: FAIL: {msg}", file=sys.stderr)
    return 1


def build_fixture(work: str, prefix: str = "wu"):
    """One workunit over a widened template bank: the small_bank orbit
    quadruplet tiled with small period/phase offsets to N_TEMPLATES, so
    a single session produces enough dispatch windows for stable
    percentiles.  Returns the DriverArgs (``prefix`` separates the
    warmup session's files from the measured one's)."""
    import numpy as np
    from fixtures import small_bank, synthetic_timeseries

    from boinc_app_eah_brp_tpu.io import write_template_bank, write_workunit
    from boinc_app_eah_brp_tpu.io.templates import TemplateBank
    from boinc_app_eah_brp_tpu.runtime.driver import DriverArgs

    base = small_bank(P_true=2.2, tau_true=0.04, psi_true=1.2)
    reps = -(-N_TEMPLATES // len(base.P))
    idx = np.arange(N_TEMPLATES)
    P = np.tile(base.P, reps)[:N_TEMPLATES] * (1.0 + 0.003 * idx)
    tau = np.tile(base.tau, reps)[:N_TEMPLATES]
    psi = np.tile(base.psi0, reps)[:N_TEMPLATES] + 0.01 * idx
    bank_path = os.path.join(work, "bank.dat")
    write_template_bank(bank_path, TemplateBank(P, tau, psi))
    ts = synthetic_timeseries(
        N_SAMPLES, f_signal=31.0, P_orb=2.2, tau=0.04, psi0=1.2,
        amp=7.0, seed=0,
    )
    wu = os.path.join(work, f"{prefix}.bin4")
    write_workunit(wu, ts, tsample_us=TSAMPLE_US, scale=1.0, dm=55.5)
    return DriverArgs(
        inputfile=wu,
        outputfile=os.path.join(work, f"{prefix}.cand"),
        templatebank=bank_path,
        checkpointfile=os.path.join(work, f"{prefix}.cpt"),
        window=WINDOW,
        batch_size=BATCH,
    )


def measure(work: str) -> tuple[dict, list[dict], object, str]:
    """Fresh measured run: (steptime summary, per-window records, geom,
    backend).  The bracket is force-armed on the default context so the
    scheduler's dispatch loop records every window."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("ERP_RESULT_DATE", RESULT_DATE)
    import fleet_bench

    from boinc_app_eah_brp_tpu.runtime import steptime
    from boinc_app_eah_brp_tpu.runtime.scheduler import Scheduler

    warm_args = build_fixture(work, prefix="warm")
    args = build_fixture(work, prefix="wu")
    geom = fleet_bench.warm_spec_for(args).geom
    sched = Scheduler()
    try:
        # session 1 pays the compile; the bracket arms (re-arming resets
        # the ring) only for session 2, so the measured windows are the
        # steady state the baseline ceilings are about
        res = sched.process(warm_args)
        if not res.ok:
            raise RuntimeError(
                f"warmup session exited {res.code}: {res.error}"
            )
        steptime.configure(
            steptime_file=os.path.join(work, "steptime.jsonl"), force=True
        )
        res = sched.process(args)
    finally:
        sched.close()
    if not res.ok:
        raise RuntimeError(
            f"measurement session exited {res.code}: {res.error}"
        )
    summary = steptime.summary()
    records = steptime.records()
    steptime.finish(0)
    if summary["windows"] == 0:
        raise RuntimeError("bracket armed but no step windows recorded")
    import jax

    return summary, records, geom, jax.default_backend()


def build_report(
    summary: dict, geom, backend: str, chip: str,
    capture_stage_ms: dict | None = None,
) -> dict:
    """Join measured windows against the roofline stage model into one
    ``erp-step-report/1`` document."""
    from boinc_app_eah_brp_tpu.runtime.devicecost import (
        ledger_stage,
        stage_time_model,
    )

    model = stage_time_model(
        geom.nsamples, geom.n_unpadded, geom.fund_hi, geom.harm_hi,
        max_slope=geom.max_slope, chip=chip,
    )
    windows = summary["windows"]
    templates = summary["templates"]
    tpw = templates / windows if windows else 0.0  # templates per window
    mean_window_ms = summary["step_ms"]["mean"]
    measured_tps = summary["templates_per_sec"]
    model_ms_per_template = sum(r["t_ms"] for r in model)
    modeled_tps = (
        round(1e3 / model_ms_per_template, 3)
        if model_ms_per_template > 0 else 0.0
    )

    measured_lane = bool(capture_stage_ms)
    stages = []
    for row in model:
        modeled_ms = row["t_ms"] * tpw
        if measured_lane:
            # per-window share of the profiler's per-stage totals
            measured_ms = capture_stage_ms.get(row["scope"], 0.0) / windows
        else:
            measured_ms = mean_window_ms * row["fraction"]
        stages.append(
            {
                "stage": row["stage"],
                "scope": row["scope"],
                "bound": row["bound"],
                "modeled_fraction": round(row["fraction"], 4),
                "modeled_ms_per_window": round(modeled_ms, 4),
                "measured_ms_per_window": round(measured_ms, 4),
                "discrepancy": round(
                    measured_ms / modeled_ms, 2
                ) if modeled_ms > 0 else 0.0,
                "bucket": ledger_stage(row["scope"]),
            }
        )
    stages.sort(key=lambda s: s["discrepancy"], reverse=True)

    return {
        "schema": REPORT_SCHEMA,
        "generated_unix": time.time(),
        "backend": backend,
        "chip_model": chip,
        "geometry": {
            "nsamples": geom.nsamples,
            "n_unpadded": geom.n_unpadded,
            "batch": BATCH,
            "templates": N_TEMPLATES,
        },
        "measured": {
            "windows": windows,
            "templates": templates,
            "templates_per_sec": measured_tps,
            "step_ms": summary["step_ms"],
        },
        "modeled": {
            "templates_per_sec": modeled_tps,
            "ms_per_template": round(model_ms_per_template, 4),
            "source": f"stage_time_model({chip})",
        },
        "ratio_measured_to_modeled": round(
            modeled_tps / measured_tps, 2
        ) if measured_tps > 0 and modeled_tps > 0 else None,
        "device_lane": "measured" if measured_lane else "modeled-split",
        "stages": stages,
    }


def render(doc: dict) -> str:
    m, mo = doc["measured"], doc["modeled"]
    out = [
        f"== step report ({doc['backend']} measured vs "
        f"{doc['chip_model']} model, {doc['device_lane']}) ==",
        f"measured: {m['templates_per_sec']} t/s over {m['windows']} "
        f"windows (p50 {m['step_ms']['p50']} ms, p95 {m['step_ms']['p95']} "
        f"ms)",
        f"modeled:  {mo['templates_per_sec']} t/s "
        f"({mo['ms_per_template']} ms/template roofline)",
        f"model-over-measured: x{doc['ratio_measured_to_modeled']}",
        "",
        f"{'stage':<18} {'bound':<5} {'model ms/win':>12} "
        f"{'meas ms/win':>12} {'disc':>8}",
    ]
    for s in doc["stages"]:
        out.append(
            f"{s['stage']:<18} {s['bound']:<5} "
            f"{s['modeled_ms_per_window']:>12} "
            f"{s['measured_ms_per_window']:>12} "
            f"{'x' + str(s['discrepancy']):>8}"
        )
    return "\n".join(out)


def check_baseline(doc: dict, base_path: str) -> list[str]:
    """Ceiling violations versus STEPTIME_BASELINE.json (empty = green).
    Same-backend only: a CPU baseline says nothing about a TPU run."""
    with open(base_path, encoding="utf-8") as f:
        base = json.load(f)
    if base.get("schema") != BASELINE_SCHEMA:
        return [f"{base_path} is not a {BASELINE_SCHEMA} document"]
    if base.get("backend") != doc.get("backend"):
        print(
            f"step-report: baseline backend {base.get('backend')!r} != "
            f"run backend {doc.get('backend')!r}; gate skipped"
        )
        return []
    bad = []
    m = doc["measured"]
    p50_max = base.get("p50_step_ms_max")
    if p50_max is not None and m["step_ms"]["p50"] > p50_max:
        bad.append(
            f"p50 step {m['step_ms']['p50']} ms over ceiling {p50_max} ms"
        )
    p95_max = base.get("p95_step_ms_max")
    if p95_max is not None and m["step_ms"]["p95"] > p95_max:
        bad.append(
            f"p95 step {m['step_ms']['p95']} ms over ceiling {p95_max} ms"
        )
    tps_min = base.get("templates_per_sec_min")
    if tps_min is not None and m["templates_per_sec"] < tps_min:
        bad.append(
            f"{m['templates_per_sec']} templates/s under floor {tps_min}"
        )
    return bad


def diff(old_path: str, new_path: str, threshold_pct: float) -> int:
    """Regression diff: non-zero when NEW's measured step latency (p50)
    grew — or throughput fell — past the threshold, same backend only."""
    docs = []
    for p in (old_path, new_path):
        try:
            with open(p, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            return fail(f"cannot read {p}: {e}")
        errs = validate_step_report(doc)
        if errs:
            return fail(f"{p}: invalid report: {'; '.join(errs)}")
        docs.append(doc)
    old, new = docs
    if old["backend"] != new["backend"]:
        print(
            f"step-report: diff across backends ({old['backend']} -> "
            f"{new['backend']}); regression gate skipped"
        )
        return 0
    bad = []
    p50_old = old["measured"]["step_ms"]["p50"]
    p50_new = new["measured"]["step_ms"]["p50"]
    if p50_old > 0 and p50_new > p50_old * (1.0 + threshold_pct / 100.0):
        bad.append(
            f"p50 step latency {p50_old} -> {p50_new} ms "
            f"(+{100.0 * (p50_new - p50_old) / p50_old:.1f}% > "
            f"{threshold_pct}%)"
        )
    tps_old = old["measured"]["templates_per_sec"]
    tps_new = new["measured"]["templates_per_sec"]
    if tps_old > 0 and tps_new < tps_old * (1.0 - threshold_pct / 100.0):
        bad.append(
            f"throughput {tps_old} -> {tps_new} templates/s "
            f"({100.0 * (tps_new - tps_old) / tps_old:.1f}% < "
            f"-{threshold_pct}%)"
        )
    if bad:
        return fail("measured-step regression: " + "; ".join(bad))
    print(
        f"step-report: no regression ({p50_old} -> {p50_new} ms p50, "
        f"threshold {threshold_pct}%)"
    )
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Measured-vs-modeled step-time reconciliation "
        "(chip-free)."
    )
    ap.add_argument("--check", nargs="+", metavar="PATH",
                    help="validate existing erp-step-report/1 files and "
                         "exit (no fresh run)")
    ap.add_argument("--diff", nargs=2, metavar=("OLD", "NEW"),
                    help="exit non-zero when NEW's measured step slowed "
                         "past --threshold vs OLD (same backend only)")
    ap.add_argument("--threshold", type=float, default=50.0,
                    help="regression threshold for --diff, percent "
                         "(default 50: CI step times are noisy)")
    ap.add_argument("--baseline",
                    help="gate the fresh run against this "
                         "STEPTIME_BASELINE.json (same backend only)")
    ap.add_argument("--chip", default="v5e",
                    help="roofline chip model for the modeled column "
                         "(default v5e — the ROADMAP item 1 target)")
    ap.add_argument("--json",
                    default=os.path.join(REPO, ".erp_cache",
                                         "step_report_ci.json"),
                    help="report cache path (empty string disables)")
    ap.add_argument("--workdir", help="reuse this dir instead of a tmp one")
    ap.add_argument("--keep", action="store_true",
                    help="keep the workdir (default: removed when green)")
    args = ap.parse_args(argv)

    if args.check:
        bad = 0
        for p in args.check:
            try:
                with open(p, encoding="utf-8") as f:
                    doc = json.load(f)
            except (OSError, ValueError) as e:
                print(f"{p}: INVALID\n  - unreadable: {e}")
                bad += 1
                continue
            errs = validate_step_report(doc)
            if errs:
                bad += 1
                print(f"{p}: INVALID")
                for e in errs:
                    print(f"  - {e}")
            else:
                print(f"{p}: OK ({REPORT_SCHEMA})")
        return 1 if bad else 0

    if args.diff:
        return diff(args.diff[0], args.diff[1], args.threshold)

    work = args.workdir or tempfile.mkdtemp(prefix="erp-step-report-")
    os.makedirs(work, exist_ok=True)
    print(f"step-report: workdir {work}")
    try:
        summary, records, geom, backend = measure(work)
    except RuntimeError as e:
        return fail(str(e))
    doc = build_report(summary, geom, backend, args.chip)
    errs = validate_step_report(doc)
    if errs:  # a malformed fresh report is a bug in this tool
        return fail("self-check failed: " + "; ".join(errs))
    print(render(doc))

    if args.json:
        os.makedirs(os.path.dirname(args.json), exist_ok=True)
        tmp = f"{args.json}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, args.json)
        print(f"step-report: cached at {args.json}")

    if args.baseline:
        try:
            violations = check_baseline(doc, args.baseline)
        except (OSError, ValueError) as e:
            return fail(f"cannot read baseline {args.baseline}: {e}")
        if violations:
            return fail("baseline violations: " + "; ".join(violations))
        print(
            f"step-report: within "
            f"{os.path.basename(args.baseline)} ceilings"
        )

    if not args.keep and not args.workdir:
        shutil.rmtree(work, ignore_errors=True)
    print(
        f"step-report: PASS ({doc['measured']['templates_per_sec']} "
        f"measured t/s vs {doc['modeled']['templates_per_sec']} modeled)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
