"""Render / diff / validate the structured-metrics artifacts.

Companion to ``runtime/metrics.py``: a run with ``--metrics-file`` (or
``$ERP_METRICS_FILE``) leaves a JSONL heartbeat stream and a run-report
JSON; this tool turns either into a human summary table, diffs two run
reports for regression triage alongside the ``BENCH_*.json`` trajectory,
and schema-checks a report for use as a gate in bench pipelines.

Usage:
    python tools/metrics_report.py RUN.jsonl            # render stream
    python tools/metrics_report.py RUN.report.json      # render report
    python tools/metrics_report.py --diff OLD.json NEW.json
    python tools/metrics_report.py --check RUN.report.json

``--diff`` and ``--check`` accept either form: a JSONL stream is reduced
to the ``run_report`` line it carries (the last one, if the file holds
several runs).  ``--check`` additionally recognizes flight-recorder
crash dumps (``erp-blackbox/1``, ``runtime/flightrec.py``) and host span
traces (``erp-trace/1`` JSONL streams and their Chrome exports,
``runtime/tracing.py``), the watchdog's
incident sidecar (``erp-incident-log/1``, ``runtime/watchdog.py`` —
the memory behind poison-range quarantine) and the signed quorum
verdicts the volunteer fabric emits per validation round
(``erp-quorum/1``, ``fabric/validator.py`` — structure AND HMAC
signature are checked) and the fleet rollup those verdicts feed
(``erp-fleet-report/1``, ``tools/fleet_report.py``) and the measured-
time observatory's artifacts (``erp-steptime/1`` step-latency streams
and ``erp-step-report/1`` reconciliations, ``runtime/steptime.py`` /
``tools/step_report.py``; ``erp-serving-slo/1`` heartbeat streams,
``serving/slo.py``; ``erp-serving-journal/1`` WU journals,
``serving/journal.py``; ``erp-fleet-timeline/1`` merged-timeline
sidecars, ``tools/fleet_timeline.py``) and validates each
against its own schema —
well-formed events, monotone timestamps, no span left open on a clean
exit — so one invocation can gate every artifact a run leaves behind
(for the rendered views use ``tools/blackbox_report.py`` and
``tools/trace_report.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from boinc_app_eah_brp_tpu.fabric.validator import (  # noqa: E402
    QUORUM_SCHEMA,
    validate_quorum_verdict,
)
from boinc_app_eah_brp_tpu.runtime.flightrec import (  # noqa: E402
    SCHEMA as BLACKBOX_SCHEMA,
)
from boinc_app_eah_brp_tpu.runtime.flightrec import (  # noqa: E402
    validate_dump,
)
from boinc_app_eah_brp_tpu.runtime.metrics import (  # noqa: E402
    REPORT_SCHEMA,
    validate_report,
)
from boinc_app_eah_brp_tpu.runtime.precision import (  # noqa: E402
    PRECISION_BASELINE_SCHEMA,
    PRECISION_SCHEMA,
    validate_precision_audit,
    validate_precision_baseline,
)
from boinc_app_eah_brp_tpu.runtime.steptime import (  # noqa: E402
    REPORT_SCHEMA as STEP_REPORT_SCHEMA,
    STEPTIME_SCHEMA,
    validate_step_report,
)
from boinc_app_eah_brp_tpu.runtime.steptime import (  # noqa: E402
    validate_stream as validate_steptime_stream,
)
from boinc_app_eah_brp_tpu.serving.journal import (  # noqa: E402
    JOURNAL_SCHEMA,
    validate_journal,
)
from boinc_app_eah_brp_tpu.serving.slo import (  # noqa: E402
    SLO_SCHEMA,
    validate_slo_stream,
)
from boinc_app_eah_brp_tpu.runtime.tracing import (  # noqa: E402
    TRACE_SCHEMA,
    validate_chrome,
    validate_stream,
)
from boinc_app_eah_brp_tpu.runtime.watchdog import (  # noqa: E402
    INCIDENT_SCHEMA,
    validate_incident_log,
)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from fleet_report import (  # noqa: E402
    FLEET_SCHEMA,
    validate_fleet_report,
)
from fleet_timeline import (  # noqa: E402
    TIMELINE_SCHEMA,
    validate_fleet_timeline,
)


def _raw_json(path: str):
    """The file parsed as one JSON document, or None (JSONL streams and
    torn files land here and flow through :func:`load_report`)."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _jsonl_dict_lines(path: str) -> list[dict]:
    """Every parseable JSON-object line of a JSONL file (torn tails of
    crashed runs are skipped); [] on IO failure."""
    lines: list[dict] = []
    try:
        with open(path) as f:
            for raw in f:
                raw = raw.strip()
                if not raw:
                    continue
                try:
                    rec = json.loads(raw)
                except json.JSONDecodeError:
                    continue  # torn tail of a crashed run
                if isinstance(rec, dict):
                    lines.append(rec)
    except OSError:
        return []
    return lines


def _trace_stream_lines(path: str) -> list[dict] | None:
    """Parsed lines of an ``erp-trace/1`` JSONL stream, or None when the
    file is not one (a metrics stream's first line is a heartbeat)."""
    lines = _jsonl_dict_lines(path)
    if (
        lines
        and lines[0].get("kind") == "start"
        and lines[0].get("schema") == TRACE_SCHEMA
    ):
        return lines
    return None


def _steptime_stream_lines(path: str) -> list[dict] | None:
    """Parsed lines of an ``erp-steptime/1`` JSONL stream
    (``runtime/steptime.py``), or None when the file is not one."""
    lines = _jsonl_dict_lines(path)
    if (
        lines
        and lines[0].get("kind") == "start"
        and lines[0].get("schema") == STEPTIME_SCHEMA
    ):
        return lines
    return None


def _slo_stream_lines(path: str) -> list[dict] | None:
    """Parsed lines of an ``erp-serving-slo/1`` heartbeat stream
    (``serving/slo.py``), or None when the file is not one (every line
    is a self-describing heartbeat; the first line's schema decides)."""
    lines = _jsonl_dict_lines(path)
    if lines and lines[0].get("schema") == SLO_SCHEMA:
        return lines
    return None


def _is_journal_stream(path: str) -> bool:
    """True when the file is an ``erp-serving-journal/1`` WAL
    (``serving/journal.py``); the first parseable line's schema
    decides.  Validation itself runs on the raw file — the journal
    checker owns the torn-tail rule."""
    lines = _jsonl_dict_lines(path)
    return bool(lines) and lines[0].get("schema") == JOURNAL_SCHEMA


def load_report(path: str) -> tuple[dict | None, list[dict]]:
    """(run_report-or-None, heartbeat lines) from either artifact form.

    A run-report JSON file yields (report, []).  A JSONL stream yields
    the last ``run_report`` line's report (None when the run died before
    writing one) plus every heartbeat, so a crashed run still renders
    its final heartbeat snapshot.
    """
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict) and doc.get("schema") == REPORT_SCHEMA:
        return doc, []
    report = None
    heartbeats = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if not isinstance(rec, dict):
            continue
        if rec.get("kind") == "run_report" and isinstance(
            rec.get("report"), dict
        ):
            report = rec["report"]
        elif rec.get("kind") == "heartbeat":
            heartbeats.append(rec)
    return report, heartbeats


def _fmt(v) -> str:
    if v is None:
        return "n/a"
    if isinstance(v, bool):
        return str(v)
    if isinstance(v, float):
        return f"{v:,.3f}".rstrip("0").rstrip(".")
    if isinstance(v, int):
        return f"{v:,}"
    return str(v)


def _table(rows: list[tuple], header: tuple) -> str:
    rows = [tuple(str(c) for c in r) for r in rows]
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(header)
    ]
    def line(cells):
        return "  ".join(c.ljust(w) for c, w in zip(cells, widths)).rstrip()
    out = [line(header), line(tuple("-" * w for w in widths))]
    out.extend(line(r) for r in rows)
    return "\n".join(out)


def _hist_summary(h: dict) -> str:
    if not h.get("count"):
        return "(empty)"
    mean = h["sum"] / h["count"]
    # coarse p50/p95 from the bucket counts (upper bound of the bucket
    # the quantile lands in; overflow reports the observed max)
    edges = list(h["buckets"]) + [None]
    def quantile(q: float):
        target = q * h["count"]
        acc = 0
        for edge, c in zip(edges, h["counts"]):
            acc += c
            if acc >= target:
                return edge if edge is not None else h["max"]
        return h["max"]
    return (
        f"n={h['count']} mean={_fmt(mean)} p50<={_fmt(quantile(0.5))} "
        f"p95<={_fmt(quantile(0.95))} max={_fmt(h['max'])}"
    )


def render(report: dict | None, heartbeats: list[dict], title: str) -> str:
    out = [f"== {title} =="]
    snap = None
    if report is not None:
        status = report.get("exit_status")
        out.append(
            f"exit_status={status} ok={report.get('ok')} "
            f"wall={_fmt(report.get('wall_s'))} s"
        )
        tracing = report.get("tracing") or {}
        if tracing.get("active"):
            out.append(f"profiler trace: {', '.join(tracing.get('dirs', []))}")
        for d in report.get("devices", []):
            out.append(
                f"device {d.get('device')}: peak "
                f"{_fmt(d.get('peak_bytes_in_use'))} / "
                f"{_fmt(d.get('bytes_limit'))} B"
            )
        snap = report.get("metrics")
    elif heartbeats:
        out.append(
            f"NO RUN REPORT (run still live or died hard); "
            f"showing last of {len(heartbeats)} heartbeats"
        )
        snap = heartbeats[-1].get("metrics")
    if not isinstance(snap, dict):
        out.append("no metrics payload found")
        return "\n".join(out)

    phases = snap.get("phases") or {}
    if phases:
        out.append("\nPhases:")
        out.append(
            _table(
                [
                    (name, _fmt(p.get("wall_s")), p.get("count"))
                    for name, p in phases.items()
                ],
                ("phase", "wall_s", "count"),
            )
        )
    counters = snap.get("counters") or {}
    gauges = snap.get("gauges") or {}
    scalars = [
        (name, c.get("value"), c.get("unit", ""), "counter")
        for name, c in sorted(counters.items())
    ] + [
        (name, g.get("value"), g.get("unit", ""), "gauge")
        for name, g in sorted(gauges.items())
    ]
    if scalars:
        out.append("\nCounters / gauges:")
        out.append(
            _table(
                [(n, _fmt(v), u, k) for n, v, u, k in scalars],
                ("name", "value", "unit", "kind"),
            )
        )
    hists = snap.get("histograms") or {}
    if hists:
        out.append("\nHistograms:")
        out.append(
            _table(
                [
                    (name, h.get("unit", ""), _hist_summary(h))
                    for name, h in sorted(hists.items())
                ],
                ("name", "unit", "summary"),
            )
        )
    return "\n".join(out)


def _flatten_scalars(report: dict) -> dict:
    """name -> numeric value across phases + counters (+ wall) for diffing."""
    out = {"wall_s": report.get("wall_s")}
    m = report.get("metrics") or {}
    for name, p in (m.get("phases") or {}).items():
        out[f"phase:{name}"] = p.get("wall_s")
    for name, c in (m.get("counters") or {}).items():
        out[name] = c.get("value")
    for name, g in (m.get("gauges") or {}).items():
        v = g.get("value")
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[name] = v
    return out


def diff(a: dict, b: dict, a_name: str, b_name: str) -> str:
    fa, fb = _flatten_scalars(a), _flatten_scalars(b)
    rows = []
    for name in sorted(set(fa) | set(fb)):
        va, vb = fa.get(name), fb.get(name)
        if va is None and vb is None:
            continue
        if (
            isinstance(va, (int, float))
            and isinstance(vb, (int, float))
            and va != 0
        ):
            pct = f"{100.0 * (vb - va) / va:+.1f}%"
            delta = _fmt(vb - va)
        else:
            pct = ""
            delta = "" if va == vb else "changed"
        rows.append((name, _fmt(va), _fmt(vb), delta, pct))
    head = [f"== diff: {a_name} -> {b_name} =="]
    head.append(_table(rows, ("metric", "a", "b", "delta", "delta%")))
    return "\n".join(head)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Render, diff or validate erp metrics artifacts."
    )
    ap.add_argument("paths", nargs="+", help="JSONL stream or run-report JSON")
    ap.add_argument(
        "--diff", action="store_true",
        help="diff two run reports (exactly two paths)",
    )
    ap.add_argument(
        "--check", action="store_true",
        help="validate each report against the schema; exit 1 on failure",
    )
    args = ap.parse_args(argv)

    if args.diff:
        if len(args.paths) != 2:
            ap.error("--diff needs exactly two paths")
        loaded = []
        for p in args.paths:
            report, _ = load_report(p)
            if report is None:
                print(f"{p}: no run report found", file=sys.stderr)
                return 1
            loaded.append(report)
        print(diff(loaded[0], loaded[1], *args.paths))
        return 0

    if args.check:
        bad = 0
        for p in args.paths:
            doc = _raw_json(p)
            trace_lines = _trace_stream_lines(p) if doc is None else None
            if isinstance(doc, dict) and doc.get("schema") == BLACKBOX_SCHEMA:
                errs = validate_dump(doc)
                schema = BLACKBOX_SCHEMA
            elif (
                isinstance(doc, dict)
                and doc.get("schema") == INCIDENT_SCHEMA
            ):
                errs = validate_incident_log(doc)
                schema = INCIDENT_SCHEMA
            elif (
                isinstance(doc, dict)
                and doc.get("schema") == QUORUM_SCHEMA
            ):
                errs = validate_quorum_verdict(doc)
                schema = QUORUM_SCHEMA
            elif (
                isinstance(doc, dict)
                and doc.get("schema") == FLEET_SCHEMA
            ):
                errs = validate_fleet_report(doc)
                schema = FLEET_SCHEMA
            elif (
                isinstance(doc, dict)
                and doc.get("schema") == STEP_REPORT_SCHEMA
            ):
                errs = validate_step_report(doc)
                schema = STEP_REPORT_SCHEMA
            elif (
                isinstance(doc, dict)
                and doc.get("schema") == PRECISION_SCHEMA
            ):
                errs = validate_precision_audit(doc)
                schema = PRECISION_SCHEMA
            elif (
                isinstance(doc, dict)
                and doc.get("schema") == PRECISION_BASELINE_SCHEMA
            ):
                errs = validate_precision_baseline(doc)
                schema = PRECISION_BASELINE_SCHEMA
            elif (
                isinstance(doc, dict)
                and doc.get("schema") == TIMELINE_SCHEMA
                and "traceEvents" not in doc
            ):
                errs = validate_fleet_timeline(doc)
                schema = TIMELINE_SCHEMA
            elif isinstance(doc, dict) and isinstance(
                doc.get("traceEvents"), list
            ):
                errs = validate_chrome(doc)
                schema = "chrome-trace"
            elif trace_lines is not None:
                errs = validate_stream(trace_lines)
                schema = TRACE_SCHEMA
            elif (
                doc is None
                and (steptime_lines := _steptime_stream_lines(p)) is not None
            ):
                errs = validate_steptime_stream(steptime_lines)
                schema = STEPTIME_SCHEMA
            elif (
                doc is None
                and (slo_lines := _slo_stream_lines(p)) is not None
            ):
                errs = validate_slo_stream(slo_lines)
                schema = SLO_SCHEMA
            elif (
                doc is None and _is_journal_stream(p)
            ) or (
                # a fully-compacted journal is a single close record, so
                # it parses as one JSON doc — route by schema
                isinstance(doc, dict) and doc.get("schema") == JOURNAL_SCHEMA
            ):
                errs = validate_journal(p)
                schema = JOURNAL_SCHEMA
            else:
                report, _ = load_report(p)
                errs = (
                    ["no run report found"]
                    if report is None
                    else validate_report(report)
                )
                schema = REPORT_SCHEMA
            if errs:
                bad += 1
                print(f"{p}: INVALID")
                for e in errs:
                    print(f"  - {e}")
            else:
                print(f"{p}: OK ({schema})")
        return 1 if bad else 0

    for p in args.paths:
        report, heartbeats = load_report(p)
        print(render(report, heartbeats, p))
    return 0


if __name__ == "__main__":
    sys.exit(main())
