"""Measured step time: the observatory's measured half.

Layer 10 of the observability stack (docs/observability.md).  Every
device-side number below layer 10 is *modeled* — the trace timeline's
device lane is synthesized from ``stage_time_model`` roofline fractions
and the AOT ledger gates bytes, not time.  This module measures: the
dispatch loop (``models/search.py::_run_bank_attempt``) brackets each
batched bank step with monotonic-clock + ``jax.block_until_ready``
timing, so "how long does one step really take" is a recorded number a
regression gate can hold (``tools/step_report.py``,
``STEPTIME_BASELINE.json``), not a roofline estimate.

Measuring is intrusive by design: draining every step serializes the
lookahead pipeline, so the bracket lives behind a cheap always-on gate
(``ERP_STEPTIME``) with the same contract as ``tracing`` / ``metrics``:

* **Near-zero cost when disabled.**  ``recorder()`` returns one shared
  no-op object; the steady-state loop cost is two no-op method calls
  per batch, no allocation, and ``import steptime`` never imports jax
  (``tests/test_steptime.py`` bounds it like the tracing precedent).
* **Zero compiled-code effect.**  The bracket only times the host side
  of an unchanged jitted step — byte-identical results and zero extra
  recompiles with the gate on (``tools/fleet_bench.py`` proves both).
* **Thread-safe.**  One recorder per dispatch loop; the shared context
  appends under a lock, so a resident server's serialized Sessions all
  land in one ordered record stream.

Three outputs per measured window: a ``steptime.step_ms`` histogram
observation (``runtime/metrics.py``), a ``step-measured`` instant in
the host trace stream (``runtime/tracing.py``), and a record in this
module's own ``erp-steptime/1`` JSONL artifact when
``ERP_STEPTIME_FILE`` names a path.

:func:`capture_profile` is the on-demand device half (tentpole b): it
wraps a block in a ``jax.profiler`` trace session, parses the xplane
through ``runtime/devicecost.py`` into per-stage *measured* device
records via the ``stage_of_op_name`` registry, and merges them into the
Chrome export as a ``device:measured`` lane alongside the estimated
one.  ``ERP_STEPTIME_PROFILE=<dir>`` arms it for the Session's template
loop without code changes (:func:`maybe_capture_profile`).

Env surface: ``ERP_STEPTIME`` (truthy enables the bracket),
``ERP_STEPTIME_FILE`` (JSONL artifact path; implies enabled),
``ERP_STEPTIME_EVENTS`` (ring capacity, default 65536),
``ERP_STEPTIME_PROFILE`` (profiler logdir for the session's template
loop).  Env fallbacks apply only to the default context.
"""

from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field

from . import logging as erplog
from .percentiles import latency_block

STEPTIME_ENV = "ERP_STEPTIME"
STEPTIME_FILE_ENV = "ERP_STEPTIME_FILE"
STEPTIME_EVENTS_ENV = "ERP_STEPTIME_EVENTS"
STEPTIME_PROFILE_ENV = "ERP_STEPTIME_PROFILE"

STEPTIME_SCHEMA = "erp-steptime/1"
REPORT_SCHEMA = "erp-step-report/1"
BASELINE_SCHEMA = "erp-steptime-baseline/1"

_DEFAULT_RING = 65536

_FALSY = ("", "0", "false", "no", "off")


def _env_truthy(name: str) -> bool:
    return os.environ.get(name, "").strip().lower() not in _FALSY


class _NullRecorder:
    """Shared no-op bracket: the whole disabled-path cost per batch is
    two no-op method calls — no perf_counter read, no jax, nothing."""

    __slots__ = ()

    def begin(self) -> None:
        pass

    def observe(self, state, start, stop) -> None:
        pass


_NULL_RECORDER = _NullRecorder()


class _Recorder:
    """One live bracket for one dispatch loop: ``begin()`` stamps the
    clock before the step dispatch, ``observe(state, start, stop)``
    drains the step (``jax.block_until_ready``) and records the wall
    between them — dispatch + device execution, the measured step
    latency."""

    __slots__ = ("_ctx", "_t0")

    def __init__(self, ctx: "StepTimeContext"):
        self._ctx = ctx
        self._t0 = 0.0

    def begin(self) -> None:
        self._t0 = time.perf_counter()

    def observe(self, state, start, stop) -> None:
        import jax  # measurement path only; the gate never imports jax

        jax.block_until_ready(state)
        self._ctx.record(
            int(start), int(stop),
            (time.perf_counter() - self._t0) * 1e3,
        )


# every live context, for the atexit terminator (tracing/metrics idiom)
_contexts_lock = threading.Lock()
_all_contexts: list = []


class StepTimeContext:
    """One measured-step-time window: bounded ring + optional JSONL
    stream + metrics/tracing feeds."""

    def __init__(self, name: str = "scoped", env_fallback: bool = False):
        self.name = name
        self._env_fallback = env_fallback
        self._env_checked = False
        self._lock = threading.Lock()
        self._enabled = False
        self._stream_path: str | None = None
        self._stream_broken = False
        self._ring: deque = deque(maxlen=_DEFAULT_RING)
        self._total = 0
        self._templates = 0
        self._sum_ms = 0.0
        self._last_t = 0.0
        with _contexts_lock:
            _all_contexts.append(self)

    # -- gate -------------------------------------------------------------

    def enabled(self) -> bool:
        return self._enabled

    def _maybe_arm_from_env(self) -> None:
        """Lazy env arming: the bracket is always installed in the
        dispatch loop, so the gate must be decidable without any driver
        wiring — first ``recorder()`` call checks ``$ERP_STEPTIME`` /
        ``$ERP_STEPTIME_FILE`` exactly once per process."""
        if self._env_checked or self._enabled:
            return
        self._env_checked = True
        if _env_truthy(STEPTIME_ENV) or os.environ.get(STEPTIME_FILE_ENV):
            self.configure()

    def recorder(self):
        """The per-loop bracket: a live recorder when measuring, the
        shared no-op otherwise.  Bind once outside the dispatch loop,
        like the metrics instruments."""
        if self._env_fallback:
            self._maybe_arm_from_env()
        if not self._enabled:
            return _NULL_RECORDER
        return _Recorder(self)

    # -- recording --------------------------------------------------------

    def record(self, start: int, stop: int, ms: float) -> None:
        """Append one measured window.  Feeds the ring, the JSONL
        stream, the ``steptime.step_ms`` histogram and a
        ``step-measured`` trace instant (each layer independently
        no-ops when unarmed)."""
        if not self._enabled:
            return
        with self._lock:
            self._total += 1
            seq = self._total
            t = time.time()
            if t < self._last_t:  # wall clock stepped back: keep monotone
                t = self._last_t
            self._last_t = t
            rec = {
                "kind": "step",
                "seq": seq,
                "t": round(t, 6),
                "start": start,
                "stop": stop,
                "templates": max(0, stop - start),
                "ms": round(float(ms), 3),
            }
            self._ring.append(rec)
            self._templates += rec["templates"]
            self._sum_ms += float(ms)
        self._stream_record(rec)
        try:
            from . import metrics, tracing

            metrics.histogram(
                "steptime.step_ms", metrics.LATENCY_BUCKETS_MS, unit="ms"
            ).observe(float(ms))
            tracing.instant(
                "step-measured", start=start, stop=stop,
                ms=round(float(ms), 3),
            )
        except Exception:
            pass  # telemetry must never take down the search

    def records(self, since: int = 0) -> list[dict]:
        """Measured windows with ``seq > since``, oldest first (bounded
        by the ring: a long fleet run keeps the most recent window)."""
        with self._lock:
            return [r for r in self._ring if r["seq"] > since]

    def count(self) -> int:
        with self._lock:
            return self._total

    def summary(self) -> dict:
        """The scoreboard block: ``{windows, templates,
        templates_per_sec, step_ms: {n, p50, p95, p99, mean, max}}``
        over the ring's windows (percentiles) and lifetime totals
        (throughput)."""
        with self._lock:
            ring = list(self._ring)
            total = self._total
            templates = self._templates
            sum_ms = self._sum_ms
        return {
            "windows": total,
            "templates": templates,
            "templates_per_sec": round(
                templates / (sum_ms / 1e3), 3
            ) if sum_ms > 0 else 0.0,
            "step_ms": latency_block([r["ms"] for r in ring], digits=3),
        }

    # -- stream -----------------------------------------------------------

    def _stream_record(self, rec: dict) -> None:
        if self._stream_path is None or self._stream_broken:
            return
        try:
            line = json.dumps(rec, default=str)
            with self._lock:
                with open(self._stream_path, "a") as f:
                    f.write(line + "\n")
        except OSError as e:
            self._stream_broken = True
            erplog.warn("Steptime stream %s unwritable (%s); disabling.\n",
                        self._stream_path, e)

    def configure(
        self, steptime_file: str | None = None, ring_events: int | None = None,
        force: bool = False,
    ) -> bool:
        """Arm this window; returns True when enabled.  On the default
        context the stream path falls back to ``$ERP_STEPTIME_FILE``;
        ``force`` arms the in-memory ring without a file (tests, tools).
        Reconfiguring resets the ring — each run's windows stand alone."""
        path = steptime_file or (
            os.environ.get(STEPTIME_FILE_ENV) if self._env_fallback else None
        ) or None
        if path is None and not force and not (
            self._env_fallback and _env_truthy(STEPTIME_ENV)
        ):
            return False
        if ring_events is None:
            try:
                ring_events = int(
                    os.environ.get(STEPTIME_EVENTS_ENV, _DEFAULT_RING)
                )
            except ValueError:
                ring_events = _DEFAULT_RING
        with self._lock:
            self._ring = deque(maxlen=max(16, ring_events))
            self._total = 0
            self._templates = 0
            self._sum_ms = 0.0
            self._last_t = 0.0
            self._stream_broken = False
            self._stream_path = path
            self._enabled = True
        _register_atexit()
        if path:
            try:  # each run's stream stands alone (append would interleave)
                if os.path.exists(path):
                    os.remove(path)
            except OSError:
                pass
            self._stream_record(
                {
                    "kind": "start",
                    "schema": STEPTIME_SCHEMA,
                    "t": time.time(),
                    "pid": os.getpid(),
                    "argv": sys.argv,
                }
            )
        return True

    def finish(self, exit_status=None) -> dict | None:
        """Close the window: append the finish line (with the summary
        block) and disable.  Returns the summary, or None when never
        enabled.  Idempotent."""
        if not self._enabled:
            return None
        summary = self.summary()
        self._stream_record(
            {
                "kind": "finish",
                "t": time.time(),
                "exit_status": exit_status,
                "summary": summary,
            }
        )
        with self._lock:
            self._enabled = False
            self._ring.clear()
            self._total = 0
            self._templates = 0
            self._sum_ms = 0.0
        return summary

    close = finish


_DEFAULT = StepTimeContext(name="default", env_fallback=True)


def default_context() -> StepTimeContext:
    return _DEFAULT


# ---------------------------------------------------------------------------
# module-level delegation


def enabled() -> bool:
    return _DEFAULT.enabled()


def recorder():
    return _DEFAULT.recorder()


def record(start: int, stop: int, ms: float) -> None:
    _DEFAULT.record(start, stop, ms)


def records(since: int = 0) -> list[dict]:
    return _DEFAULT.records(since)


def count() -> int:
    return _DEFAULT.count()


def summary() -> dict:
    return _DEFAULT.summary()


def configure(
    steptime_file: str | None = None, ring_events: int | None = None,
    force: bool = False,
) -> bool:
    return _DEFAULT.configure(
        steptime_file=steptime_file, ring_events=ring_events, force=force
    )


def finish(exit_status=None) -> dict | None:
    return _DEFAULT.finish(exit_status)


def _atexit_finish() -> None:
    with _contexts_lock:
        live = [c for c in _all_contexts if c.enabled()]
    for c in live:
        c.finish("abnormal-exit")


_atexit_registered = False


def _register_atexit() -> None:
    global _atexit_registered
    if not _atexit_registered:
        _atexit_registered = True
        atexit.register(_atexit_finish)


# ---------------------------------------------------------------------------
# on-demand device profiling (tentpole b)


@dataclass
class ProfileCapture:
    """Result of one :func:`capture_profile` session: the raw device
    events, the per-stage records merged into the Chrome export, and
    the per-stage measured totals."""

    logdir: str
    lane: str = "device:measured"
    records: list = field(default_factory=list)
    stage_records: list = field(default_factory=list)
    stage_ms: dict = field(default_factory=dict)
    warning: str | None = None


@contextmanager
def capture_profile(
    logdir: str, lane: str = "device:measured", op_scopes: dict | None = None
):
    """First-class device-profiling orchestrator: ``jax.profiler``
    start/stop around the with-block (N dispatch windows), xplane parse
    into per-stage *measured* device records via the
    ``devicecost.stage_of_op_name`` registry, merged into the Chrome
    export as ``lane`` alongside the estimated one.  On a TPU, whose
    events are named after HLO instructions, pass ``op_scopes``
    (``devicecost.hlo_op_scopes`` of the profiled executable's text).

    Yields a :class:`ProfileCapture` filled on exit.  Chip-free runs
    yield an empty capture with ``warning`` set (the CPU backend's
    xplane has no device plane) — a logged warning, never an error:
    profiling is diagnostics, the search result is the product."""
    import jax

    from . import devicecost, metrics, tracing

    cap = ProfileCapture(logdir=str(logdir), lane=lane)
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(str(logdir))
    try:
        yield cap
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # a dead trace session must not mask the run
            cap.warning = f"profiler stop failed: {e}"
        parsed = devicecost.collect_profiler_device_records(str(logdir))
        cap.records = list(parsed.records)
        cap.warning = cap.warning or parsed.warning
        if cap.warning:
            erplog.warn("steptime.capture_profile: %s\n", cap.warning)
        cap.stage_records = devicecost.stage_records(
            cap.records, lane=lane, op_scopes=op_scopes
        )
        for r in cap.stage_records:
            stage = r["args"].get("stage")
            cap.stage_ms[stage] = round(
                cap.stage_ms.get(stage, 0.0) + r["dur_us"] / 1e3, 3
            )
        if cap.stage_records:
            tracing.add_device_records(cap.stage_records)
        metrics.note_trace(str(logdir))


def maybe_capture_profile():
    """The env-armed form the Session wraps its template loop in:
    :func:`capture_profile` when ``$ERP_STEPTIME_PROFILE`` names a
    logdir, else a no-op context (no jax import, nothing written)."""
    logdir = os.environ.get(STEPTIME_PROFILE_ENV)
    if not logdir:
        return nullcontext(None)
    return capture_profile(logdir)


# ---------------------------------------------------------------------------
# validation (shared by tools/metrics_report.py --check and tests)


def _is_num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def validate_stream(lines: list[dict]) -> list[str]:
    """Structural check of a parsed ``erp-steptime/1`` JSONL stream:
    start header, per-step records with nonnegative ``ms`` and
    non-decreasing timestamps / strictly increasing ``seq``, exactly
    one trailing finish line carrying the summary."""
    errs: list[str] = []
    if not lines:
        return ["empty steptime stream"]
    head = lines[0]
    if not isinstance(head, dict) or head.get("kind") != "start":
        errs.append("first record must be kind=start")
    elif head.get("schema") != STEPTIME_SCHEMA:
        errs.append(
            f"schema is {head.get('schema')!r}, expected {STEPTIME_SCHEMA!r}"
        )
    last_t = -1.0
    last_seq = 0
    finishes = 0
    for i, rec in enumerate(lines[1:], start=2):
        if not isinstance(rec, dict):
            errs.append(f"line {i}: not a JSON object")
            continue
        kind = rec.get("kind")
        if kind == "finish":
            finishes += 1
            if not isinstance(rec.get("summary"), dict):
                errs.append(f"line {i}: finish lacks summary object")
            continue
        if kind != "step":
            errs.append(f"line {i}: unknown kind {kind!r}")
            continue
        if not _is_num(rec.get("ms")) or rec.get("ms", -1) < 0:
            errs.append(f"line {i}: ms missing or negative")
        if not isinstance(rec.get("seq"), int) or rec["seq"] <= last_seq:
            errs.append(
                f"line {i}: seq {rec.get('seq')!r} not strictly increasing "
                f"(prev {last_seq})"
            )
        else:
            last_seq = rec["seq"]
        t = rec.get("t")
        if not _is_num(t):
            errs.append(f"line {i}: t missing")
        elif t < last_t:
            errs.append(f"line {i}: t {t} goes backwards (prev {last_t})")
        else:
            last_t = t
        a, b = rec.get("start"), rec.get("stop")
        if not (isinstance(a, int) and isinstance(b, int) and b > a >= 0):
            errs.append(f"line {i}: window [{a}, {b}) is not a valid range")
    if finishes == 0:
        errs.append("no finish record (run died before steptime.finish)")
    elif finishes > 1:
        errs.append(f"{finishes} finish records (expected exactly 1)")
    elif lines[-1].get("kind") != "finish":
        errs.append("finish record is not the last line")
    return errs


def _check_block(block, path: str, errs: list[str]) -> None:
    if not isinstance(block, dict):
        errs.append(f"{path} missing or not an object")
        return
    for key in ("n", "p50", "p95", "p99", "mean", "max"):
        if not _is_num(block.get(key)):
            errs.append(f"{path}.{key} missing or not numeric")


def validate_step_report(doc) -> list[str]:
    """Structural check of an ``erp-step-report/1`` reconciliation
    artifact (``tools/step_report.py``)."""
    errs: list[str] = []
    if not isinstance(doc, dict):
        return ["not a JSON object"]
    if doc.get("schema") != REPORT_SCHEMA:
        errs.append(
            f"schema is {doc.get('schema')!r}, expected {REPORT_SCHEMA!r}"
        )
    if not doc.get("backend"):
        errs.append("missing backend")
    if not _is_num(doc.get("generated_unix")):
        errs.append("missing numeric generated_unix")
    meas = doc.get("measured")
    if not isinstance(meas, dict):
        errs.append("missing measured object")
    else:
        for key in ("windows", "templates", "templates_per_sec"):
            if not _is_num(meas.get(key)):
                errs.append(f"measured.{key} missing or not numeric")
        _check_block(meas.get("step_ms"), "measured.step_ms", errs)
    model = doc.get("modeled")
    if not isinstance(model, dict):
        errs.append("missing modeled object")
    elif not _is_num(model.get("templates_per_sec")):
        errs.append("modeled.templates_per_sec missing or not numeric")
    stages = doc.get("stages")
    if not isinstance(stages, list) or not stages:
        errs.append("missing non-empty stages list")
    else:
        for i, row in enumerate(stages):
            if not isinstance(row, dict) or not row.get("stage"):
                errs.append(f"stage row {i}: missing stage name")
                continue
            for key in ("modeled_fraction", "measured_ms_per_window"):
                if not _is_num(row.get(key)):
                    errs.append(f"stage {row['stage']}: missing numeric {key}")
            frac = row.get("modeled_fraction")
            if _is_num(frac) and not (0.0 <= frac <= 1.0):
                errs.append(
                    f"stage {row['stage']}: modeled_fraction {frac} "
                    "outside [0, 1]"
                )
    if doc.get("device_lane") not in ("measured", "modeled-split"):
        errs.append(
            "device_lane must be 'measured' or 'modeled-split' "
            f"(got {doc.get('device_lane')!r})"
        )
    return errs
