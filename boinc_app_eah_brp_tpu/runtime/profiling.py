"""Tracing, profiling and device-memory observability.

TPU equivalents of the reference's observability hooks (SURVEY.md section 5):

* ``device_memory_status(tag)`` — per-device HBM usage logging at each
  pipeline stage, the analogue of the CUDA backend's global-memory
  watermark prints after every ``set_up_*`` call
  (``cuda_utilities.c:240-259``, called from ``demod_binary.c:1126-1147``).
* ``trace(...)`` / ``ERP_PROFILE_DIR`` — ``jax.profiler`` trace capture,
  the analogue of the CUDA profiler counter config
  (``cuda/app/profiler.cfg``): set the env var or pass ``--profile-dir``
  and every search run drops an xplane trace viewable in TensorBoard /
  XProf.
* ``phase(name)`` — wall-clock + memory bracket around a pipeline stage at
  debug level, the analogue of the reference's pervasive per-kernel-launch
  ``logMessage(debug, ...)`` lines (``demod_binary_cuda.cu:435,519,573``).

Everything degrades gracefully on backends without memory introspection
(CPU returns no stats) and is a no-op above the active log level.
"""

from __future__ import annotations

import contextlib
import os
import time

from . import logging as erplog
from . import metrics, tracing

PROFILE_DIR_ENV = "ERP_PROFILE_DIR"


def memory_stats() -> list[dict]:
    """One dict per local device: bytes in use / limit / peak (empty values
    when the backend exposes no stats, e.g. CPU)."""
    import jax

    out = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        out.append(
            {
                "device": f"{dev.platform}:{dev.id}",
                "bytes_in_use": stats.get("bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            }
        )
    return out


def _fmt_bytes(n) -> str:
    if n is None:
        return "n/a"
    return f"{n / (1024.0 * 1024.0):.1f} MB"


def device_memory_status(tag: str, level: erplog.Level = erplog.Level.DEBUG) -> None:
    """Log current/peak HBM per device, like the reference's
    "Used %u MB out of %u MB global memory" prints.

    Early-returns when ``level`` is suppressed: no device walk, and — for
    processes that never needed jax — no jax import either."""
    if not erplog.enabled(level):
        return
    for s in memory_stats():
        in_use, limit, peak = (
            s["bytes_in_use"],
            s["bytes_limit"],
            s["peak_bytes_in_use"],
        )
        if in_use is None and limit is None:
            erplog.log_message(
                level, True, "%s: device %s exposes no memory stats\n", tag, s["device"]
            )
            continue
        erplog.log_message(
            level,
            True,
            "%s: device %s using %s of %s (peak %s)\n",
            tag,
            s["device"],
            _fmt_bytes(in_use),
            _fmt_bytes(limit),
            _fmt_bytes(peak),
        )


@contextlib.contextmanager
def phase(
    name: str, level: erplog.Level = erplog.Level.DEBUG,
    span: str | None = None,
):
    """Debug bracket: wall time + post-phase memory for one pipeline stage.

    The wall time always lands in the metrics registry under ``name``
    and on the span ``span`` (default ``name``), which the block gets to
    attach args to; the log lines and the per-device memory walk only
    happen when ``level`` clears the active log threshold."""
    loud = erplog.enabled(level)
    t0 = time.perf_counter()
    if loud:
        erplog.log_message(level, True, "phase %s: start\n", name)
    try:
        with tracing.span(span or name) as sp:
            yield sp
    finally:
        dt = time.perf_counter() - t0
        metrics.record_phase(name, dt)
        if loud:
            erplog.log_message(
                level, True, "phase %s: done in %.3f s\n", name, dt
            )
            device_memory_status(f"phase {name}", level)


@contextlib.contextmanager
def trace(logdir: str | None = None):
    """``jax.profiler`` trace capture around a block.

    ``logdir`` falls back to ``$ERP_PROFILE_DIR``; when neither is set this
    is a free no-op, so callers can wrap unconditionally.
    """
    logdir = logdir or os.environ.get(PROFILE_DIR_ENV)
    if not logdir:
        yield
        return
    import jax

    os.makedirs(logdir, exist_ok=True)
    erplog.info("Capturing jax.profiler trace to %s\n", logdir)
    metrics.note_trace(logdir)
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        # an exception mid-search must still flush the xplane file —
        # a truncated trace of a crashing run is the one you most need
        jax.profiler.stop_trace()
        erplog.info("Profiler trace written to %s\n", logdir)

