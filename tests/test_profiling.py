"""Observability module: memory stats, phase brackets, trace capture.

TPU analogue of the reference's memory-watermark logging
(``cuda_utilities.c:240-259``) and profiler config (``cuda/app/profiler.cfg``);
SURVEY.md section 5.
"""

import os

import jax.numpy as jnp
import pytest

from boinc_app_eah_brp_tpu.runtime import logging as erplog
from boinc_app_eah_brp_tpu.runtime import metrics, profiling
from boinc_app_eah_brp_tpu.runtime.logging import Level


def test_memory_stats_one_entry_per_device():
    stats = profiling.memory_stats()
    assert len(stats) >= 1
    for s in stats:
        assert set(s) == {"device", "bytes_in_use", "bytes_limit", "peak_bytes_in_use"}
        assert ":" in s["device"]


def test_device_memory_status_logs(capsys):
    profiling.device_memory_status("unit test", level=Level.INFO)
    err = capsys.readouterr().err
    assert "unit test" in err


def test_phase_bracket_logs_duration(capsys):
    with profiling.phase("median", level=Level.INFO):
        jnp.ones(8).block_until_ready()
    err = capsys.readouterr().err
    assert "phase median: start" in err
    assert "phase median: done in" in err


def test_trace_noop_without_dir(monkeypatch):
    monkeypatch.delenv(profiling.PROFILE_DIR_ENV, raising=False)
    with profiling.trace():
        pass  # must not require jax.profiler or create any files


def test_trace_writes_xplane(tmp_path):
    logdir = str(tmp_path / "trace")
    with profiling.trace(logdir):
        jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))).block_until_ready()
    found = [
        os.path.join(root, f)
        for root, _, files in os.walk(logdir)
        for f in files
        if f.endswith(".xplane.pb")
    ]
    assert found, "expected an xplane trace file"


def test_device_memory_status_early_returns_when_suppressed(monkeypatch):
    """With the level suppressed there must be NO device walk at all (the
    old code paid jax.local_devices() on every phase exit even with
    logging off)."""
    def boom():
        raise AssertionError("memory_stats must not be called")

    monkeypatch.setattr(profiling, "memory_stats", boom)
    saved = erplog.threshold()
    try:
        erplog.set_level(Level.INFO)
        profiling.device_memory_status("suppressed", level=Level.DEBUG)
        with profiling.phase("quiet", level=Level.DEBUG):
            pass
        # at an emitting level the walk still happens (and raises here)
        with pytest.raises(AssertionError, match="must not be called"):
            profiling.device_memory_status("loud", level=Level.INFO)
    finally:
        erplog.set_level(saved)


def test_phase_suppressed_still_records_metrics(capsys):
    """Phase wall time lands in the metrics registry even when the log
    line is suppressed — the run report keeps per-phase walls without
    requiring debug logging."""
    assert metrics.configure(force=True)
    saved = erplog.threshold()
    try:
        erplog.set_level(Level.ERROR)
        with profiling.phase("silent stage", level=Level.DEBUG):
            pass
        assert capsys.readouterr().err == ""
        phases = metrics.snapshot()["phases"]
        assert phases["silent stage"]["count"] == 1
        assert phases["silent stage"]["wall_s"] >= 0.0
    finally:
        erplog.set_level(saved)
        metrics.finish(0)


def test_phase_span_name_and_args_apart_from_its_phase_key():
    """A phase keeps its run-report key and opens the span it names,
    to which the block attaches args."""
    from boinc_app_eah_brp_tpu.runtime import tracing

    assert metrics.configure(force=True)
    assert tracing.configure(force=True)
    try:
        with profiling.phase("oracle rescore", span="rescore") as sp:
            sp.set(n_eval=3)
        (ev,) = tracing.events()
        assert ev["name"] == "rescore" and ev["args"] == {"n_eval": 3}
        assert metrics.snapshot()["phases"]["oracle rescore"]["count"] == 1
    finally:
        tracing.finish(0)
        metrics.finish(0)


def test_trace_flushes_on_exception(tmp_path):
    """An exception inside the traced block must still close and flush
    the profiler trace (try/finally hardening) AND propagate; the run
    report records that tracing was active."""
    assert metrics.configure(force=True)
    logdir = str(tmp_path / "crash-trace")
    try:
        with pytest.raises(RuntimeError, match="mid-trace"):
            with profiling.trace(logdir):
                jnp.dot(
                    jnp.ones((64, 64)), jnp.ones((64, 64))
                ).block_until_ready()
                raise RuntimeError("mid-trace failure")
        found = [
            f
            for root, _, files in os.walk(logdir)
            for f in files
            if f.endswith(".xplane.pb")
        ]
        assert found, "trace must be flushed even when the block raises"
    finally:
        report = metrics.finish(1)
    assert report["tracing"]["active"] is True
    assert logdir in report["tracing"]["dirs"]
