"""Measured step time (runtime/steptime.py): the zero-cost disabled
path (no jax import, no files, bounded per-step overhead — the same
contract the tracing layer pins), env arming, in-memory ring semantics,
the erp-steptime/1 JSONL artifact round-trip, the erp-step-report/1
validator, and the best-effort on-demand device profiling orchestrator."""

import json
import os
import subprocess
import sys
import time

import pytest

from boinc_app_eah_brp_tpu.runtime import metrics, steptime, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import metrics_report  # noqa: E402


@pytest.fixture(autouse=True)
def _reset():
    """Every test leaves the layer disabled for its neighbours."""
    yield
    steptime.finish()


# ---------------------------------------------------------------------------
# the disabled path: no jax, no files, no measurable overhead


def test_disabled_import_pulls_no_jax(tmp_path):
    """Acceptance: with ERP_STEPTIME unset, importing the module and
    running the bracket must not drag jax in — and must not write a
    single file."""
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop(steptime.STEPTIME_ENV, None)
    env.pop(steptime.STEPTIME_FILE_ENV, None)
    code = (
        "import os, sys\n"
        "from boinc_app_eah_brp_tpu.runtime import steptime\n"
        "rec = steptime.recorder()\n"
        "for i in range(100):\n"
        "    rec.begin()\n"
        "    rec.observe(None, i, i + 2)\n"
        "assert not steptime.enabled()\n"
        "assert steptime.count() == 0\n"
        "assert 'jax' not in sys.modules, 'jax imported by steptime'\n"
        "assert not os.listdir('.'), 'disabled steptime wrote files'\n"
        "print('ok')\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=str(tmp_path),
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "ok"


def test_disabled_recorder_is_shared_noop():
    assert not steptime.enabled()
    rec = steptime.recorder()
    assert rec is steptime.recorder()  # one shared inert object
    rec.begin()
    rec.observe(object(), 0, 8)  # inert: nothing recorded
    assert steptime.records() == []
    assert steptime.count() == 0
    assert steptime.finish() is None


def test_disabled_recorder_overhead():
    """The disabled bracket is two no-op method calls per batch; bound
    it loosely (same contract as the disabled tracing span)."""
    n = 100_000
    rec = steptime.recorder()
    t0 = time.perf_counter()
    for i in range(n):
        rec.begin()
        rec.observe(None, i, i + 2)
    dt = time.perf_counter() - t0
    assert dt / n < 2e-6, f"disabled bracket costs {dt / n * 1e9:.0f}ns"


def test_env_arming_per_context(monkeypatch):
    """The bracket is always installed in the dispatch loop, so the
    first recorder() call must decide from the env alone."""
    monkeypatch.delenv(steptime.STEPTIME_ENV, raising=False)
    monkeypatch.delenv(steptime.STEPTIME_FILE_ENV, raising=False)
    off = steptime.StepTimeContext(name="t-off", env_fallback=True)
    assert off.recorder() is steptime.recorder()  # both the shared no-op
    assert not off.enabled()

    monkeypatch.setenv(steptime.STEPTIME_ENV, "1")
    on = steptime.StepTimeContext(name="t-on", env_fallback=True)
    on.recorder()
    assert on.enabled()
    on.finish()

    monkeypatch.setenv(steptime.STEPTIME_ENV, "0")
    explicit_off = steptime.StepTimeContext(name="t-0", env_fallback=True)
    explicit_off.recorder()
    assert not explicit_off.enabled()

    # scoped contexts never self-arm from env (the default ctx owns it)
    scoped = steptime.StepTimeContext(name="t-scoped")
    monkeypatch.setenv(steptime.STEPTIME_ENV, "1")
    scoped.recorder()
    assert not scoped.enabled()


# ---------------------------------------------------------------------------
# ring semantics (in-memory mode, no stream file)


def test_recorder_measures_and_feeds_layers():
    """One measured window lands in the ring, the steptime.step_ms
    histogram and a step-measured trace instant."""
    assert metrics.configure(force=True)
    assert tracing.configure(force=True)
    assert steptime.configure(force=True)
    try:
        rec = steptime.recorder()
        assert type(rec).__name__ == "_Recorder"  # live, not the no-op
        rec.begin()
        rec.observe([1.0, 2.0], 4, 8)  # plain pytree: drains trivially
        (r,) = steptime.records()
        assert r["kind"] == "step"
        assert r["seq"] == 1
        assert r["start"] == 4 and r["stop"] == 8 and r["templates"] == 4
        assert r["ms"] >= 0.0
        summary = steptime.summary()
        assert summary["windows"] == 1 and summary["templates"] == 4
        assert summary["step_ms"]["n"] == 1
        snap = metrics.snapshot()
        assert snap["histograms"]["steptime.step_ms"]["count"] == 1
        assert any(
            e["name"] == "step-measured" for e in tracing.events()
        )
    finally:
        tracing.finish()
        metrics.finish(0)


def test_ring_bounded_and_records_since():
    assert steptime.configure(force=True, ring_events=32)
    for i in range(100):
        steptime.record(i, i + 2, 1.0)
    assert steptime.count() == 100
    ring = steptime.records()
    assert len(ring) == 32
    assert ring[-1]["seq"] == 100  # newest survive
    assert [r["seq"] for r in steptime.records(since=95)] == [
        96, 97, 98, 99, 100,
    ]
    summary = steptime.summary()
    assert summary["windows"] == 100
    assert summary["templates"] == 200  # lifetime total, not ring-bounded
    assert summary["templates_per_sec"] == pytest.approx(2000.0)


def test_reconfigure_resets_the_window():
    assert steptime.configure(force=True)
    steptime.record(0, 2, 1.0)
    assert steptime.configure(force=True)  # a new run's windows stand alone
    assert steptime.count() == 0
    assert steptime.records() == []


# ---------------------------------------------------------------------------
# stream round-trip + metrics_report --check


def _run_streamed(path, windows=3):
    assert steptime.configure(steptime_file=path)
    for i in range(windows):
        steptime.record(i * 2, i * 2 + 2, 1.5 + i)
    return steptime.finish(0)


def test_stream_roundtrip_validates(tmp_path, capsys):
    path = str(tmp_path / "steptime.jsonl")
    summary = _run_streamed(path)
    assert summary["windows"] == 3

    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["kind"] == "start"
    assert lines[0]["schema"] == steptime.STEPTIME_SCHEMA
    assert lines[-1]["kind"] == "finish"
    assert lines[-1]["exit_status"] == 0
    assert lines[-1]["summary"]["windows"] == 3
    assert steptime.validate_stream(lines) == []

    assert metrics_report.main(["--check", path]) == 0
    assert f"OK ({steptime.STEPTIME_SCHEMA})" in capsys.readouterr().out


def test_metrics_report_check_flags_truncated_stream(tmp_path, capsys):
    path = str(tmp_path / "steptime.jsonl")
    _run_streamed(path)
    lines = open(path).read().splitlines()
    with open(path, "w") as f:  # drop the finish terminator (a dead run)
        f.write("\n".join(lines[:-1]) + "\n")
    assert metrics_report.main(["--check", path]) == 1
    assert "no finish record" in capsys.readouterr().out


def test_crash_leaves_stream_with_finish(tmp_path):
    """A run that dies mid-window still terminates its artifact: the
    atexit terminator writes the finish line with abnormal-exit."""
    path = str(tmp_path / "crash.jsonl")
    env = dict(os.environ, PYTHONPATH=REPO)
    env[steptime.STEPTIME_FILE_ENV] = path
    code = (
        "from boinc_app_eah_brp_tpu.runtime import steptime\n"
        "steptime.recorder()\n"  # env-arms from ERP_STEPTIME_FILE
        "steptime.record(0, 2, 1.5)\n"
        # interpreter exits without finish() -> atexit terminator
    )
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True
    )
    assert r.returncode == 0, r.stderr
    lines = [json.loads(l) for l in open(path)]
    assert lines[-1]["kind"] == "finish"
    assert lines[-1]["exit_status"] == "abnormal-exit"
    assert lines[-1]["summary"]["windows"] == 1
    assert steptime.validate_stream(lines) == []


def test_validate_stream_flags_disorder():
    head = {"kind": "start", "schema": steptime.STEPTIME_SCHEMA, "t": 1.0}
    step = {"kind": "step", "seq": 1, "t": 2.0, "start": 0, "stop": 2,
            "templates": 2, "ms": 1.0}
    fin = {"kind": "finish", "t": 3.0, "exit_status": 0, "summary": {}}
    assert steptime.validate_stream([head, step, fin]) == []
    assert steptime.validate_stream([]) == ["empty steptime stream"]
    bad_seq = [head, step, dict(step, seq=1, t=2.5), fin]
    assert any("seq" in e for e in steptime.validate_stream(bad_seq))
    backwards = [head, step, dict(step, seq=2, t=1.5), fin]
    assert any("backwards" in e for e in steptime.validate_stream(backwards))
    bad_window = [head, dict(step, start=5, stop=5), fin]
    assert any("valid range" in e for e in steptime.validate_stream(bad_window))
    negative = [head, dict(step, ms=-1.0), fin]
    assert any("negative" in e for e in steptime.validate_stream(negative))


# ---------------------------------------------------------------------------
# the erp-step-report/1 validator + the committed baseline


def _good_report():
    block = {"n": 8, "p50": 1.0, "p95": 1.3, "p99": 1.5, "mean": 1.1,
             "max": 1.6}
    return {
        "schema": steptime.REPORT_SCHEMA,
        "generated_unix": 1.0,
        "backend": "cpu",
        "chip_model": "v5e",
        "measured": {
            "windows": 8, "templates": 128, "templates_per_sec": 2000.0,
            "gb_per_sec": 7.5, "step_ms": block,
        },
        "modeled": {"templates_per_sec": 9e5, "ms_per_template": 1e-3},
        "stages": [
            {"stage": "resample_split", "modeled_fraction": 0.7,
             "measured_ms_per_window": 0.7},
            {"stage": "rfft_packed+power", "modeled_fraction": 0.3,
             "measured_ms_per_window": 0.3},
        ],
        "device_lane": "modeled-split",
    }


def test_validate_step_report_good_and_bad():
    assert steptime.validate_step_report(_good_report()) == []
    assert steptime.validate_step_report("nope") == ["not a JSON object"]
    bad = dict(_good_report(), schema="erp-step-report/0")
    assert any("schema" in e for e in steptime.validate_step_report(bad))
    bad = dict(_good_report(), stages=[])
    assert any("stages" in e for e in steptime.validate_step_report(bad))
    bad = _good_report()
    bad["stages"][0]["modeled_fraction"] = 1.7
    assert any(
        "outside [0, 1]" in e for e in steptime.validate_step_report(bad)
    )
    bad = _good_report()
    del bad["measured"]["step_ms"]["p95"]
    assert any("p95" in e for e in steptime.validate_step_report(bad))
    bad = dict(_good_report(), device_lane="vibes")
    assert any(
        "device_lane" in e for e in steptime.validate_step_report(bad)
    )


def test_committed_baseline_is_well_formed():
    doc = json.load(open(os.path.join(REPO, "STEPTIME_BASELINE.json")))
    assert doc["schema"] == steptime.BASELINE_SCHEMA
    assert doc["backend"] == "cpu"
    for key in ("p50_step_ms_max", "p95_step_ms_max", "templates_per_sec_min"):
        assert isinstance(doc[key], (int, float)) and doc[key] > 0


# ---------------------------------------------------------------------------
# on-demand device profiling (best-effort by contract)


def test_maybe_capture_profile_noop_without_env(monkeypatch):
    monkeypatch.delenv(steptime.STEPTIME_PROFILE_ENV, raising=False)
    with steptime.maybe_capture_profile() as cap:
        assert cap is None


def test_capture_profile_is_best_effort(tmp_path):
    """A profiler session around real dispatches must never raise: on
    the CPU backend the xplane decodes but holds no device plane, so it
    yields an empty capture with the warning explaining WHAT was
    skipped."""
    import jax
    import jax.numpy as jnp

    logdir = str(tmp_path / "prof")
    with steptime.capture_profile(logdir) as cap:
        jax.jit(lambda x: x * 2.0)(jnp.ones(64)).block_until_ready()
    assert cap.logdir == logdir
    assert cap.lane == "device:measured"
    assert isinstance(cap.records, list)
    assert isinstance(cap.stage_records, list)
    assert isinstance(cap.stage_ms, dict)
    if jax.default_backend() == "cpu":  # diagnosable, not silent
        assert cap.records == []
        assert "no device plane" in cap.warning
