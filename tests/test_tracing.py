"""Host span tracing (runtime/tracing.py): the zero-cost disabled path,
ring/stream/Chrome-export consistency, cross-thread trace contexts, the
validators that gate the artifacts, and the trace_report / cost_ledger
reductions built on top."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from boinc_app_eah_brp_tpu.runtime import metrics, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import cost_ledger  # noqa: E402
import metrics_report  # noqa: E402
import trace_report  # noqa: E402


@pytest.fixture(autouse=True)
def _reset():
    """Every test leaves the layer disabled for its neighbours."""
    yield
    tracing.finish()
    tracing.set_context(None)


# ---------------------------------------------------------------------------
# the disabled path: no jax, no files, no measurable overhead


def test_disabled_import_pulls_no_jax(tmp_path):
    """Acceptance: with ERP_TRACE_FILE unset, importing and using the
    span API must not drag jax in — and must not write a single file."""
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop(tracing.TRACE_FILE_ENV, None)
    code = (
        "import os, sys\n"
        "from boinc_app_eah_brp_tpu.runtime import tracing\n"
        "with tracing.span('dispatch', start=0):\n"
        "    tracing.instant('marker')\n"
        "tracing.new_context()\n"
        "assert 'jax' not in sys.modules, 'jax imported by tracing'\n"
        "assert not os.listdir('.'), 'disabled tracing wrote files'\n"
        "print('ok')\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=str(tmp_path),
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "ok"


def test_disabled_span_is_shared_noop():
    assert not tracing.enabled()
    s = tracing.span("dispatch", start=3)
    assert s is tracing.span("drain")  # one shared inert object
    with s:
        s.set(stop=4)  # inert
    assert tracing.events() == []
    assert tracing.open_spans() == []
    assert tracing.new_context() == 0


def test_disabled_span_overhead():
    """The disabled span is a flag test returning a shared no-op; bound
    the with-block loosely (same contract as the unarmed fault point)."""
    n = 100_000
    sp = tracing.span
    t0 = time.perf_counter()
    for _ in range(n):
        with sp("dispatch"):
            pass
    dt = time.perf_counter() - t0
    assert dt / n < 2e-6, f"disabled span costs {dt / n * 1e9:.0f}ns"


def test_disabled_span_with_jax_loaded_stays_shared_noop():
    """Once jax is imported the disabled path adds one is_enabled()
    check: with no profiler recording it is still the shared no-op, and
    still within the bound."""
    import jax  # noqa: F401

    assert tracing.span("a") is tracing.span("b") is tracing._NULL_SPAN
    n = 100_000
    sp = tracing.span
    t0 = time.perf_counter()
    for _ in range(n):
        with sp("dispatch"):
            pass
    dt = time.perf_counter() - t0
    assert dt / n < 2e-6, f"disabled span costs {dt / n * 1e9:.0f}ns"


# ---------------------------------------------------------------------------
# the profiler trace: every span is an erp:<name> annotation


def _profiled(tmp_path, body):
    """Run ``body`` under a profiler session; the host spans of the
    xplane as ``benchmark/trace.py`` reads them."""
    import jax

    from benchmark import trace as bench_trace

    logdir = str(tmp_path / "prof")
    jax.profiler.start_trace(logdir)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return bench_trace.host_spans(bench_trace.load_planes(logdir))


@pytest.mark.parametrize("armed", [False, True], ids=["unarmed", "jsonl"])
def test_span_lands_in_profiler_trace_on_every_thread(tmp_path, armed):
    """With or without the JSONL layer, a span on the main thread and on
    a pool thread are both host events ``erp:x`` of the xplane, args left
    out of the name."""
    from concurrent.futures import ThreadPoolExecutor

    if armed:
        assert tracing.configure(force=True)

    def body():
        with tracing.span("x", start=0):
            time.sleep(0.002)

        def on_pool():
            with tracing.span("x", start=1):
                time.sleep(0.002)

        with ThreadPoolExecutor(max_workers=1) as pool:
            pool.submit(on_pool).result()

    spans = _profiled(tmp_path, body)
    assert [s[0] for s in spans].count("erp:x") == 2
    assert all(e >= s for _, s, e in spans)
    if armed:
        assert [e["name"] for e in tracing.events()] == ["x", "x"]
        assert tracing.events()[0]["args"] == {"start": 0}


def test_span_opened_before_the_profiler_is_absent(tmp_path):
    """The documented limit: a span already open when the profiler
    starts is not in the trace, its child opened after is."""
    outer = tracing.span("outer")

    def body():
        with tracing.span("inner"):
            pass

    outer.__enter__()
    try:
        spans = _profiled(tmp_path, body)
    finally:
        outer.__exit__(None, None, None)
    assert [s[0] for s in spans] == ["erp:inner"]


# ---------------------------------------------------------------------------
# ring semantics (in-memory mode, no stream file)


def test_ring_records_nesting_and_monotone_ends():
    assert tracing.configure(force=True)
    with tracing.span("template loop"):
        with tracing.span("dispatch", start=0, stop=8):
            pass
        with tracing.span("drain"):
            pass
    evs = tracing.events()
    names = [e["name"] for e in evs]
    # children complete before the parent; end_us never goes backwards
    assert names == ["dispatch", "drain", "template loop"]
    assert [e["depth"] for e in evs] == [1, 1, 0]
    ends = [e["end_us"] for e in evs]
    assert ends == sorted(ends)
    assert evs[0]["args"] == {"start": 0, "stop": 8}
    assert all(e["dur_us"] >= 0 for e in evs)


def test_span_records_error_and_set_args():
    tracing.configure(force=True)
    with pytest.raises(ValueError):
        with tracing.span("checkpoint") as sp:
            sp.set(n_done=17)
            raise ValueError("boom")
    (ev,) = tracing.events()
    assert ev["error"] == "ValueError"
    assert ev["args"]["n_done"] == 17


def test_ring_is_bounded():
    tracing.configure(force=True, ring_events=32)
    for i in range(100):
        with tracing.span("dispatch", i=i):
            pass
    evs = tracing.events()
    assert len(evs) == 32
    assert evs[-1]["args"]["i"] == 99  # newest survive
    summary = tracing.finish(0)
    assert summary["spans_total"] == 100
    assert summary["spans_dropped"] == 68


def test_open_spans_snapshot_shows_live_stack():
    tracing.configure(force=True)
    with tracing.span("setup"):
        with tracing.span("whitening"):
            snap = tracing.open_spans()
    assert [s["name"] for s in snap] == ["setup", "whitening"]
    assert all(s["elapsed_ms"] >= 0 for s in snap)
    assert tracing.open_spans() == []


def test_context_propagates_across_threads():
    tracing.configure(force=True)
    ctx = tracing.new_context()
    assert ctx == 1

    def worker(adopted):
        tracing.set_context(adopted)
        with tracing.span("prefetch-compute", tid="prefetch"):
            pass

    t = threading.Thread(target=worker, args=(tracing.context(),))
    t.start()
    t.join()
    with tracing.span("dispatch"):
        pass
    by_name = {e["name"]: e for e in tracing.events()}
    assert by_name["prefetch-compute"]["ctx"] == ctx
    assert by_name["prefetch-compute"]["tid"] == "prefetch"
    assert by_name["dispatch"]["ctx"] == ctx


def test_spans_bridge_into_metrics_histograms():
    metrics.configure(force=True)
    tracing.configure(force=True)
    with tracing.span("drain"):
        pass
    snap = metrics.snapshot()
    assert "span.drain_ms" in snap["histograms"]
    assert snap["histograms"]["span.drain_ms"]["count"] == 1
    metrics.finish(0)


# ---------------------------------------------------------------------------
# stream + Chrome export round-trip


def _run_traced(path):
    """One small multi-thread traced window against a stream file."""
    assert tracing.configure(trace_file=path)
    ctx = tracing.new_context()

    def worker():
        tracing.set_context(ctx)
        with tracing.span("rescore-feed", tid="rescore-feed"):
            time.sleep(0.002)

    t = threading.Thread(target=worker)
    with tracing.span("template loop"):
        t.start()
        with tracing.span("dispatch", start=0, stop=8):
            time.sleep(0.002)
        with tracing.span("drain"):
            time.sleep(0.002)
        tracing.instant("window-done", n=8)
        t.join()
    return tracing.finish(0)


def test_stream_validates_and_chrome_roundtrips(tmp_path):
    path = str(tmp_path / "run.trace.jsonl")
    summary = _run_traced(path)
    assert summary["open_spans"] == []

    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["kind"] == "start"
    assert lines[0]["schema"] == tracing.TRACE_SCHEMA
    assert lines[-1]["kind"] == "finish"
    assert tracing.validate_stream(lines) == []

    chrome_path = path + tracing.CHROME_SUFFIX
    doc = json.loads(open(chrome_path).read())  # round-trips json.loads
    assert tracing.validate_chrome(doc) == []
    evs = doc["traceEvents"]
    # trace-event schema: every event has ph + pid/tid, timed ones ts,
    # and every B is closed by an E with the same name on its lane
    assert all("ph" in e and "pid" in e and "tid" in e for e in evs)
    b = [e for e in evs if e["ph"] == "B"]
    e = [e for e in evs if e["ph"] == "E"]
    assert len(b) == len(e) == 4
    assert {ev["name"] for ev in b} == {
        "template loop", "dispatch", "drain", "rescore-feed",
    }
    lanes = {
        ev["args"]["name"]
        for ev in evs
        if ev["ph"] == "M" and ev["name"] == "thread_name"
    }
    assert "MainThread" in lanes and "rescore-feed" in lanes


def test_metrics_report_check_gates_trace_artifacts(tmp_path, capsys):
    """--check stays the one schema gate: pointed at the trace stream or
    the Chrome export it validates each against its own schema."""
    path = str(tmp_path / "run.trace.jsonl")
    _run_traced(path)
    assert metrics_report.main(["--check", path]) == 0
    assert f"OK ({tracing.TRACE_SCHEMA})" in capsys.readouterr().out
    assert (
        metrics_report.main(["--check", path + tracing.CHROME_SUFFIX]) == 0
    )
    assert "OK (chrome-trace)" in capsys.readouterr().out


def test_metrics_report_check_flags_truncated_stream(tmp_path, capsys):
    path = str(tmp_path / "run.trace.jsonl")
    _run_traced(path)
    lines = open(path).read().splitlines()
    with open(path, "w") as f:  # drop the finish terminator (a dead run)
        f.write("\n".join(lines[:-1]) + "\n")
    assert metrics_report.main(["--check", path]) == 1
    assert "no finish record" in capsys.readouterr().out


def test_validate_stream_flags_open_spans_and_backwards_time():
    good = [
        {"kind": "start", "schema": tracing.TRACE_SCHEMA, "epoch_unix": 1.0},
        {"kind": "span", "name": "a", "ts_us": 0, "dur_us": 5, "end_us": 5},
        {"kind": "finish", "open_spans": []},
    ]
    assert tracing.validate_stream(good) == []

    dirty = [dict(r) for r in good]
    dirty[-1]["open_spans"] = [{"name": "drain"}]
    assert any(
        "left open" in e for e in tracing.validate_stream(dirty)
    )

    backwards = [
        good[0],
        {"kind": "span", "name": "a", "ts_us": 0, "dur_us": 9, "end_us": 9},
        {"kind": "span", "name": "b", "ts_us": 0, "dur_us": 3, "end_us": 3},
        good[-1],
    ]
    assert any(
        "backwards" in e for e in tracing.validate_stream(backwards)
    )


def test_validate_chrome_flags_unbalanced_lanes():
    doc = {
        "traceEvents": [
            {"ph": "B", "pid": 1, "tid": 1, "ts": 0, "name": "dispatch"},
        ]
    }
    assert any("never closed" in e for e in tracing.validate_chrome(doc))


# ---------------------------------------------------------------------------
# flow arrows + merged multi-pid exports (the fleet-timeline surface)


def _flow(ph, ts, **kw):
    ev = {"ph": ph, "pid": 1, "tid": 1, "ts": ts, "name": "adopt",
          "cat": "adoption", "id": "adopt-1-e2"}
    ev.update(kw)
    return ev


def test_validate_chrome_accepts_flow_chain():
    """s -> t -> f with one id, across lanes, is a legal Chrome flow."""
    doc = {"traceEvents": [
        _flow("s", 10.0),
        _flow("t", 20.0, pid=2),
        _flow("f", 30.0, pid=3, bp="e"),
    ]}
    assert tracing.validate_chrome(doc) == []


def test_validate_chrome_flags_flow_violations():
    no_id = _flow("s", 1.0)
    del no_id["id"]
    assert any(
        "lacks an id" in e
        for e in tracing.validate_chrome({"traceEvents": [no_id]})
    )
    assert any(
        "no start" in e
        for e in tracing.validate_chrome({"traceEvents": [_flow("t", 1.0)]})
    )
    assert any(
        "never finished" in e
        for e in tracing.validate_chrome({"traceEvents": [_flow("s", 1.0)]})
    )
    after = [_flow("s", 1.0), _flow("f", 2.0), _flow("t", 3.0)]
    assert any(
        "after" in e
        for e in tracing.validate_chrome({"traceEvents": after})
    )
    twice = [_flow("s", 1.0), _flow("s", 2.0)]
    assert any(
        "started twice" in e
        for e in tracing.validate_chrome({"traceEvents": twice})
    )


def test_validate_chrome_accepts_multi_pid_export():
    """A merged fleet timeline keeps per-pid lane balance independent:
    host0's open B must not be closable by host1's E."""
    doc = {"traceEvents": [
        {"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
         "args": {"name": "host0"}},
        {"ph": "M", "pid": 2, "tid": 0, "name": "process_name",
         "args": {"name": "host1"}},
        {"ph": "B", "pid": 1, "tid": 1, "ts": 0, "name": "dispatch"},
        {"ph": "B", "pid": 2, "tid": 1, "ts": 1, "name": "dispatch"},
        {"ph": "E", "pid": 1, "tid": 1, "ts": 5, "name": "dispatch"},
        {"ph": "E", "pid": 2, "tid": 1, "ts": 6, "name": "dispatch"},
    ]}
    assert tracing.validate_chrome(doc) == []
    # host1's E alone must NOT balance host0's B
    lonely = {"traceEvents": [
        {"ph": "B", "pid": 1, "tid": 1, "ts": 0, "name": "dispatch"},
        {"ph": "E", "pid": 2, "tid": 1, "ts": 5, "name": "dispatch"},
    ]}
    errs = tracing.validate_chrome(lonely)
    assert any("no open B" in e for e in errs)
    assert any("never closed" in e for e in errs)


def test_stable_lane_identity_in_chrome_export(tmp_path):
    """ERP_TRACE_LANE names the process lane in the export — the stable
    identity merged fleet timelines key on instead of the OS pid."""
    path = str(tmp_path / "lane.trace.jsonl")
    env = dict(os.environ, PYTHONPATH=REPO)
    env[tracing.TRACE_FILE_ENV] = path
    env[tracing.LANE_ID_ENV] = "host7"
    code = (
        "from boinc_app_eah_brp_tpu.runtime import tracing\n"
        "tracing.configure()\n"
        "assert tracing.lane_id() == 'host7'\n"
        "with tracing.span('dispatch'):\n"
        "    pass\n"
        "tracing.finish(0)\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], env=env,
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["lane"] == "host7"
    doc = json.loads(open(path + tracing.CHROME_SUFFIX).read())
    assert doc["otherData"]["lane"] == "host7"
    proc = [
        ev for ev in doc["traceEvents"]
        if ev["ph"] == "M" and ev["name"] == "process_name"
    ]
    assert proc and proc[0]["args"]["name"] == "erp-search:host7"


def test_crash_leaves_stream_with_open_span(tmp_path):
    """A span open when the process dies must be visible: the atexit
    terminator records it in finish.open_spans, which --check flags."""
    path = str(tmp_path / "crash.trace.jsonl")
    env = dict(os.environ, PYTHONPATH=REPO)
    env[tracing.TRACE_FILE_ENV] = path
    code = (
        "from boinc_app_eah_brp_tpu.runtime import tracing\n"
        "tracing.configure()\n"
        "tracing.span('dispatch', start=0).__enter__()\n"
        # interpreter exits with the span open -> atexit terminator
    )
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True
    )
    assert r.returncode == 0, r.stderr
    lines = [json.loads(l) for l in open(path)]
    assert lines[-1]["kind"] == "finish"
    assert lines[-1]["exit_status"] == "abnormal-exit"
    assert [s["name"] for s in lines[-1]["open_spans"]] == ["dispatch"]
    errs = tracing.validate_stream(lines)
    assert any("left open" in e for e in errs)


# ---------------------------------------------------------------------------
# trace_report: stall attribution


def test_stall_table_exclusive_time_and_coverage(tmp_path):
    path = str(tmp_path / "run.trace.jsonl")
    _run_traced(path)
    table = trace_report.stall_table(trace_report.load_trace(path))
    cats = table["categories"]
    assert {"dispatch", "drain-stall", "template loop"} <= set(cats)
    assert cats["dispatch"]["self_s"] >= 0.0015  # slept ~2ms inside
    # exclusive time: the loop bracket must NOT absorb its children, so
    # summed self-times can't exceed the wall (double counting would)
    total_self = sum(r["self_s"] for r in cats.values())
    assert total_self <= table["wall_s"] * 1.05
    # the rescore-feed thread is a background lane, not wall attribution
    assert "rescore-feed" not in cats
    assert table["background_busy_s"]["rescore-feed"] > 0
    assert table["coverage"] > 0.5  # tiny run: spans dominate the window
    # both artifact forms reduce to the same categories
    chrome = trace_report.stall_table(
        trace_report.load_trace(path + tracing.CHROME_SUFFIX)
    )
    assert set(chrome["categories"]) == set(cats)


def test_trace_report_diff_flags_injected_backoff(tmp_path):
    """The acceptance scenario: two runs, the second with a
    retry-backoff wall — --diff must exit nonzero on it."""
    a = str(tmp_path / "a.trace.jsonl")
    b = str(tmp_path / "b.trace.jsonl")
    _run_traced(a)
    assert tracing.configure(trace_file=b)
    with tracing.span("template loop"):
        with tracing.span("dispatch", start=0, stop=8):
            time.sleep(0.002)
        with tracing.span("retry-backoff", site="dispatch", attempt=0):
            time.sleep(0.03)
        with tracing.span("drain"):
            time.sleep(0.002)
    tracing.finish(0)
    assert trace_report.main(["--diff", a, b, "--min-delta-s", "0.02"]) == 1
    # the reverse direction is an improvement, not a regression
    assert trace_report.main(["--diff", b, a, "--min-delta-s", "0.02"]) == 0


def test_trace_report_windows_and_json(tmp_path, capsys):
    path = str(tmp_path / "run.trace.jsonl")
    _run_traced(path)
    assert trace_report.main(["--json", "--windows", "3", path]) == 0
    out = capsys.readouterr().out.splitlines()
    table = json.loads(out[0])
    assert table["main_lane"] == "MainThread"
    assert any("ctx" in l for l in out[1:])


# ---------------------------------------------------------------------------
# cost_ledger: the chip-free traffic trajectory


def _aot_file(dirpath, n, bytes_per_template, stage_bytes=0):
    doc = {
        "batch": 2,
        "compiler": {
            "bytes_accessed_per_template": bytes_per_template,
            "flops_per_template": 1e9,
        },
        "roofline_model": {"ideal_bytes_per_template": 9.437e8},
        "bytes_vs_model": bytes_per_template / 9.437e8,
        "layout_hotspots": [
            {
                "op": "copy",
                "source": "jit(step)/vmap(jit(harmonic_sumspec))/reshape",
                "count": 3,
                "out_bytes": stage_bytes,
            }
        ],
    }
    path = os.path.join(dirpath, f"AOT_COST_r{n:02d}.json")
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def test_cost_ledger_reduces_committed_artifact():
    ledger = cost_ledger.build_ledger(REPO)
    assert ledger["rows"], "repo must carry at least one AOT_COST artifact"
    row = ledger["rows"][0]
    assert row["gb_per_template"] > row["ideal_gb_per_template"] > 0
    assert "harmonic-sum" in row["layout_gb_per_template"]
    assert "fft+power" in row["layout_gb_per_template"]


def test_cost_ledger_strict_flags_traffic_growth(tmp_path, capsys):
    _aot_file(tmp_path, 1, 5.0e9, stage_bytes=1_000_000_000)
    _aot_file(tmp_path, 2, 5.1e9, stage_bytes=1_000_000_000)  # +2%: fine
    assert cost_ledger.main(["--root", str(tmp_path), "--strict"]) == 0
    assert os.path.exists(tmp_path / cost_ledger.LEDGER_PATH)
    capsys.readouterr()
    _aot_file(tmp_path, 3, 7.0e9, stage_bytes=3_000_000_000)  # +37%
    assert cost_ledger.main(["--root", str(tmp_path), "--strict"]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "gb_per_template" in out
    assert "harmonic-sum" in out  # the stage growth is named too
    doc = json.load(open(tmp_path / cost_ledger.LEDGER_PATH))
    assert doc["schema"] == cost_ledger.SCHEMA
    assert [r["round"] for r in doc["rows"]] == [1, 2, 3]
