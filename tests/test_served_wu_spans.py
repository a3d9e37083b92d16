"""The served WU's program spans in the profiler trace: two tiny whitened
WUs through the FleetServer with prep overlap, under ``jax.profiler``,
read back the way the benchmark reads a traced run
(``benchmark/trace.py``)."""

import pytest

from boinc_app_eah_brp_tpu.io import write_template_bank, write_workunit
from boinc_app_eah_brp_tpu.runtime import tracing
from boinc_app_eah_brp_tpu.runtime.driver import DriverArgs
from boinc_app_eah_brp_tpu.serving import FleetServer
from fixtures import small_bank, synthetic_timeseries

SERVED = (
    "erp:wu", "erp:prep-wait", "erp:prepare", "erp:parse", "erp:whiten",
    "erp:whiten-h2d", "erp:whiten-d2h", "erp:template-loop", "erp:dispatch",
    "erp:drain", "erp:progress", "erp:checkpoint", "erp:finalize",
    "erp:rescore", "erp:rescore-template", "erp:result-write",
)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The xplane's host lines (per thread) and the JSONL layer's state
    after two WUs were served under the profiler."""
    import jax

    from benchmark import trace as bench_trace

    tmp = tmp_path_factory.mktemp("served")
    bank = str(tmp / "bank.dat")
    write_template_bank(
        bank, small_bank(P_true=2.2, tau_true=0.04, psi_true=1.2)
    )
    zap = tmp / "zap.txt"
    zap.write_text("20.0 20.3\n")

    def args(i):
        ts = synthetic_timeseries(
            4096, f_signal=31.0 + 2.0 * i, P_orb=2.2, tau=0.04, psi0=1.2,
            amp=7.0, seed=i,
        )
        wu = str(tmp / f"wu{i}.bin4")
        write_workunit(wu, ts, tsample_us=500.0, scale=1.0, dm=55.5)
        return DriverArgs(
            inputfile=wu, outputfile=str(tmp / f"wu{i}.cand"),
            templatebank=bank, checkpointfile=str(tmp / f"wu{i}.cpt"),
            zaplistfile=str(zap), white=True, window=200, batch_size=2,
        )

    logdir = str(tmp / "prof")
    assert tracing.configure(force=True)
    try:
        with FleetServer(name="spans", prep_overlap=True) as server:
            jax.profiler.start_trace(logdir)
            try:
                tickets = [server.submit(args(i)) for i in range(2)]
                results = [server.result(t) for t in tickets]
            finally:
                jax.profiler.stop_trace()
        left_open = tracing.open_spans()
        events = tracing.events()
    finally:
        tracing.finish(0)
    assert all(r.ok for r in results), [r.error for r in results]
    planes = bench_trace.load_planes(logdir)
    lines = [
        [ev for ev in line["events"] if ev[0].startswith("erp:")]
        for plane in planes if not bench_trace.is_device_plane(plane["name"])
        for line in plane["lines"]
    ]
    return {"lines": [ln for ln in lines if ln], "open": left_open,
            "events": events,
            "spans": bench_trace.host_spans(planes)}


def test_served_wu_spans_are_in_the_profiler_trace(served):
    names = {s[0] for s in served["spans"]}
    missing = [n for n in SERVED if n not in names]
    assert not missing, sorted(names)


def test_prepare_begins_and_ends_on_the_thread_that_parses(served):
    """Each ``erp:prepare`` event lies on one thread's line with the
    ``erp:parse`` and ``erp:whiten`` spans it holds: it was entered and
    exited on the prep thread."""
    n = 0
    for line in served["lines"]:
        for name, start, dur in line:
            if name != "erp:prepare":
                continue
            n += 1
            inside = {c for c, s, d in line
                      if start <= s and s + d <= start + dur}
            assert {"erp:parse", "erp:whiten"} <= inside
    assert n == 2


def test_no_span_left_open_and_prepare_recorded_on_its_thread(served):
    assert served["open"] == []
    prep = [e for e in served["events"] if e["name"] == "prepare"]
    assert len(prep) == 2
    assert all(e["tid"].startswith("erp-fleet-prep") for e in prep)
    assert not any(e["name"] == "setup" for e in served["events"])
    rescore = [e for e in served["events"] if e["name"] == "rescore"]
    assert rescore and all(
        {"n_winners", "n_eval"} <= set(e["args"]) for e in rescore
    )
