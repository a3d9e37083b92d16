"""The four-chip cell ``palfa_p3_mesh4.bank`` at a tiny size on four virtual
CPU devices, with the resident chain forced (interpret mode): the mesh
agrees with the plain reference and fails it without the exchange between
chips, every template goes through the resident chain, the traced run
reads what a CPU trace holds, and the collective's reader."""

import json
import os

import pytest

import bench_tiny
from benchmark import harness, run
from benchmark.kinds.bank import Run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """bench_tiny's root, with ``tiny_mesh4.bank`` in the metric lists
    that name ``palfa_p3_mesh4.bank`` and ``tiny_mesh4``'s orbits at 100-300
    s and up to 0.03 lt-s: periods the resident chain's LUT window admits
    (it refuses bench_tiny's 1.5-3 s), and delays of up to 60 samples, deep
    enough that the templates' sums differ (a mesh without its exchange
    shows) and shallow enough that at 8,192 samples no nearest-index tie
    rounds one way in the chain and the other in the reference (one such
    flip moves the padding mean by a sample's 1/8,192)."""
    root = bench_tiny.tiny_root(tmp_path_factory.mktemp("tiny"))
    path = os.path.join(root, "benchmark/configs/tiny_mesh4.json")
    cfg = json.load(open(path))
    cfg["bank"].update(P_range=[100.0, 300.0], tau_max=0.03)
    json.dump(cfg, open(path, "w"))
    lists = {m["name"]: m.get("workloads") or []
             for m in harness.spec()["end_to_end"] + harness.spec()["per_layer"]}
    spec_path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(spec_path))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "palfa_p3_mesh4.bank" in lists[m["name"]]:
            m["workloads"].append("tiny_mesh4.bank")
    json.dump(spec, open(spec_path, "w"))
    return root


@pytest.fixture
def resident(monkeypatch):
    from boinc_app_eah_brp_tpu.runtime import metrics

    monkeypatch.setenv("ERP_PALLAS_RESIDENT", "1")
    assert metrics.configure(force=True)
    yield metrics
    metrics.finish(0)


def _counter(metrics, name):
    return (metrics.snapshot()["counters"].get(name) or {}).get("value", 0)


def test_mesh_config_is_palfa_p3_on_four_chips():
    palfa = harness.load_config("palfa_p3")
    mesh = harness.load_config("palfa_p3_mesh4")
    for key in ("geometry", "expect", "bank", "signal", "zaplist", "reduced"):
        assert mesh[key] == palfa[key], key
    assert mesh["chips"] == 4 and mesh["mesh"] == {"devices": 4}
    assert mesh["assumed"][:3] == palfa["assumed"]
    cell = harness.cell("palfa_p3_mesh4.bank")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "palfa_p3_mesh4", "bank", 4)
    e2e = [m["name"] for m in harness.end_to_end_for(cell["name"])]
    assert e2e == ["templates_per_s", "setup_s"]
    assert set(harness.per_layer_for(cell["name"], e2e)) == {
        "resample.ms_per_t", "fft.ms_per_t", "harmonic.ms_per_t",
        "step_roofline", "step.other_ms_per_t", "step.drain_gap_ms",
        "device_idle.loop", "allreduce.ms_per_step"}


@pytest.mark.parametrize("exchange", [True, False], ids=["merged", "no-exchange"])
def test_resident_mesh_cell_against_the_reference(root, resident, monkeypatch,
                                                  exchange):
    from boinc_app_eah_brp_tpu.parallel import sharded_search

    if not exchange:
        monkeypatch.setattr(sharded_search, "_allreduce_merge",
                            lambda axis, n, M, T: (M, T))
    res, checks = run.execute(bench_tiny.args("tiny_mesh4.bank", seed=4000000025),
                              root, require_chip=False)
    assert res["correct"] is exchange, checks
    assert res["extra"]["info"]["mesh"] == 4
    n = _counter(resident, "search.templates")
    assert n > 0 and _counter(resident, "search.templates_resident") == n
    assert _counter(resident, "resilience.pallas_fallback") == 0


def test_resident_mesh_cell_traced(root, resident, monkeypatch):
    """The traced run lowers the mesh step for the trace's scopes and
    reduces a CPU trace: no device plane, so the stage groups read 0 and
    the readers that need a chip (the collective, the drain gap, the idle
    share, the roofline) read nothing."""
    monkeypatch.setenv("ERP_BATCH", "1")  # 10 steps of 4: past the boundary
    res, checks = run.execute(bench_tiny.args("tiny_mesh4.bank", trace=1), root,
                              require_chip=False)
    assert res["correct"], checks
    assert res["metrics"] == {
        name: {"value": 0.0, "unit": "ms/template"}
        for name in ("resample.ms_per_t", "fft.ms_per_t", "harmonic.ms_per_t",
                     "step.other_ms_per_t")}
    info = res["extra"]["info"]
    assert info["per_device_batch"] == 1 and info["trace_tries"] == 0
    assert info["trace_scope_s"] == {}


def _traced(**trace):
    red = {"chips": 4, "dropped": False, "window_s": 1.0, "busy_s": 1.0,
           "scope_s": {"allreduce": 0.0048, "resample": 0.4}}
    red.update(trace)
    return Run(trace=red, templates_traced=128)


@pytest.mark.parametrize("rec,want", [
    (_traced(), 1.2),
    (_traced(chips=1, scope_s={"allreduce": 0.0007}), 0.7),
    (_traced(dropped=True), None),
    (_traced(chips=0, scope_s={}), None),
    (_traced(scope_s={"resample": 0.4}), None),
    (Run(), None),
    (dict(kind="wu"), None),
], ids=["four-chips", "one-chip", "dropped", "no-chip", "no-scope", "untraced",
        "wu"])
def test_allreduce_reader(rec, want):
    if isinstance(rec, dict):
        rec = Run(trace=_traced().trace, **rec)
    got = harness.load_reader("allreduce.ms_per_step").read(rec)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
