"""Device-cost observatory (runtime/devicecost.py, tools/hlo_attrib.py):
stage-registry semantics, named scopes surviving into COMPILED HLO
op_name metadata, zero recompiles and zero numeric effect from scoping,
synthetic-module byte attribution, the estimated device timeline ->
Chrome-export merge -> trace_report device section, the artifact
validators behind ``metrics_report --check``, and cost_ledger's
attribution-artifact consumption."""

import json
import os
import sys

import numpy as np
import pytest

from boinc_app_eah_brp_tpu.runtime import devicecost, metrics, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import cost_ledger  # noqa: E402
import metrics_report  # noqa: E402
import trace_report  # noqa: E402

# hlo_attrib calls use_cpu_backend() at import, which exports
# ERP_FORCE_CASCADE=1 for the AOT tools' sake; restore the test
# process's env so the whiten/fft native-path tests keep their meaning
_cascade = os.environ.get("ERP_FORCE_CASCADE")
import hlo_attrib  # noqa: E402

if _cascade is None:
    os.environ.pop("ERP_FORCE_CASCADE", None)
else:
    os.environ["ERP_FORCE_CASCADE"] = _cascade


# --- registry semantics -----------------------------------------------------


def test_scope_name_and_registry():
    assert devicecost.scope_name("resample") == "erp.resample"
    assert devicecost.scope_name("bank-slice") == "erp.bank-slice"
    with pytest.raises(KeyError):
        devicecost.scope_name("no-such-stage")
    # the decorator and context forms validate BEFORE importing jax
    with pytest.raises(KeyError):
        devicecost.stage_scope("typo")
    with pytest.raises(KeyError):
        devicecost.scoped("typo")


def test_stage_of_op_name_innermost_wins():
    f = devicecost.stage_of_op_name
    assert f(None) is None
    assert f("") is None
    assert f("jit(step)/mul") is None
    assert f("jit(step)/erp.power/mul") == "power"
    # nested scopes: the innermost (last) registered scope owns the op
    assert f("jit(step)/erp.power/x/erp.fft/mul") == "fft"
    # unregistered erp.* names are ignored, outer registered one holds
    assert f("erp.fft/erp.bogus/mul") == "fft"
    assert f("erp.bogus/mul") is None


def test_ledger_stage_collapse():
    assert devicecost.ledger_stage("fft") == "fft+power"
    assert devicecost.ledger_stage("power") == "fft+power"
    assert devicecost.ledger_stage("median") == "whiten"
    assert devicecost.ledger_stage("allreduce") == "merge"
    # unknown names pass through (stale artifacts keep rendering)
    assert devicecost.ledger_stage("mystery") == "mystery"


# --- scopes in compiled HLO -------------------------------------------------


def test_scopes_survive_into_compiled_hlo():
    """The acceptance property: scope names must appear in the OPTIMIZED
    module's op_name metadata (lowered StableHLO drops them without
    debug info, so this asserts on the compiled text)."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with devicecost.stage_scope("fft"):
            y = jnp.fft.rfft(x)
        with devicecost.stage_scope("power"):
            return jnp.abs(y) ** 2

    txt = (
        jax.jit(f)
        .lower(jnp.ones(256, jnp.float32))
        .compile()
        .as_text()
    )
    assert "erp.fft" in txt
    assert "erp.power" in txt


def test_instrumented_op_carries_scope():
    """A real instrumented pipeline stage (ops/harmonic.py) tags its
    compiled instructions."""
    import jax
    import jax.numpy as jnp

    from boinc_app_eah_brp_tpu.ops.harmonic import harmonic_sumspec

    ps = jnp.ones(64, jnp.float32)
    txt = (
        jax.jit(
            lambda p: harmonic_sumspec(
                p, window_2=32, fund_hi=16, harm_hi=64
            )
        )
        .lower(ps)
        .compile()
        .as_text()
    )
    assert "erp.harmonic" in txt


def test_scope_has_no_numeric_effect():
    from boinc_app_eah_brp_tpu.ops.harmonic import (
        _harmonic_sumspec_impl,
        harmonic_sumspec,
    )

    rng = np.random.default_rng(7)
    ps = np.asarray(rng.random(64), np.float32)
    scoped = harmonic_sumspec(ps, window_2=32, fund_hi=16, harm_hi=64)
    plain = _harmonic_sumspec_impl(
        ps, window_2=32, fund_hi=16, harm_hi=64, natural=True
    )
    np.testing.assert_array_equal(
        np.asarray(scoped), np.asarray(plain)
    )


def test_scopes_cause_no_recompile():
    """Entering/exiting a named scope must not change jit cache keys
    (watched through the jax.monitoring recompile counter)."""
    import jax
    import jax.numpy as jnp

    assert metrics.configure(force=True)
    try:

        @jax.jit
        def f(x):
            with devicecost.stage_scope("merge"):
                return x * 2.0

        x = jnp.ones(16, jnp.float32)
        f(x).block_until_ready()

        def recompiles():
            snap = metrics.snapshot()
            row = snap["counters"].get("jax.recompiles") or {}
            return row.get("value", 0)

        before = recompiles()
        for _ in range(3):
            f(x).block_until_ready()
        assert recompiles() == before
    finally:
        metrics.finish(0)


def test_oracle_path_untouched():
    """The CPU oracle is the numerics ground truth: it must stay free of
    device-cost instrumentation (scopes are a device-metadata concern)."""
    oracle_dir = os.path.join(REPO, "boinc_app_eah_brp_tpu", "oracle")
    for name in os.listdir(oracle_dir):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(oracle_dir, name)) as f:
            src = f.read()
        assert "devicecost" not in src, f"oracle/{name} imports devicecost"
        assert "named_scope" not in src, f"oracle/{name} uses named_scope"


# --- synthetic-module attribution (tools/hlo_attrib.py) ---------------------


_SYNTH_HLO = """\
HloModule synth

fused_computation {
  p0 = f32[1024,256]{1,0} parameter(0)
  t = f32[256,1024]{0,1} transpose(p0), dimensions={1,0}, metadata={op_name="jit(step)/erp.resample/transpose"}
  ROOT m = f32[256,1024]{1,0} multiply(t, t), metadata={op_name="jit(step)/erp.resample/mul"}
}

ENTRY main {
  p = f32[1024,256]{1,0} parameter(0)
  f = f32[256,1024]{1,0} fusion(p), kind=kLoop, calls=fused_computation, metadata={op_name="jit(step)/erp.resample/mul"}
  h = f32[64]{0} add(p, p), metadata={op_name="jit(step)/erp.harmonic/add"}
  c = f32[1024,256]{1,0} copy(p), metadata={op_name="jit(step)/transpose"}
  ROOT r = f32[512]{0} add(c, c)
}
"""

_MB = 256 * 1024 * 4  # bytes of one f32[1024,256]


def test_walk_module_skips_plumbing_and_counts_bodies():
    rows = list(hlo_attrib.walk_module(_SYNTH_HLO))
    opcodes = [r[0] for r in rows]
    # parameters, the fusion caller line: skipped; body instructions kept
    assert "parameter" not in opcodes
    assert "fusion" not in opcodes
    assert opcodes.count("transpose") == 1
    assert opcodes.count("copy") == 1


def test_attribute_module_buckets_by_scope():
    doc = hlo_attrib.attribute_module(_SYNTH_HLO, batch=2)
    stages = doc["stages"]
    assert set(stages) == {"resample", "harmonic"}
    # transpose + multiply from the fusion body
    assert stages["resample"]["out_bytes"] == 2 * _MB
    assert stages["resample"]["layout_bytes"] == _MB  # the transpose
    assert stages["harmonic"]["out_bytes"] == 64 * 4
    # copy (op_name without a scope) + root add (no metadata) unattributed
    assert doc["unattributed_bytes"] == _MB + 512 * 4
    assert doc["total_bytes"] == (
        doc["attributed_bytes"] + doc["unattributed_bytes"]
    )
    un_ops = {row["op"] for row in doc["unattributed_top"]}
    assert un_ops == {"copy", "add"}
    # stage rows are rendered in registry (pipeline) order
    assert list(stages) == ["resample", "harmonic"]


def test_attribute_module_artifact_validates_and_collapses():
    doc = {
        "schema": devicecost.ATTRIB_SCHEMA,
        "batch": 2,
        "platform": "cpu",
        **hlo_attrib.attribute_module(_SYNTH_HLO, batch=2),
    }
    assert devicecost.validate_hlo_attrib(doc) == []
    led = hlo_attrib.ledger_stages(doc)
    assert set(led) == {"resample", "harmonic-sum", "compiler-generated"}
    assert led["resample"] == round(2 * _MB / 2 / 1e9, 4)


def test_diff_artifacts_flags_coverage_and_stage_growth():
    base = {
        "attributed_fraction": 0.9,
        "stages": {"resample": {"gb_per_template": 1.0}},
    }
    worse = {
        "attributed_fraction": 0.8,  # fell > 0.02
        "stages": {"resample": {"gb_per_template": 1.5}},  # +50%
    }
    problems = hlo_attrib.diff_artifacts(base, worse, threshold_pct=10.0)
    assert any("attributed_fraction" in p for p in problems)
    assert any("stage resample" in p for p in problems)
    assert hlo_attrib.diff_artifacts(base, base, threshold_pct=10.0) == []


# --- validators / metrics_report --check ------------------------------------


def test_validate_hlo_attrib_catches_breakage():
    assert devicecost.validate_hlo_attrib("nope") == ["not a JSON object"]
    doc = {
        "schema": devicecost.ATTRIB_SCHEMA,
        "batch": 4,
        "total_bytes": 10,
        "attributed_bytes": 8,
        "attributed_fraction": 0.8,
        "stages": {"fft": {"out_bytes": 8}},
        "unattributed_top": [],
    }
    assert devicecost.validate_hlo_attrib(doc) == []
    bad = dict(doc, attributed_fraction=1.7)
    assert any("outside [0, 1]" in e for e in devicecost.validate_hlo_attrib(bad))
    bad = dict(doc, stages={"fft": {}})
    assert any("out_bytes" in e for e in devicecost.validate_hlo_attrib(bad))


def test_validate_cost_ledger():
    doc = {
        "schema": "erp-cost-ledger/1",
        "rows": [
            {
                "file": "AOT_COST_r05.json",
                "gb_per_template": 7.9,
                "ideal_gb_per_template": 0.9,
                "layout_gb_per_template": {"resample": 0.1},
            }
        ],
    }
    assert devicecost.validate_cost_ledger(doc) == []
    bad = {"schema": "erp-cost-ledger/1", "rows": [{"file": "x"}]}
    errs = devicecost.validate_cost_ledger(bad)
    assert any("gb_per_template" in e for e in errs)


def test_metrics_report_check_dispatches_new_schemas(tmp_path, capsys):
    attrib = tmp_path / "HLO_ATTRIB_r06.json"
    attrib.write_text(
        json.dumps(
            {
                "schema": devicecost.ATTRIB_SCHEMA,
                "batch": 4,
                "total_bytes": 10,
                "attributed_bytes": 8,
                "attributed_fraction": 0.8,
                "stages": {"fft": {"out_bytes": 8}},
                "unattributed_top": [],
            }
        )
    )
    ledger = tmp_path / "COST_LEDGER.json"
    ledger.write_text(
        json.dumps({"schema": "erp-cost-ledger/1", "rows": []})
    )
    rc = metrics_report.main(["--check", str(attrib), str(ledger)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "OK (erp-hlo-attrib/1)" in out
    assert "OK (erp-cost-ledger/1)" in out
    # a malformed artifact of either schema fails the gate
    attrib.write_text(json.dumps({"schema": devicecost.ATTRIB_SCHEMA}))
    assert metrics_report.main(["--check", str(attrib)]) == 1


# --- estimated device timeline ----------------------------------------------


def _span(name, ctx, ts, end, tid="MainThread"):
    return {
        "kind": "span", "name": name, "tid": tid, "ctx": ctx,
        "ts_us": ts, "end_us": end, "dur_us": end - ts, "depth": 0,
    }


def test_dispatch_windows_from_span_sequence():
    spans = [
        _span("dispatch", 1, 0.0, 10.0),
        _span("dispatch", 2, 200.0, 210.0),  # lookahead: closes window 1
        _span("drain", 2, 300.0, 350.0),  # drain end closes window 2
        _span("checkpoint", 2, 360.0, 400.0),  # ignored
    ]
    assert devicecost.dispatch_windows(spans) == [
        (1, 0.0, 200.0),
        (2, 200.0, 350.0),
    ]
    assert devicecost.dispatch_windows([]) == []


def test_estimate_device_records_partition_and_tagging():
    model = [
        {"stage": "a", "scope": "resample", "fraction": 0.25, "bound": "hbm"},
        {"stage": "b", "scope": "fft", "fraction": 0.75, "bound": "mxu"},
        {"stage": "c", "scope": "merge", "fraction": 0.0, "bound": "hbm"},
    ]
    recs = devicecost.estimate_device_records([(7, 1000.0, 2000.0)], model)
    # the zero-fraction stage emits nothing; the rest partition the window
    assert [r["name"] for r in recs] == ["erp.resample", "erp.fft"]
    assert recs[0]["ts_us"] == 1000.0 and recs[0]["dur_us"] == 250.0
    assert recs[1]["ts_us"] == 1250.0 and recs[1]["end_us"] == 2000.0
    assert all(r["tid"] == "device:estimated" for r in recs)
    assert all(r["args"]["estimated"] is True for r in recs)
    assert all(r["ctx"] == 7 for r in recs)


def test_device_records_merge_into_chrome_only(tmp_path):
    """Tentpole c end-to-end without jax: host spans stream to JSONL,
    device records land ONLY in the Chrome export, and trace_report
    splits drain wall into device-bound vs host-stall."""
    stream = str(tmp_path / "run.trace.jsonl")
    assert tracing.configure(trace_file=stream)
    try:
        with tracing.span("dispatch", tid="MainThread", ctx=1):
            pass
        with tracing.span("drain", tid="MainThread", ctx=1):
            pass
        host = tracing.events()
        drain = next(r for r in host if r["name"] == "drain")
        dur = max(10.0, drain["end_us"] - drain["ts_us"])
        accepted = tracing.add_device_records(
            [
                {
                    "name": "erp.fft", "tid": "device:estimated", "ctx": 1,
                    "ts_us": drain["ts_us"], "dur_us": dur,
                    "end_us": drain["ts_us"] + dur,
                    "args": {"estimated": True, "bound": "mxu"},
                },
                {"name": 42},  # malformed: dropped, not crashed
            ]
        )
        assert accepted == 1
        summary = tracing.finish(0)
    finally:
        if tracing.enabled():
            tracing.finish(0)
    assert summary["device_records"] == 1

    # the JSONL stream stays host-only and strictly ordered
    lines = [json.loads(x) for x in open(stream)]
    assert tracing.validate_stream(lines) == []
    assert not any(
        str(r.get("tid", "")).startswith("device:") for r in lines
    )

    chrome = json.load(open(stream + ".chrome.json"))
    assert tracing.validate_chrome(chrome) == []
    assert chrome["otherData"]["device_records"] == 1

    table = trace_report.stall_table(trace_report.load_trace(stream + ".chrome.json"))
    # device lanes never leak into host attribution
    assert table["main_lane"] == "MainThread"
    assert not any(
        trace_report.is_device_lane(t) for t in table["background_busy_s"]
    )
    dev = table["device"]
    assert dev["estimated"] is True
    assert "device:estimated" in dev["lane_busy_s"]
    assert dev["stages"]["fft"]["count"] == 1
    # the synthetic device span covers the whole drain: all device-bound
    assert dev["drain_host_stall_s"] == pytest.approx(0.0, abs=1e-4)
    assert dev["drain_device_bound_s"] == pytest.approx(
        dev["drain_s"], rel=0.05
    )
    rendered = trace_report.render(table, "t")
    assert "Device lanes (estimated):" in rendered
    assert "drain split:" in rendered


def test_stall_table_without_device_lanes_has_no_device_key():
    trace = {
        "spans": [_span("dispatch", 1, 0.0, 10.0)],
        "wall_us": 10.0,
        "open_spans": [],
    }
    assert "device" not in trace_report.stall_table(trace)


# --- measured device records (xplane parse, runtime/steptime.py feed) -------


def _plane_fixture():
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "golden", "xplane_planes_v5e.json",
    )
    with open(path) as f:
        return json.load(f)


def test_parse_plane_dicts_selects_device_planes_and_rebases():
    recs = devicecost.parse_plane_dicts(_plane_fixture())
    # the host plane is skipped, lanes come from the xplane line names,
    # and the event without a start timestamp is dropped
    assert len(recs) == 5
    assert all(r["tid"].startswith("device:") for r in recs)
    assert "device:TensorCore 0" in {r["tid"] for r in recs}
    # the lineless lane falls back to the plane name
    assert recs[-1]["tid"] == "device:/device:TPU:0"
    # timestamps rebase so the earliest device event sits at 0
    assert min(r["ts_us"] for r in recs) == 0.0
    first = recs[0]
    assert first["name"] == "jit(step)/erp.resample/gather"
    assert first["ts_us"] == 0.0
    assert first["dur_us"] == 400.0 and first["end_us"] == 400.0
    assert first["args"] == {"measured": True}


def test_parse_plane_dicts_empty_and_host_only():
    assert devicecost.parse_plane_dicts([]) == []
    host_only = [p for p in _plane_fixture() if "host" in p["name"]]
    assert host_only  # the fixture does carry a host plane to skip
    assert devicecost.parse_plane_dicts(host_only) == []


def test_stage_records_attribution():
    recs = devicecost.parse_plane_dicts(_plane_fixture())
    staged = devicecost.stage_records(recs)
    # the compiler-named fusion has no erp.* scope: dropped, the four
    # scoped kernels fold onto the measured lane under their stage name
    assert [r["args"]["stage"] for r in staged] == [
        "resample", "fft", "power", "harmonic",
    ]
    assert all(r["tid"] == "device:measured" for r in staged)
    assert [r["name"] for r in staged] == [
        "erp.resample", "erp.fft", "erp.power", "erp.harmonic",
    ]
    assert staged[0]["args"]["op"] == "jit(step)/erp.resample/gather"
    assert all(r["args"]["measured"] is True for r in staged)
    # timing carries through untouched
    assert staged[0]["dur_us"] == 400.0


def test_tpu_op_events_attribute_through_the_executable_text():
    """A TPU names each device event after the HLO instruction it ran,
    with no op metadata (as a v5e showed, PR 21): the compiled module's
    text maps the instruction to its erp.* scope."""
    module = "\n".join([
        "%fused_computation.8 (p: f32[8]) -> f32[8] {",
        '  ROOT %sine.1 = f32[8]{0} sine(f32[8]{0} %p), '
        'metadata={op_name="jit(step)/erp.power/sin"}',
        "}",
        '  %fusion.42 = f32[8]{0:T(256)} fusion(f32[8]{0} %a), kind=kLoop, '
        'calls=%fused_computation.8, metadata={op_name="jit(step)/erp.fft/dot" '
        'source_file="ops/fft.py"}',
        '  %copy.3 = f32[8]{0} copy(f32[8]{0} %b), '
        'metadata={op_name="jit(step)/transpose"}',
    ])
    scopes = devicecost.hlo_op_scopes(module)
    assert scopes == {"sine.1": "jit(step)/erp.power/sin",
                      "fusion.42": "jit(step)/erp.fft/dot"}
    recs = [
        {"name": n, "ts_us": 0.0, "dur_us": 1.0, "end_us": 1.0}
        for n in ("%fusion.42 = f32[8]{0:T(256)} fusion(f32[8]{0} %a), "
                  "kind=kLoop, calls=%fused_computation.8",
                  "%copy.3 = f32[8]{0} copy(f32[8]{0} %b)", "sine.1")
    ]
    staged = devicecost.stage_records(recs, op_scopes=scopes)
    assert [r["args"]["stage"] for r in staged] == ["fft", "power"]
    assert devicecost.stage_records(recs) == []


def test_hlo_op_scopes_of_a_compiled_scoped_function():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with devicecost.stage_scope("fft"):
            y = jnp.sin(x) * 2.0
        with devicecost.stage_scope("merge"):
            return jnp.maximum(y, x)

    text = f.lower(jnp.ones(64)).compile().as_text()
    stages = {devicecost.stage_of_op_name(v)
              for v in devicecost.hlo_op_scopes(text).values()}
    assert stages == {"fft", "merge"}


def test_collect_profiler_device_records_typed_empty_on_failure(tmp_path):
    """Every failure mode returns a typed empty result with the warning
    saying what was skipped — never a silent []."""
    r = devicecost.collect_profiler_device_records(str(tmp_path))
    assert isinstance(r, devicecost.ProfilerRecords)
    assert not r and len(r) == 0 and list(r) == []
    assert r.warning  # ProfileData unavailable, or no *.xplane.pb
    # a corrupt proto is equally diagnosable
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "run.xplane.pb").write_bytes(b"\x00not-a-proto")
    r2 = devicecost.collect_profiler_device_records(str(bad))
    assert isinstance(r2, devicecost.ProfilerRecords)
    assert r2.warning and not r2.records


def test_profiler_records_is_list_like():
    rec = {"name": "x", "tid": "device:d", "ts_us": 0.0, "dur_us": 1.0,
           "end_us": 1.0, "args": {"measured": True}}
    full = devicecost.ProfilerRecords(records=[rec], path="p")
    assert bool(full) and len(full) == 1 and list(full) == [rec]
    assert full.warning is None


# --- cost_ledger attribution-artifact consumption ---------------------------


def _aot_cost(path, gb=5.0, hotspots=()):
    doc = {
        "batch": 2,
        "compiler": {
            "bytes_accessed_per_template": gb * 1e9,
            "flops_per_template": 1e9,
        },
        "roofline_model": {"ideal_bytes_per_template": 1e9},
        "bytes_vs_model": gb,
        "layout_hotspots": list(hotspots),
    }
    path.write_text(json.dumps(doc))


def _attrib(path, batch=2, stages=None):
    stages = stages or {"resample": 2.0e9, "fft": 1.0e9}
    doc = {
        "schema": devicecost.ATTRIB_SCHEMA,
        "batch": batch,
        "total_bytes": sum(stages.values()) + 1.0e9,
        "attributed_bytes": sum(stages.values()),
        "attributed_fraction": 0.75,
        "stages": {
            k: {"out_bytes": v, "ledger_stage": devicecost.ledger_stage(k)}
            for k, v in stages.items()
        },
        "unattributed_bytes": 1.0e9,
        "unattributed_top": [],
    }
    doc["ledger_stages"] = {
        **{
            devicecost.ledger_stage(k): round(v / batch / 1e9, 4)
            for k, v in stages.items()
        },
        "compiler-generated": round(1.0e9 / batch / 1e9, 4),
    }
    path.write_text(json.dumps(doc))


def test_cost_ledger_prefers_attrib_sibling(tmp_path):
    _aot_cost(
        tmp_path / "AOT_COST_r06.json",
        hotspots=[{"out_bytes": 4e8, "source": "resample_split"}],
    )
    _attrib(tmp_path / "HLO_ATTRIB_r06.json")
    ledger = cost_ledger.build_ledger(str(tmp_path))
    (row,) = ledger["rows"]
    assert row["stage_source"] == "hlo-attrib"
    assert row["layout_gb_per_template"]["resample"] == 1.0
    assert row["layout_gb_per_template"]["compiler-generated"] == 0.5
    assert devicecost.validate_cost_ledger(ledger) == []


def test_cost_ledger_falls_back_to_markers(tmp_path):
    _aot_cost(
        tmp_path / "AOT_COST_r06.json",
        hotspots=[{"out_bytes": 4e8, "source": "resample_split"}],
    )
    ledger = cost_ledger.build_ledger(str(tmp_path))
    (row,) = ledger["rows"]
    assert row["stage_source"] == "layout-hotspots"
    assert row["layout_gb_per_template"] == {"resample": 0.2}


def test_cost_ledger_stage_gate_and_methodology_guard(tmp_path):
    # r06 marker-based, r07+r08 attribution-based with a stage regression
    _aot_cost(tmp_path / "AOT_COST_r06.json")
    _aot_cost(tmp_path / "AOT_COST_r07.json")
    _attrib(tmp_path / "HLO_ATTRIB_r07.json", stages={"resample": 2.0e9})
    _aot_cost(tmp_path / "AOT_COST_r08.json")
    _attrib(tmp_path / "HLO_ATTRIB_r08.json", stages={"resample": 3.0e9})
    ledger = cost_ledger.build_ledger(str(tmp_path))
    flags = cost_ledger.flag_regressions(ledger, threshold_pct=10.0)
    # methodology switch r06->r07 is NOT flagged; the real r07->r08
    # growth (1.0 -> 1.5 GB/template) is, naming the stage
    stage_flags = [f for f in flags if "stage " in f]
    assert len(stage_flags) == 1
    assert "stage resample" in stage_flags[0]
    assert "AOT_COST_r08.json" in stage_flags[0]
