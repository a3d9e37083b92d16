"""Device-cost observatory (runtime/devicecost.py): stage-registry
semantics, named scopes surviving into COMPILED HLO op_name metadata —
down to every stage of the dispatched bank step, which the benchmark's
per-stage device times read — zero recompiles and zero numeric effect
from scoping, and device records -> Chrome-export merge -> trace_report
device section."""

import json
import os
import sys

import numpy as np
import pytest

from boinc_app_eah_brp_tpu.runtime import devicecost, metrics, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import trace_report  # noqa: E402


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


# --- every stage of the dispatched bank step carries its scope ------------

# the stages each step path runs: the ladder's XLA rung (with the health
# vector) and the Pallas path (resident resampler chain + fused fold)
_XLA_STAGES = ("resample", "fft", "power", "harmonic", "bank-slice",
               "merge", "health")
_PALLAS_STAGES = ("resample", "fftprep", "fft", "power", "sumspec",
                  "bank-slice", "merge")


def _bank_step_stages(env, geom, **step_kw):
    """The stages named by the innermost ``erp.*`` scope of some
    instruction of ``make_bank_step``'s compiled module (CPU), built and
    lowered under ``env``."""
    import jax
    import jax.numpy as jnp

    from boinc_app_eah_brp_tpu.models.search import (
        init_state,
        make_bank_step,
        upload_bank,
    )

    S = jax.ShapeDtypeStruct
    with pytest.MonkeyPatch.context() as mp:
        for k, v in env.items():
            mp.setenv(k, v)
        for k in {"ERP_PALLAS_RESIDENT", "ERP_PALLAS_SUMSPEC"} - set(env):
            mp.delenv(k, raising=False)
        bank = upload_bank(tuple(np.zeros(4, np.float32) for _ in range(4)), 2)
        ts = tuple(S((geom.n_unpadded // 2,), jnp.float32) for _ in range(2))
        text = make_bank_step(geom, 2, **step_kw).lower(
            ts, *(S(a.shape, a.dtype) for a in bank), S((), jnp.int32),
            S((), jnp.int32), *jax.eval_shape(lambda: init_state(geom)),
        ).compile().as_text()
    return {devicecost.stage_of_op_name(v)
            for v in devicecost.hlo_op_scopes(text).values()}


def _geom(max_slope, lut_step):
    from boinc_app_eah_brp_tpu.models.search import SearchGeometry
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig

    derived = DerivedParams.derive(1 << 13, 500.0, SearchConfig(window=200))
    return SearchGeometry.from_derived(
        derived, max_slope=max_slope, lut_step=lut_step
    )


@pytest.fixture(scope="module")
def xla_step_stages():
    return _bank_step_stages({}, _geom(0.5, 0.05), with_health=True,
                             allow_pallas=False)


@pytest.fixture(scope="module")
def pallas_step_stages():
    # slope/LUT bounds inside the resident chain's gates (the PALFA
    # bank's pow2-ceil'd values)
    geom = _geom(0.00390625, 1.52587890625e-05)
    return _bank_step_stages(
        {"ERP_PALLAS_RESIDENT": "1", "ERP_PALLAS_SUMSPEC": "1"}, geom
    )


@pytest.mark.parametrize(
    "path,stage",
    [("xla", s) for s in _XLA_STAGES] + [("pallas", s) for s in _PALLAS_STAGES],
)
def test_bank_step_stage_carries_its_scope(path, stage, request):
    """Each stage the dispatched step runs is the innermost ``erp.*``
    scope of some instruction of its compiled module: the per-stage device
    times of the profiler trace (``resample.ms_per_t``, ``fft.ms_per_t``,
    ``harmonic.ms_per_t``, ...) read the step's events through these
    scopes, so a stage that lost its scope would fall silently into
    ``step.other``."""
    stages = request.getfixturevalue(f"{path}_step_stages")
    assert stage in stages, sorted(s for s in stages if s)


# --- device records in the Chrome export ----------------------------------


def _span(name, ctx, ts, end, tid="MainThread"):
    return {
        "kind": "span", "name": name, "tid": tid, "ctx": ctx,
        "ts_us": ts, "end_us": end, "dur_us": end - ts, "depth": 0,
    }


def test_device_records_merge_into_chrome_only(tmp_path):
    """End to end without jax: host spans stream to JSONL,
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
                    "name": "erp.fft", "tid": "device:measured", "ctx": 1,
                    "ts_us": drain["ts_us"], "dur_us": dur,
                    "end_us": drain["ts_us"] + dur,
                    "args": {"measured": True, "stage": "fft"},
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
    assert "device:measured" in dev["lane_busy_s"]
    assert dev["stages"]["fft"]["count"] == 1
    # the device span covers the whole drain: all device-bound
    assert dev["drain_host_stall_s"] == pytest.approx(0.0, abs=1e-4)
    assert dev["drain_device_bound_s"] == pytest.approx(
        dev["drain_s"], rel=0.05
    )
    rendered = trace_report.render(table, "t")
    assert "Device lanes:" in rendered
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
