"""Compiles for a described (not attached) TPU v5e: the production Pallas
kernels at the shipped 2^22-sample WU's widths, and the layout-pinned,
donated bank step, with interpret mode off.  What the chip's compiler
refuses (fast-memory limits, slices not aligned to the tiling, a wrong
layout) fails here at no chip time (on-chip-measurement guide, section 2).

The topology is described only inside the module fixture: one process at a
time may load the TPU library, so no import-time call and one file."""

import os
import re
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

BATCH = 2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def geom():
    from _aot_common import production_geometry

    return production_geometry(1 << 22, 65.476, "")[0]


@pytest.fixture(autouse=True)
def _mosaic(monkeypatch):
    """Real Mosaic lowering, the TPU's FFT cascade, no persistent cache
    (a deviceless compile cannot be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("ERP_PALLAS_INTERPRET", "0")
    monkeypatch.setenv("ERP_FORCE_CASCADE", "1")
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", saved)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


_OPCODE_RE = re.compile(r" = .*? ([a-z][a-z0-9-]*)\(")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
# what XLA lowers the resampler's gather to: a while loop of
# dynamic-slice / dynamic-update-slice, one iteration per output block
_GATHER_LOOP_OPS = {"while", "dynamic-slice", "dynamic-update-slice"}


def _ops_by_stage(text):
    """{stage: {opcode, ...}} over the compiled module's instructions,
    and the stages of its Mosaic kernels."""
    from boinc_app_eah_brp_tpu.runtime.devicecost import stage_of_op_name

    ops, kernels = {}, set()
    for line in text.splitlines():
        name, code = _OP_NAME_RE.search(line), _OPCODE_RE.search(line)
        if name is None or code is None:
            continue
        stage = stage_of_op_name(name.group(1))
        ops.setdefault(stage, set()).add(code.group(1))
        if 'custom_call_target="tpu_custom_call"' in line:
            kernels.add(stage)
    return ops, kernels


_SLICE_RE = re.compile(r"slice=\{([^}]*)\}")


def _strided_slices(text, stage):
    """The compiled module's slices under ``stage`` whose minor dimension
    takes a stride above 1 (``[start:limit:stride]``)."""
    from boinc_app_eah_brp_tpu.runtime.devicecost import stage_of_op_name

    out = []
    for line in text.splitlines():
        name, sl = _OP_NAME_RE.search(line), _SLICE_RE.search(line)
        if name is None or sl is None or stage_of_op_name(name.group(1)) != stage:
            continue
        dims = re.findall(r"\[([^\]]*)\]", sl.group(1))
        parts = dims[-1].split(":") if dims else []
        if len(parts) == 3 and int(parts[2]) > 1:
            out.append(line.strip()[:160])
    return out


@pytest.mark.parametrize("fund_hi,harm_hi,L", [
    (329551, 5272824, 3 * (1 << 21) + 1),  # palfa_p3: FFT length 3 * 2^22
    (68657, 1098505, (1 << 21) + 1),  # refdefault_p1: FFT length 2^22
])
def test_sumspec_kernel_at_production_widths(one_chip, fund_hi, harm_hi, L):
    """The fused fold at the cells' widths: its Mosaic kernel is there,
    and nothing under ``erp.sumspec`` rearranges the spectrum by strides
    along the lane axis (its views are reshapes and 2D transposes)."""
    from boinc_app_eah_brp_tpu.ops.pallas_sumspec import (
        state_width,
        sumspec_applicable,
        sumspec_pallas_batch,
    )

    assert sumspec_applicable(fund_hi, harm_hi)
    fn = jax.jit(lambda ps: sumspec_pallas_batch(
        ps, window_2=500, fund_hi=fund_hi, harm_hi=harm_hi, interpret=False,
    ))
    ps = _spec((BATCH, L), jnp.float32, one_chip)
    comp = fn.lower(ps).compile()
    text = comp.as_text()
    _, kernels = _ops_by_stage(text)
    assert "sumspec" in kernels, kernels
    assert not _strided_slices(text, "sumspec"), _strided_slices(text, "sumspec")
    assert comp.out_info.shape == (BATCH, 5, state_width(fund_hi))


def test_resident_resample_fftprep_kernel_at_production_widths(geom, one_chip):
    from boinc_app_eah_brp_tpu.ops.pallas_resample import (
        pallas_applicable,
        resample_fftprep_pallas_batch,
    )

    assert pallas_applicable(geom.max_slope, geom.lut_step, geom.lut_tiles)
    fn = jax.jit(lambda ev, od, tau, om, psi, s0: resample_fftprep_pallas_batch(
        ev, od, tau, om, psi, s0, nsamples=geom.nsamples,
        n_unpadded=geom.n_unpadded, dt=geom.dt, max_slope=geom.max_slope,
        lut_step=geom.lut_step, lut_tiles=geom.lut_tiles, interpret=False,
    ))
    half = _spec((geom.n_unpadded // 2,), jnp.float32, one_chip)
    par = _spec((BATCH,), jnp.float32, one_chip)
    comp = fn.lower(half, half, par, par, par, par).compile()
    assert "tpu_custom_call" in comp.as_text()
    ev, od = comp.out_info
    assert ev.shape[0] == od.shape[0] == BATCH


def test_layout_pinned_bank_step(topo, monkeypatch):
    """The donated, layout-pinned bank step with the fused fold kernel:
    row-major (M, T) on both sides of the donation, so the state aliases
    through every dispatch window unchanged."""
    from _aot_common import compile_step

    from boinc_app_eah_brp_tpu.models.search import SearchGeometry
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig

    monkeypatch.setenv("ERP_PALLAS_SUMSPEC", "1")
    cfg = SearchConfig(window=200, padding=1.5)
    geom = SearchGeometry.from_derived(DerivedParams.derive(4096, 500.0, cfg))
    comp = compile_step(geom, 4, topo.devices[0])
    text = comp.as_text()
    assert "tpu_custom_call" in text and "erp.sumspec" in text
    # the TPU's profile names events after these instructions: the text's
    # op_name metadata is what attributes them (runtime/devicecost.py)
    from boinc_app_eah_brp_tpu.runtime.devicecost import (
        hlo_op_scopes,
        stage_of_op_name,
    )

    stages = {stage_of_op_name(v) for v in hlo_op_scopes(text).values()}
    assert {"resample", "fft", "sumspec", "merge"} <= stages, stages
    in_f, _ = comp.input_formats
    for f in (in_f[7], in_f[8], *comp.output_formats):
        assert f.layout.major_to_minor == (0, 1)


@pytest.mark.parametrize("f0,padding,fA", [
    (400.0, 3.0, 0.08),  # palfa_p3: FFT length 3 * 2^22
    (250.0, 1.0, 0.04),  # refdefault_p1: FFT length 2^22
])
def test_resident_bank_step_at_production_widths(topo, monkeypatch, f0,
                                                 padding, fA):
    """The production bank step at the shipped WU's widths: the resident
    resample -> FFT-prep chain and the fused harmonic fold inside a
    donated, layout-pinned step (the gates forced, since this backend is
    the CPU), with the whitening renorm folded into the kernel as the
    Session defers it.  All three kernels are there, the XLA resampler's
    gather loop is not, the fold's scope holds no lane-strided slice, and
    (M, T) stay row-major."""
    import dataclasses

    from _aot_common import compile_step

    from boinc_app_eah_brp_tpu.models.search import (
        SearchGeometry,
        lut_step_for_bank,
        max_slope_for_bank,
        resident_defers_renorm,
        use_pallas_resident,
    )
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig

    monkeypatch.setenv("ERP_PALLAS_RESIDENT", "1")
    monkeypatch.setenv("ERP_PALLAS_SUMSPEC", "1")
    cfg = SearchConfig(f0=f0, padding=padding, fA=fA, window=1000, white=True)
    derived = DerivedParams.derive(1 << 22, 65.476, cfg)
    P, tau = np.array([660.0, 2231.0]), np.array([0.335, 0.0])  # PALFA
    geom = SearchGeometry.from_derived(
        derived, max_slope=max_slope_for_bank(P, tau),
        lut_step=lut_step_for_bank(P, derived.dt),
    )
    assert use_pallas_resident(geom) and resident_defers_renorm(geom)
    geom = dataclasses.replace(geom, ts_prescaled=False)
    comp = compile_step(geom, BATCH, topo.devices[0])
    text = comp.as_text()
    ops, kernels = _ops_by_stage(text)
    assert {"resample", "fftprep", "sumspec"} <= kernels, kernels
    assert not ops["resample"] & _GATHER_LOOP_OPS, ops["resample"]
    assert not _strided_slices(text, "sumspec")
    in_f, _ = comp.input_formats
    for f in (in_f[7], in_f[8], *comp.output_formats):
        assert f.layout.major_to_minor == (0, 1)


def test_mesh_step_on_the_resident_chain(topo, monkeypatch):
    """The four-chip mesh step (``make_sharded_batch_step``) over the
    described 2x2 at the shipped WU's palfa_p3 widths: each shard runs the
    resident chain and the fused fold (their kernels there, the XLA
    resampler's gather loop not) and the (M, T) merge is
    ``collective-permute``s under ``erp.allreduce``.  The mesh keeps its
    series prescaled, so the kernel folds no renorm."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from boinc_app_eah_brp_tpu.models.search import (
        SearchGeometry,
        init_state,
        lut_step_for_bank,
        max_slope_for_bank,
        upload_bank,
    )
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig
    from boinc_app_eah_brp_tpu.parallel import make_sharded_batch_step

    monkeypatch.setenv("ERP_PALLAS_RESIDENT", "1")
    monkeypatch.setenv("ERP_PALLAS_SUMSPEC", "1")
    cfg = SearchConfig(f0=400.0, padding=3.0, fA=0.08, window=1000, white=True)
    derived = DerivedParams.derive(1 << 22, 65.476, cfg)
    P, tau = np.array([660.0, 2231.0]), np.array([0.335, 0.0])  # PALFA
    geom = SearchGeometry.from_derived(
        derived, max_slope=max_slope_for_bank(P, tau),
        lut_step=lut_step_for_bank(P, derived.dt),
    )
    mesh = Mesh(np.array(topo.devices[:4]), ("templates",))
    rep = NamedSharding(mesh, PartitionSpec())
    step = make_sharded_batch_step(geom, mesh, BATCH)
    assert step.resident and step.fused
    bank = upload_bank(
        tuple(np.zeros(16, np.float32) for _ in range(4)), 4 * BATCH
    )
    M, T = jax.eval_shape(lambda: init_state(geom))
    ts = tuple(
        _spec((geom.n_unpadded // 2,), jnp.float32, rep) for _ in range(2)
    )
    text = step.lower(
        ts, *(_spec(a.shape, a.dtype, rep) for a in bank),
        _spec((), jnp.int32, rep), _spec((), jnp.int32, rep),
        _spec(M.shape, M.dtype, rep), _spec(T.shape, T.dtype, rep),
    ).compile().as_text()
    ops, kernels = _ops_by_stage(text)
    assert {"resample", "fftprep", "sumspec"} <= kernels, kernels
    assert not ops["resample"] & _GATHER_LOOP_OPS, ops["resample"]
    # a TPU runs the permute async: collective-permute-start / -done
    permutes = {
        k for k, v in ops.items()
        if any(op.startswith("collective-permute") for op in v)
    }
    assert permutes == {"allreduce"}, ops.get("allreduce")
