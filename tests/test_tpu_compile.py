"""Compiles for a described (not attached) TPU v5e: the production Pallas
kernels at the shipped 2^22-sample WU's widths, and the layout-pinned,
donated bank step, with interpret mode off.  What the chip's compiler
refuses (fast-memory limits, slices not aligned to the tiling, a wrong
layout) fails here at no chip time (on-chip-measurement guide, section 2).

The topology is described only inside the module fixture: one process at a
time may load the TPU library, so no import-time call and one file."""

import os
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


def test_sumspec_kernel_at_production_widths(geom, one_chip):
    from boinc_app_eah_brp_tpu.ops.pallas_sumspec import (
        state_width,
        sumspec_applicable,
        sumspec_pallas_batch,
    )

    assert sumspec_applicable(geom.fund_hi, geom.harm_hi)
    fn = jax.jit(lambda ps: sumspec_pallas_batch(
        ps, window_2=geom.window_2, fund_hi=geom.fund_hi,
        harm_hi=geom.harm_hi, interpret=False,
    ))
    ps = _spec((BATCH, geom.nsamples // 2 + 1), jnp.float32, one_chip)
    comp = fn.lower(ps).compile()
    assert "tpu_custom_call" in comp.as_text()
    assert comp.out_info.shape == (BATCH, 5, state_width(geom.fund_hi))


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
    from boinc_app_eah_brp_tpu.models.search import (
        SearchGeometry,
        bank_step_layouts,
        init_state,
        make_bank_step,
        upload_bank,
    )
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig

    monkeypatch.setenv("ERP_PALLAS_SUMSPEC", "1")
    cfg = SearchConfig(window=200, padding=1.5)
    geom = SearchGeometry.from_derived(DerivedParams.derive(4096, 500.0, cfg))
    B = 4
    bank = upload_bank(tuple(np.zeros(8, np.float32) for _ in range(4)), B)
    M, T = jax.eval_shape(lambda: init_state(geom))
    S = jax.ShapeDtypeStruct
    ts = tuple(S((geom.n_unpadded // 2,), jnp.float32) for _ in range(2))

    fn = make_bank_step(geom, batch_size=B).__wrapped__
    in_sh, out_sh = bank_step_layouts(geom, False, topo.devices[0])
    comp = jax.jit(
        fn, donate_argnums=(7, 8), in_shardings=in_sh, out_shardings=out_sh
    ).lower(
        ts, *(S(a.shape, a.dtype) for a in bank), S((), jnp.int32),
        S((), jnp.int32), M, T,
    ).compile()
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
