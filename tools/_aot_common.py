"""Shared plumbing for the deviceless AOT tools (aot_prewarm, aot_analyze).

Both tools must compile EXACTLY the program the live chain runs, so the
geometry derivation, trace-time knobs, topology resolution and
lower/compile sequence live here once — a drifted copy would silently
produce artifacts describing different executables.
"""

from __future__ import annotations

import os


# the bank the chain's wisdom/bench stages actually use: geometry bounds
# (max_slope, lut_step) derive from it and are part of the compiled
# program — a toy bank would prewarm cache keys nothing ever reads
PRODUCTION_BANK = (
    "/root/reference/debian/extra/einstein_bench/testwu/stochastic_full.bank"
)


def use_cpu_backend() -> None:
    """The deviceless tools compile FOR a described TPU from the CPU
    backend, with the FFT cascade the TPU traces.  Call BEFORE importing
    jax."""
    os.environ["ERP_FORCE_CASCADE"] = "1"  # mirror the TPU trace
    os.environ["JAX_PLATFORMS"] = "cpu"


def topology_devices(topology: str = "v5e:2x2"):
    """Devices of the described (not attached) TPU topology."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name=topology
    ).devices


def production_geometry(nsamples: int, tsample_us: float, bank_path: str):
    """(geom, derived) exactly as the driver derives them for the WU."""
    import numpy as np

    from boinc_app_eah_brp_tpu.models.search import (
        SearchGeometry,
        lut_step_for_bank,
        max_slope_for_bank,
    )
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig

    cfg = SearchConfig(f0=400.0, padding=3.0, window=1000, white=True)
    derived = DerivedParams.derive(nsamples, tsample_us, cfg)
    if bank_path and os.path.exists(bank_path):
        from boinc_app_eah_brp_tpu.io.templates import read_template_bank

        bank = read_template_bank(bank_path)
        bank_P, bank_tau = bank.P, bank.tau
    else:
        # shipped PALFA bank parameter ranges, for hosts without the
        # reference checkout (same bounds the bank would produce)
        bank_P = np.array([660.0, 2231.0])
        bank_tau = np.array([0.335, 0.0])
    geom = SearchGeometry.from_derived(
        derived,
        max_slope=max_slope_for_bank(bank_P, bank_tau),
        lut_step=lut_step_for_bank(bank_P, derived.dt),
    )
    # mirror the driver's deferred-renorm flip (runtime/session.py): with
    # the resident chain gated on, whitening ships the series unscaled
    # and the compiled step bakes the sqrt(nsamples) fold — the artifact
    # must describe that executable, not a near miss
    from boinc_app_eah_brp_tpu.models.search import resident_defers_renorm

    if cfg.white and resident_defers_renorm(geom):
        import dataclasses

        geom = dataclasses.replace(geom, ts_prescaled=False)
    return geom, derived


def compile_step(geom, derived, batch: int, device):
    """Lower + compile the production batched search step for ``device``
    (a topology device) at ``batch``; returns the Compiled object."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from boinc_app_eah_brp_tpu.models.search import (
        init_state,
        make_batch_step,
        prepare_ts,
        template_params_host,
    )

    rng = np.random.default_rng(0)
    ts = rng.uniform(0, 15, derived.n_unpadded).astype(np.float32)
    ts_args = prepare_ts(geom, ts)
    M, T = init_state(geom)
    params = [
        template_params_host(1000.0 + t, 0.01, 0.0, geom.dt)
        for t in range(batch)
    ]
    bp = tuple(
        jnp.asarray(np.array([p[i] for p in params], dtype=np.float32))
        for i in range(4)
    )

    def ab(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
            tree,
        )

    step = make_batch_step(geom)
    return (
        jax.jit(step, device=device)
        .lower(ab(ts_args), *ab(bp), jax.ShapeDtypeStruct((), np.int32),
               *ab((M, T)))
        .compile()
    )
