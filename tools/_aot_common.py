"""Shared plumbing for the deviceless AOT compiles (``tools/aot_prewarm.py``
and ``tests/test_tpu_compile.py``).

Both must compile EXACTLY the program the live chain runs, so the
geometry derivation, trace-time knobs, topology resolution and
lower/compile sequence live here once.
"""

from __future__ import annotations

import os


# the bank the chain's wisdom/bench stages actually use: geometry bounds
# (max_slope, lut_step) derive from it and are part of the compiled
# program — a toy bank would prewarm cache keys nothing ever reads
PRODUCTION_BANK = (
    "/root/reference/debian/extra/einstein_bench/testwu/stochastic_full.bank"
)
# its template count, for hosts without it: the uploaded bank's padded
# length is part of the step's signature (models/search.py::upload_bank)
PRODUCTION_BANK_SIZE = 6662


def use_cpu_backend() -> None:
    """The deviceless tools compile FOR a described TPU from the CPU
    backend, with what the TPU traces: the FFT cascade, the resident
    resampler chain and the fused harmonic fold, lowered by Mosaic (not
    interpreted).  Call BEFORE importing jax."""
    os.environ["ERP_FORCE_CASCADE"] = "1"
    os.environ["ERP_PALLAS_RESIDENT"] = "1"
    os.environ["ERP_PALLAS_SUMSPEC"] = "1"
    os.environ["ERP_PALLAS_INTERPRET"] = "0"
    os.environ["JAX_PLATFORMS"] = "cpu"


def topology_devices(topology: str = "v5e:2x2"):
    """Devices of the described (not attached) TPU topology."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name=topology
    ).devices


def production_geometry(nsamples: int, tsample_us: float, bank_path: str):
    """(geom, n_templates): the geometry exactly as the driver derives it
    for the WU, and the bank's template count."""
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
        n_templates = len(bank_P)
    else:
        # shipped PALFA bank parameter ranges, for hosts without the
        # reference checkout (same bounds the bank would produce)
        bank_P = np.array([660.0, 2231.0])
        bank_tau = np.array([0.335, 0.0])
        n_templates = PRODUCTION_BANK_SIZE
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
    return geom, n_templates


def compile_step(geom, batch: int, device, n_templates: int = 8):
    """Lower + compile the dispatched bank step (``make_bank_step``, as
    ``run_bank`` builds it on a TPU: donated (M, T), row-major layouts
    pinned for ``device``) at ``batch`` over an ``n_templates`` bank, for
    ``device`` (a topology device); returns the Compiled object."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from boinc_app_eah_brp_tpu.models.search import (
        bank_step_layouts,
        init_state,
        make_bank_step,
        upload_bank,
    )

    S = jax.ShapeDtypeStruct
    bank = upload_bank(
        tuple(np.zeros(n_templates, np.float32) for _ in range(4)), batch
    )
    n_ts = 2 if geom.parity_split else 1
    ts = tuple(
        S((geom.n_unpadded // n_ts,), jnp.float32) for _ in range(n_ts)
    )
    M, T = jax.eval_shape(lambda: init_state(geom))
    in_sh, out_sh = bank_step_layouts(geom, False, device)
    step = make_bank_step(geom, batch_size=batch).__wrapped__
    return (
        jax.jit(step, donate_argnums=(7, 8), in_shardings=in_sh,
                out_shardings=out_sh)
        .lower(ts, *(S(a.shape, a.dtype) for a in bank), S((), jnp.int32),
               S((), jnp.int32), M, T)
        .compile()
    )
