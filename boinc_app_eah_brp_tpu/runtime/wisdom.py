"""Pre-populate the XLA persistent compilation cache ("wisdom").

TPU analogue of the reference's FFTW wisdom tooling
(``debian/extra/create_wisdomf_eah_brp.sh``, which spends 6-120 h finding
FFT plans for the production 3*2^22-sample transform): here the expensive
artifact is the XLA compilation of the batched search step and of the
whitening pass (minutes, not hours). Run once per (geometry, batch size,
device) — every subsequent worker start hits the persistent cache
(``runtime/driver.py:enable_compilation_cache``, ON by default).

Lives in the package (not only ``tools/``) so the deployed worker archive
can warm its own cache: ``python3 eah_brp_worker.pyz --create-wisdom`` or
``python tools/create_wisdom.py`` both land here.
"""

from __future__ import annotations

import argparse
import os
import time


def warm(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="create_wisdom")
    ap.add_argument(
        "--batch", type=int, default=None,
        help="templates per step (default: the driver's own auto choice, "
        "runtime/autobatch.py, so the cache entry matches production)",
    )
    ap.add_argument("--nsamples", type=int, default=1 << 22)
    ap.add_argument("--tsample-us", type=float, default=65.476)
    ap.add_argument("--f0", type=float, default=400.0)
    ap.add_argument("--padding", type=float, default=3.0)
    ap.add_argument("--window", type=int, default=1000)
    ap.add_argument(
        "--bank",
        default=None,
        help="template bank file: derive the geometry's static slope/LUT "
        "bounds exactly as the driver will, so the cache entry matches "
        "production runs",
    )
    ap.add_argument(
        "--skip-whiten", action="store_true",
        help="warm only the search step, not the whitening pass",
    )
    ap.add_argument(
        "--unwhitened", action="store_true",
        help="also warm the unwhitened-run step variant (exact_mean=True "
        "takes per-template host (n_steps, mean) inputs, a different "
        "compiled executable; production -W runs don't need it)",
    )
    args = ap.parse_args(argv)

    from .driver import compilation_cache_dir, enable_compilation_cache

    cache = compilation_cache_dir()
    if cache is None:
        print("E: ERP_COMPILATION_CACHE=off — nothing to warm")
        return 1
    enable_compilation_cache()

    import jax
    import numpy as np

    from ..models.search import (
        SearchGeometry,
        bank_params_host,
        init_state,
        lut_step_for_bank,
        make_bank_step,
        max_slope_for_bank,
        upload_bank,
    )
    from ..oracle.pipeline import DerivedParams, SearchConfig

    cfg = SearchConfig(
        f0=args.f0, padding=args.padding, window=args.window, white=True
    )
    derived = DerivedParams.derive(args.nsamples, args.tsample_us, cfg)
    if args.bank:
        from ..io.templates import read_template_bank

        bank = read_template_bank(args.bank)
        bank_P, bank_tau = bank.P, bank.tau
    else:
        # shipped PALFA bank parameter ranges (P 660-2231 s, tau <= 0.335)
        bank_P = np.array([660.0, 2231.0])
        bank_tau = np.array([0.335, 0.0])
    geom = SearchGeometry.from_derived(
        derived,
        max_slope=max_slope_for_bank(bank_P, bank_tau),
        lut_step=lut_step_for_bank(bank_P, derived.dt),
    )
    if args.batch is None:
        from .autobatch import choose_batch

        args.batch = choose_batch(geom.nsamples, log=lambda m: print(m, end=""))
    print(
        f"geometry: nsamples={geom.nsamples} fft_size={geom.fft_size} "
        f"batch={args.batch} backend={jax.default_backend()}"
    )

    # the production dispatch step (models/search.py::make_bank_step):
    # bank-resident params, sliced on device.  upload_bank pads to a
    # power-of-two capacity with an 8192 floor, so this placeholder bank
    # compiles the SAME executable as a production 6.7k-template bank —
    # the whole point of the quantized capacity.
    step = make_bank_step(geom, args.batch)
    rng = np.random.default_rng(0)
    ts = rng.uniform(0, 15, derived.n_unpadded).astype(np.float32)
    wp = np.full(args.batch, 1000.0) + np.arange(args.batch)
    params = bank_params_host(
        wp, np.full(args.batch, 0.01), np.zeros(args.batch), geom.dt
    )
    dev_bank = upload_bank(params, args.batch)
    import jax.numpy as jnp

    from ..models.search import prepare_ts

    n_total = jnp.int32(args.batch)
    M, T = init_state(geom)
    ts_args = prepare_ts(geom, ts)
    t0 = time.time()
    M, T = step(ts_args, *dev_bank, jnp.int32(0), n_total, M, T)
    jax.block_until_ready(M)
    print(f"search step compiled + executed in {time.time() - t0:.1f}s")

    if args.unwhitened:
        # unwhitened runs use the exact_mean step (driver.py): same
        # pipeline plus two per-template host-input arrays — a distinct
        # executable that must be warmed separately
        import dataclasses

        geom_em = dataclasses.replace(geom, exact_mean=True)
        step_em = make_bank_step(geom_em, args.batch)
        Me, Te = init_state(geom_em)
        ns = jnp.full((args.batch,), geom.n_unpadded - 2, dtype=jnp.int32)
        mn = jnp.full((args.batch,), 7.5, dtype=jnp.float32)
        t0 = time.time()
        Me, Te = step_em(
            ts_args, *dev_bank, jnp.int32(0), n_total, Me, Te, ns, mn
        )
        jax.block_until_ready(Me)
        print(f"unwhitened (exact_mean) step compiled in {time.time() - t0:.1f}s")

    if not args.skip_whiten:
        # whitening-path compiles (full-size rfft/irfft + scale/scatter)
        # are a separate, comparable cost paid once per worker start
        from ..ops.whiten import whiten_and_zap

        zap_ranges = np.array([[60.0, 60.2]], dtype=np.float64)
        t0 = time.time()
        whiten_and_zap(ts, derived, cfg, zap_ranges)
        print(f"whitening path compiled + executed in {time.time() - t0:.1f}s")
    print(f"cache at {cache}")
    return 0
