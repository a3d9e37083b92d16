"""Measured batch-size sweep for the batched search step (VERDICT r03 #6).

The per-template working set is known statically (~6x nsamples float32:
parity streams, cascade intermediates, spectra), but the throughput-optimal
batch also depends on how XLA schedules the vmapped pipeline, so the driver's
auto-sizing (runtime/autobatch.py) is anchored to a measured sweep on the
real chip: this tool times the production search step at a ladder of batch
sizes and records templates/sec per rung plus the winner.

Protocol per rung: compile + one warmup step, then `--steps` timed steps
(distinct template params per step, like the real driver loop).  An OOM at
a rung records the failure and stops the ladder (larger batches would OOM
too).  Strictly serial on the device, synced by one-element D2H fetches.

Writes one JSON artifact: {"rungs": [...], "best_batch": N, ...}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TESTWU = "/root/reference/debian/extra/einstein_bench/testwu"
WU = os.path.join(TESTWU, "p2030.20151015.G187.41-00.88.N.b2s0g0.00000_1099.bin4")
BANK = os.path.join(TESTWU, "stochastic_full.bank")
ZAP = os.path.join(TESTWU, "p2030.20151015.G187.41-00.88.N.b2s0g0.00000.zap")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--batches", default="16,32,64,96,128",
        help="comma-separated batch ladder (ascending)",
    )
    ap.add_argument("--steps", type=int, default=3, help="timed steps per rung")
    ap.add_argument("--json", default="BATCHSWEEP.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from boinc_app_eah_brp_tpu.runtime.driver import enable_compilation_cache
    enable_compilation_cache()
    backend = jax.default_backend()
    print(f"batch_sweep: backend={backend}", flush=True)

    from boinc_app_eah_brp_tpu.io.templates import read_template_bank
    from boinc_app_eah_brp_tpu.io.workunit import read_workunit
    from boinc_app_eah_brp_tpu.io.zaplist import read_zaplist
    from boinc_app_eah_brp_tpu.models.search import (
        SearchGeometry,
        bank_params_host,
        init_state,
        lut_step_for_bank,
        make_bank_step,
        max_slope_for_bank,
        prepare_ts,
        upload_bank,
    )
    from boinc_app_eah_brp_tpu.ops.whiten import whiten_and_zap
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig

    cfg = SearchConfig(f0=400.0, padding=3.0, fA=0.08, window=1000, white=True)
    wu = read_workunit(WU)
    bank = read_template_bank(BANK)
    zap_ranges = read_zaplist(ZAP)
    derived = DerivedParams.derive(wu.nsamples, float(wu.header["tsample"]), cfg)
    samples = whiten_and_zap(
        wu.samples, derived, cfg, zap_ranges, return_device_split=True
    )
    geom = SearchGeometry.from_derived(
        derived,
        max_slope=max_slope_for_bank(bank.P, bank.tau),
        lut_step=lut_step_for_bank(bank.P, derived.dt),
    )
    ts_args = samples if isinstance(samples, tuple) else prepare_ts(geom, samples)
    P, tau, psi = bank.P, bank.tau, bank.psi0
    # bank-resident feed, same as the production dispatch loop
    # (models/search.py::run_bank): params derived once, uploaded once
    params = bank_params_host(P, tau, psi, geom.dt)
    n_total = jnp.int32(len(P))

    def hbm_stats() -> dict:
        try:
            s = jax.devices()[0].memory_stats() or {}
            return {
                "bytes_in_use": int(s.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(s.get("peak_bytes_in_use", 0)),
                "bytes_limit": int(s.get("bytes_limit", 0)),
            }
        except Exception:
            return {}

    rungs = []
    best = None
    for batch in [int(b) for b in args.batches.split(",")]:
        if batch > len(P):
            break
        rung: dict = {"batch": batch}
        try:
            M, T = init_state(geom)
            step = make_bank_step(geom, batch)
            dev_bank = upload_bank(params, batch)
            t0 = time.perf_counter()
            M, T = step(ts_args, *dev_bank, jnp.int32(0), n_total, M, T)
            np.asarray(M.ravel()[:1])  # sync
            rung["compile_first_s"] = round(time.perf_counter() - t0, 2)
            t0 = time.perf_counter()
            for k in range(args.steps):
                start = (1 + k) * batch % (len(P) - batch)
                M, T = step(
                    ts_args, *dev_bank, jnp.int32(start), n_total, M, T
                )
            np.asarray(M.ravel()[:1])
            wall = time.perf_counter() - t0
            rung["steps"] = args.steps
            rung["wall_s"] = round(wall, 3)
            rung["templates_per_sec"] = round(args.steps * batch / wall, 3)
            rung["hbm"] = hbm_stats()
            rungs.append(rung)
            print(f"batch_sweep: batch={batch} -> "
                  f"{rung['templates_per_sec']} t/s", flush=True)
            if best is None or rung["templates_per_sec"] > best[1]:
                best = (batch, rung["templates_per_sec"])
        except Exception as e:  # OOM or backend failure: record, stop ladder
            rung["error"] = f"{type(e).__name__}: {e}"[:500]
            rungs.append(rung)
            print(f"batch_sweep: batch={batch} FAILED: {rung['error']}",
                  flush=True)
            break

    try:
        device_kind = str(jax.devices()[0].device_kind)
    except Exception:  # noqa: BLE001
        device_kind = None
    payload = {
        "what": "search-step batch sweep, production WU "
        "(-A 0.08 -P 3.0 -f 400.0 -W), templates/sec per batch size",
        "backend": backend,
        # where and at what problem size these rungs were PROVEN to run:
        # runtime/autobatch.py accepts best_batch without a model gate
        # only when BOTH device_kind and nsamples match the live run
        "device_kind": device_kind,
        "nsamples": geom.nsamples,
        "rungs": rungs,
        "best_batch": best[0] if best else None,
        "best_templates_per_sec": best[1] if best else None,
    }
    with open(args.json, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {args.json}")
    return 0 if best else 1


if __name__ == "__main__":
    sys.exit(main())
