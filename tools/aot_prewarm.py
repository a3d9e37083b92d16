"""Deviceless AOT pre-warm of the XLA persistent compilation cache.

A cold chip run compiles the batched search step for minutes per
executable — chip time spent on work that needs no chip.  This tool
compiles the SAME program for the SAME TPU generation locally, with no
device claim, via the PJRT topology API
(``jax.experimental.topologies``; the local libtpu at
``$TPU_LIBRARY_PATH`` does the compile), and writes the result into the
persistent cache the chain uses.  If the cache key matches the live
backend's, the wisdom/sweep stages start warm; if it doesn't, the
entries are simply never read — strictly harmless.

Geometry and trace-time knobs mirror the live chain exactly (shared
plumbing in ``tools/_aot_common.py``: production PALFA bank bounds and
size, the dispatched ``make_bank_step`` with its donation and pinned
layouts, ``ERP_FORCE_CASCADE=1`` so the CPU default backend doesn't lower
the native-FFT program, the resident resampler chain and the fused fold
lowered by Mosaic as on the chip, the CPU backend so no chip is
claimed).

Whether the cache key matches is no longer guesswork: ``--record-key``
snapshots the cache entry names (the keys) that a LIVE backend warm run
produced, and ``--check-key`` compares the keys this topology-AOT
prewarm writes against that record, printing MATCH or MISMATCH per
entry — a mismatch means the chain would compile cold despite the
prewarm (wrong jax version, wrong topology, drifted compile options).

``--warm`` is the serving-tier sibling: instead of a deviceless
topology compile it builds the fleet server's resident executables on
the REAL backend through ``runtime/scheduler.Scheduler.warm`` — the
same call ``serving/server.py`` makes at startup (``warm_specs=``) —
and reports how many warm compiles the persistent cache absorbed
(``fleet.aot_hit``) versus built cold (``fleet.aot_miss``).

Usage: python tools/aot_prewarm.py [--batches 16,32,64]
           [--topology v5e:2x2] [--bank FILE] [--nsamples N]
       python tools/aot_prewarm.py --record-key live-keys.json   # on chain
       python tools/aot_prewarm.py --check-key live-keys.json    # locally
       python tools/aot_prewarm.py --warm [--batches ...]        # server warmup
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _aot_common import (  # noqa: E402
    PRODUCTION_BANK,
    compile_step,
    production_geometry,
    topology_devices,
    use_cpu_backend,
)

use_cpu_backend()

KEY_SCHEMA = "erp-aot-cache-keys/1"


def _cache_entries(cache: str) -> set[str]:
    """Entry names in the persistent cache dir — the names ARE the XLA
    cache keys, so set comparison decides hit-vs-cold without touching
    jax internals."""
    try:
        return {e for e in os.listdir(cache) if not e.endswith(".tmp")}
    except OSError:
        return set()


def record_key(cache: str, path: str) -> int:
    """Snapshot the live backend's cache keys (run on the chain host
    after a warm run); ``--check-key`` compares a prewarm against it."""
    import json

    import jax

    entries = sorted(_cache_entries(cache))
    doc = {
        "schema": KEY_SCHEMA,
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "cache_dir": cache,
        "entries": entries,
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=1)
        fh.write("\n")
    print(f"recorded {len(entries)} cache key(s) from {cache} -> {path}")
    return 0 if entries else 1


def check_keys(path: str, new_entries: dict[int, set[str]]) -> int:
    """Compare the keys this prewarm wrote against the recorded live
    set.  Returns 0 when every freshly-written key is one the live
    backend is known to look up."""
    import json

    import jax

    try:
        with open(path, encoding="utf-8") as fh:
            doc = json.load(fh)
    except (OSError, ValueError) as e:
        print(f"key check: cannot read {path}: {e}")
        return 1
    if doc.get("schema") != KEY_SCHEMA:
        print(f"key check: {path} is not a {KEY_SCHEMA} document")
        return 1
    if doc.get("jax_version") != jax.__version__:
        print(
            f"key check: MISMATCH guaranteed — recorded under jax "
            f"{doc.get('jax_version')}, this prewarm runs {jax.__version__} "
            f"(the version is part of the key)"
        )
        return 1
    recorded = set(doc.get("entries", []))
    bad = 0
    for batch, fresh in sorted(new_entries.items()):
        if not fresh:
            print(f"batch {batch}: no new cache entry (already warm) — "
                  f"key comparison inconclusive")
            continue
        for key in sorted(fresh):
            if key in recorded:
                print(f"batch {batch}: key {key[:16]}... MATCH")
            else:
                print(f"batch {batch}: key {key[:16]}... MISMATCH "
                      f"(live backend never looked this key up)")
                bad += 1
    if bad:
        print(
            f"key check: {bad} entry(ies) the live chain would not reuse — "
            f"check topology/compile-option drift"
        )
        return 1
    print("key check: all freshly-compiled entries match the recorded "
          "live-backend keys")
    return 0


def warm_specs(batches: list[int], nsamples: int, tsample_us: float,
               bank_path: str) -> list:
    """The fleet server's startup warm list: one
    ``runtime/scheduler.WarmSpec`` per expected batch rung, with the
    production geometry (and the real bank when present, so the uploaded
    bank shapes match the live Sessions')."""
    from boinc_app_eah_brp_tpu.runtime import health
    from boinc_app_eah_brp_tpu.runtime.scheduler import WarmSpec

    geom, _ = production_geometry(nsamples, tsample_us, bank_path)
    kw: dict = {}
    if bank_path and os.path.exists(bank_path):
        from boinc_app_eah_brp_tpu.io.templates import read_template_bank

        bank = read_template_bank(bank_path)
        kw = {"bank_P": bank.P, "bank_tau": bank.tau, "bank_psi0": bank.psi0}
    # health telemetry changes the compiled signature; mirror what the
    # Sessions will actually request under the current env
    with_health = health.watchdog() is not None
    return [
        WarmSpec(geom=geom, batch_size=b, with_health=with_health, **kw)
        for b in batches
    ]


def warm_mode(args, cache: str) -> int:
    """``--warm``: build the serving tier's resident executables on the
    real backend, counting persistent-cache absorption."""
    from boinc_app_eah_brp_tpu.runtime.scheduler import Scheduler

    specs = warm_specs(
        [int(b) for b in args.batches.split(",")],
        args.nsamples, args.tsample_us, args.bank,
    )
    sched = Scheduler()
    t0 = time.time()
    try:
        rep = sched.warm(specs)
    finally:
        sched.close()
    print(
        f"warm: {rep['steps']} step(s) readied in {time.time() - t0:.1f}s — "
        f"fleet.aot_hit={rep['aot_hit']} fleet.aot_miss={rep['aot_miss']}"
    )
    print(f"cache {cache}: {len(_cache_entries(cache))} entries")
    return 0 if (rep["steps"] or rep["aot_hit"]) else 1


def main() -> int:
    ap = argparse.ArgumentParser(prog="aot_prewarm")
    ap.add_argument(
        "--batches", default="16,32,64",
        help="comma list of batch sizes (default: the sweep rungs that "
        "fit a v5e's HBM)",
    )
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--nsamples", type=int, default=1 << 22)
    ap.add_argument("--tsample-us", type=float, default=65.476)
    ap.add_argument("--bank", default=PRODUCTION_BANK)
    ap.add_argument("--record-key", metavar="FILE",
                    help="snapshot the cache's entry names (the live "
                         "backend's keys) to FILE and exit")
    ap.add_argument("--check-key", metavar="FILE",
                    help="after compiling, compare freshly-written keys "
                         "against a --record-key snapshot")
    ap.add_argument("--warm", action="store_true",
                    help="build the fleet server's resident executables "
                         "on the real backend (Scheduler.warm) instead of "
                         "a deviceless topology compile")
    args = ap.parse_args()

    from boinc_app_eah_brp_tpu.runtime.driver import (
        compilation_cache_dir,
        enable_compilation_cache,
    )

    cache = compilation_cache_dir()
    if cache is None:
        print("E: ERP_COMPILATION_CACHE=off — nothing to warm")
        return 1
    enable_compilation_cache()

    if args.record_key:
        return record_key(cache, args.record_key)
    if args.warm:
        return warm_mode(args, cache)

    devs = topology_devices(args.topology)
    print(f"topology: {len(devs)} devices, compiling on {devs[0]}")
    geom, n_templates = production_geometry(
        args.nsamples, args.tsample_us, args.bank
    )

    ok = 0
    new_entries: dict[int, set[str]] = {}
    for batch in [int(b) for b in args.batches.split(",")]:
        before = _cache_entries(cache)
        t0 = time.time()
        try:
            compile_step(geom, batch, devs[0], n_templates)
        except Exception as e:  # noqa: BLE001 - report and continue
            print(f"batch {batch}: AOT compile FAILED after "
                  f"{time.time() - t0:.1f}s: {type(e).__name__}: {str(e)[:300]}")
            continue
        ok += 1
        new_entries[batch] = _cache_entries(cache) - before
        print(f"batch {batch}: AOT compiled in {time.time() - t0:.1f}s")
    n_entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"cache {cache}: {n_entries} entries")
    if args.check_key:
        key_rc = check_keys(args.check_key, new_entries)
        return key_rc if ok else 1
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
