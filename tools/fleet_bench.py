"""Fleet serving bench: WUs/hour/chip at zero recompiles after warmup.

The serving tier's headline claim (ROADMAP item 3, ``docs/serving.md``)
is that a resident Session/Scheduler server streams same-geometry
workunits through CACHED executables — after warmup, the
``jax.recompiles`` counter stays flat and the inter-WU gap is host
bookkeeping only.  This bench proves it end to end, chip-free:

* synthesizes N same-geometry workunits (the 4096-sample fixture class
  every soak uses), pre-warms the server via the same
  ``Scheduler.warm`` call ``tools/aot_prewarm.py --warm`` exercises,
  then streams them through one :class:`serving.FleetServer`;
* gates ``recompiles_after_warmup == 0`` — with an explicit warm, WU 1
  already runs on the resident executable;
* ``--verify`` re-runs every workunit through the classic
  one-process-per-WU driver and requires the server's result files to
  be BYTE-IDENTICAL (same science, same provenance, zero drift);
* writes the scoreboard to ``.erp_cache/fleet_bench_ci.json`` and
  (``--check``) gates it against the committed
  ``FLEET_SERVING_BASELINE.json`` floors — the same trajectory gate
  ``tools/bench_history.py --strict`` applies in ``make test``.

Usage:
    python tools/fleet_bench.py                     # measure + cache
    python tools/fleet_bench.py --verify --check    # the make fleet-bench gate
    python tools/fleet_bench.py --wus 8 --keep --workdir DIR
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))
sys.path.insert(0, os.path.join(REPO, "tools"))

SCHEMA = "erp-fleet-bench/1"
BASELINE_SCHEMA = "erp-fleet-serving-baseline/1"
RESULT_DATE = "2008-11-12T00:00:00+00:00"

# the soak fixture class: 4096 samples at 500 us, small PALFA-shaped
# bank, pinned window/batch — same geometry for every WU by design
N_SAMPLES = 4096
TSAMPLE_US = 500.0
WINDOW = 200
BATCH = 2


def fail(msg: str) -> int:
    print(f"fleet-bench: FAIL: {msg}", file=sys.stderr)
    return 1


def build_workunits(work: str, n: int):
    """N same-geometry workunits (distinct signals/noise seeds) sharing
    one template bank; returns (DriverArgs list, bank path)."""
    from fixtures import small_bank, synthetic_timeseries

    from boinc_app_eah_brp_tpu.io import write_template_bank, write_workunit
    from boinc_app_eah_brp_tpu.runtime.driver import DriverArgs

    bank = os.path.join(work, "bank.dat")
    write_template_bank(
        bank, small_bank(P_true=2.2, tau_true=0.04, psi_true=1.2)
    )
    out = []
    for i in range(n):
        ts = synthetic_timeseries(
            N_SAMPLES, f_signal=31.0 + 2.0 * i, P_orb=2.2, tau=0.04,
            psi0=1.2, amp=7.0, seed=i,
        )
        wu = os.path.join(work, f"wu{i:03d}.bin4")
        write_workunit(wu, ts, tsample_us=TSAMPLE_US, scale=1.0, dm=55.5)
        out.append(
            DriverArgs(
                inputfile=wu,
                outputfile=os.path.join(work, f"wu{i:03d}.cand"),
                templatebank=bank,
                checkpointfile=os.path.join(work, f"wu{i:03d}.cpt"),
                window=WINDOW,
                batch_size=BATCH,
            )
        )
    return out, bank


def warm_spec_for(args0):
    """The WarmSpec matching what the Sessions will request — geometry
    derived EXACTLY like ``runtime/session.Session.prepare`` so the warm
    step's cache key is the one the first workunit looks up."""
    from boinc_app_eah_brp_tpu.io import read_template_bank, read_workunit
    from boinc_app_eah_brp_tpu.models.search import (
        SearchGeometry,
        lut_step_for_bank,
        lut_tiles_for_bank,
        max_slope_for_bank,
        resident_defers_renorm,
    )
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig
    from boinc_app_eah_brp_tpu.runtime import health
    from boinc_app_eah_brp_tpu.runtime.scheduler import WarmSpec

    bank = read_template_bank(args0.templatebank)
    wu = read_workunit(args0.inputfile)
    cfg = SearchConfig(
        f0=args0.f0, padding=args0.padding, fA=args0.fA,
        window=args0.window, white=args0.white,
    )
    derived = DerivedParams.derive(
        wu.nsamples, float(wu.header["tsample"]), cfg
    )
    geom = SearchGeometry.from_derived(
        derived,
        use_lut=args0.use_lut,
        max_slope=max_slope_for_bank(bank.P, bank.tau),
        lut_step=lut_step_for_bank(bank.P, derived.dt),
        lut_tiles=lut_tiles_for_bank(
            bank.P, bank.psi0, derived.n_unpadded, derived.dt
        ),
        exact_mean=not cfg.white,
    )
    # mirror Session.prepare's deferred-renorm flip: with the resident
    # chain gated on, whitening ships the series unscaled and the step
    # bakes the sqrt(nsamples) fold, which changes the cache key
    if cfg.white and resident_defers_renorm(geom):
        import dataclasses

        geom = dataclasses.replace(geom, ts_prescaled=False)
    return WarmSpec(
        geom=geom,
        batch_size=BATCH,
        with_health=health.watchdog() is not None,
        bank_P=bank.P, bank_tau=bank.tau, bank_psi0=bank.psi0,
    )


def run_reference(args, env_base: dict) -> bytes:
    """The classic one-process-per-WU path: a REAL driver subprocess,
    same env pins — the byte-identity oracle for ``--verify``."""
    out = args.outputfile + ".ref"
    cmd = [
        sys.executable, "-m", "boinc_app_eah_brp_tpu",
        "-i", args.inputfile, "-o", out, "-t", args.templatebank,
        "-c", args.checkpointfile + ".ref",
        "-B", str(args.window), "--batch", str(args.batch_size),
    ]
    r = subprocess.run(cmd, env=env_base, capture_output=True, text=True,
                       timeout=600)
    if r.returncode != 0:
        sys.stderr.write(r.stderr[-4000:])
        raise RuntimeError(f"reference driver exited {r.returncode}")
    with open(out, "rb") as f:
        return f.read()


def scrape_introspection(server) -> dict | None:
    """One mid-run scrape of the live introspection plane
    (``serving/introspect.py``): pull ``/metrics`` and ``/healthz`` off
    the loopback endpoint while the queue is still draining, prove the
    body parses as Prometheus text, and return the scoreboard row
    (None when introspection is disarmed)."""
    intro = getattr(server, "introspect", None)
    if intro is None or not getattr(intro, "armed", False):
        return None
    import urllib.error
    import urllib.request

    from boinc_app_eah_brp_tpu.serving.introspect import parse_prometheus

    t0 = time.monotonic()
    with urllib.request.urlopen(intro.url("/metrics"), timeout=10) as r:
        body = r.read().decode("utf-8")
    samples = parse_prometheus(body)
    try:
        with urllib.request.urlopen(intro.url("/healthz"), timeout=10) as r:
            healthz = r.status
    except urllib.error.HTTPError as e:
        healthz = e.code  # 503 = SLO burning; recorded, not fatal
    return {
        "port": intro.port,
        "scrape_ms": round((time.monotonic() - t0) * 1e3, 3),
        "metrics_samples": len(samples),
        "healthz_status": healthz,
    }


def check_baseline(stats: dict, base_path: str) -> list[str]:
    """Floor violations versus FLEET_SERVING_BASELINE.json (empty =
    green).  Mirrors ``tools/bench_history.py::load_serving_row``."""
    with open(base_path, encoding="utf-8") as f:
        base = json.load(f)
    if base.get("schema") != BASELINE_SCHEMA:
        return [f"{base_path} is not a {BASELINE_SCHEMA} document"]
    bad = []
    floor = base.get("wus_per_hour_per_chip_min")
    if floor is not None and stats["wus_per_hour_per_chip"] < floor:
        bad.append(
            f"wus_per_hour_per_chip {stats['wus_per_hour_per_chip']} "
            f"below floor {floor}"
        )
    rmax = base.get("recompiles_after_warmup_max")
    if rmax is not None and stats["recompiles_after_warmup"] > rmax:
        bad.append(
            f"recompiles_after_warmup {stats['recompiles_after_warmup']} "
            f"exceeds {rmax}"
        )
    gmax = base.get("p95_inter_wu_gap_s_max")
    if gmax is not None and stats["p95_inter_wu_gap_s"] > gmax:
        bad.append(
            f"p95_inter_wu_gap_s {stats['p95_inter_wu_gap_s']} "
            f"exceeds {gmax}"
        )
    return bad


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Fleet serving bench: WUs/hour/chip at zero "
        "recompiles after warmup (chip-free)."
    )
    ap.add_argument("--wus", type=int, default=4,
                    help="same-geometry workunits to stream (default 4)")
    ap.add_argument("--verify", action="store_true",
                    help="byte-compare every server result against the "
                         "one-process-per-WU driver path")
    ap.add_argument("--check", action="store_true",
                    help="gate the scoreboard against "
                         "FLEET_SERVING_BASELINE.json")
    ap.add_argument("--baseline",
                    default=os.path.join(REPO, "FLEET_SERVING_BASELINE.json"))
    ap.add_argument("--json",
                    default=os.path.join(REPO, ".erp_cache",
                                         "fleet_bench_ci.json"),
                    help="scoreboard cache for bench_history --strict "
                         "(empty string disables)")
    ap.add_argument("--no-warm", action="store_true",
                    help="skip the explicit Scheduler.warm (WU 1 then "
                         "counts as the warmup)")
    ap.add_argument("--no-steptime", action="store_true",
                    help="run without the measured step-time bracket and "
                         "the SLO heartbeat (they are ON by default: the "
                         "bench doubles as the proof that telemetry has "
                         "zero numeric effect)")
    ap.add_argument("--workdir", help="reuse this dir instead of a tmp one")
    ap.add_argument("--keep", action="store_true",
                    help="keep the workdir (default: removed when green)")
    args = ap.parse_args(argv)

    if args.wus < 3:
        return fail("--wus must be >= 3 (warmup + at least two resident WUs)")

    # chip-free by default, and deterministic result headers so the
    # server and per-WU paths can be byte-compared
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["ERP_RESULT_DATE"] = RESULT_DATE
    work = args.workdir or tempfile.mkdtemp(prefix="erp-fleet-bench-")
    os.makedirs(work, exist_ok=True)
    # measured-time observatory ON by default (runtime/steptime.py +
    # serving/slo.py): the byte-identity and zero-recompile gates below
    # then double as proof that measuring is free of numeric effect, and
    # the scoreboard carries measured step-latency percentiles for
    # bench_history --strict
    steptime_on = not args.no_steptime
    slo_path = None
    if steptime_on:
        os.environ.setdefault("ERP_STEPTIME", "1")
        # an explicit ERP_STEPTIME=0 in the caller's env wins
        steptime_on = os.environ["ERP_STEPTIME"].strip().lower() not in (
            "", "0", "false", "no", "off"
        )
    if steptime_on:
        os.environ.setdefault(
            "ERP_SLO_FILE", os.path.join(work, "serving_slo.jsonl")
        )
        os.environ.setdefault("ERP_SLO_INTERVAL", "0.5")
        slo_path = os.environ["ERP_SLO_FILE"]
    # live introspection plane ON by default (port 0 = ephemeral,
    # loopback-only): the mid-run scrape below plus the byte-identity /
    # zero-recompile gates prove serving with /metrics + /healthz armed
    # changes nothing. An explicit empty ERP_STATUSZ_PORT disarms it.
    os.environ.setdefault("ERP_STATUSZ_PORT", "0")
    print(f"fleet-bench: workdir {work}")

    from boinc_app_eah_brp_tpu.runtime import metrics as erp_metrics
    from boinc_app_eah_brp_tpu.serving import FleetServer

    # in-memory metrics (bench.py's mode) so the /metrics scrape sees a
    # live registry — a real deployment arms ERP_METRICS_FILE instead
    if not erp_metrics.enabled():
        erp_metrics.configure(force=True)

    wus, _bank = build_workunits(work, args.wus)
    specs = None if args.no_warm else [warm_spec_for(wus[0])]

    t0 = time.monotonic()
    server = FleetServer(warm_specs=specs, name="bench")
    warm_s = time.monotonic() - t0
    if specs:
        print(
            f"fleet-bench: warm {server.warm_report} in {warm_s:.1f}s"
        )
    tickets = [
        server.submit(a, corr_id=f"bench-{i}") for i, a in enumerate(wus)
    ]
    # one scrape while the queue is live: /metrics must parse as
    # Prometheus text and /healthz must answer; latency lands on the
    # scoreboard so a regression in the read path shows up in CI
    try:
        introspection = scrape_introspection(server)
    except Exception as e:  # noqa: BLE001 - any scrape failure is a gate
        server.close()
        return fail(f"introspection scrape failed: {e!r}")
    if introspection is not None:
        if introspection["metrics_samples"] == 0:
            server.close()
            return fail("/metrics scrape parsed to zero samples")
        print(
            f"fleet-bench: statusz :{introspection['port']} scraped in "
            f"{introspection['scrape_ms']:.1f}ms "
            f"({introspection['metrics_samples']} samples, "
            f"healthz {introspection['healthz_status']})"
        )
    results = [server.result(t, timeout=600) for t in tickets]
    stats = server.stats()
    server.close()

    for i, r in enumerate(results):
        print(
            f"fleet-bench: wu{i:03d} code={r.code} "
            f"recompiles={r.recompiles} wall={r.wall_s:.2f}s "
            f"prep={r.prepare_s:.2f}s"
        )
    bad_codes = [r for r in results if not r.ok]
    if bad_codes:
        return fail(
            f"{len(bad_codes)} session(s) failed: "
            + ", ".join(f"{r.name}:{r.code}" for r in bad_codes)
        )
    print(f"fleet-bench: {json.dumps(stats)}")

    verified = None
    if args.verify:
        env_base = dict(os.environ)
        env_base["PYTHONPATH"] = (
            REPO + os.pathsep + env_base.get("PYTHONPATH", "")
        )
        t0 = time.monotonic()
        for i, (a, r) in enumerate(zip(wus, results)):
            ref = run_reference(a, env_base)
            with open(r.outputfile, "rb") as f:
                got = f.read()
            if got != ref:
                return fail(
                    f"wu{i:03d}: server result differs from the "
                    f"one-process-per-WU driver (bytes {len(got)} vs "
                    f"{len(ref)})"
                )
        verified = len(wus)
        print(
            f"fleet-bench: all {verified} server results byte-identical "
            f"to the per-WU driver path "
            f"({time.monotonic() - t0:.1f}s of references)"
        )

    # the headline gate, baseline or not: a resident server NEVER
    # recompiles a same-geometry stream after warmup
    if stats["recompiles_after_warmup"] != 0:
        return fail(
            f"recompiles_after_warmup = "
            f"{stats['recompiles_after_warmup']} (must be 0)"
        )

    import jax

    backend = jax.default_backend()
    step_latency = None
    slo_heartbeats = None
    if steptime_on:
        from boinc_app_eah_brp_tpu.runtime import steptime

        step_latency = steptime.summary()
        if step_latency["windows"] == 0:
            return fail(
                "ERP_STEPTIME=1 but no measured step windows recorded"
            )
        print(
            f"fleet-bench: measured step latency "
            f"{json.dumps(step_latency['step_ms'])} over "
            f"{step_latency['windows']} windows ({backend})"
        )
        # the SLO stream must hold >= 1 valid heartbeat; metrics_report
        # --check is the same validator make test applies to every
        # other artifact
        if not slo_path or not os.path.exists(slo_path):
            return fail("no erp-serving-slo/1 heartbeat stream written")
        import metrics_report

        if metrics_report.main(["--check", slo_path]) != 0:
            return fail(
                f"SLO heartbeat stream {slo_path} failed "
                "metrics_report --check"
            )
        with open(slo_path, encoding="utf-8") as f:
            slo_heartbeats = sum(1 for ln in f if ln.strip())
        if slo_heartbeats < 1:
            return fail("no erp-serving-slo/1 heartbeat emitted")
        print(f"fleet-bench: {slo_heartbeats} SLO heartbeat(s) validated")

    doc = {
        "schema": SCHEMA,
        "wus": args.wus,
        "warmed": not args.no_warm,
        "warm_wall_s": round(warm_s, 3),
        "verified_byte_identical": verified,
        "backend": backend,
        "step_latency": step_latency,
        "slo_heartbeats": slo_heartbeats,
        "introspection": introspection,
        "stats": stats,
    }
    if args.json:
        os.makedirs(os.path.dirname(args.json), exist_ok=True)
        tmp = f"{args.json}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, args.json)
        print(f"fleet-bench: scoreboard cached at {args.json}")

    if args.check:
        try:
            violations = check_baseline(stats, args.baseline)
        except (OSError, ValueError) as e:
            return fail(f"cannot read baseline {args.baseline}: {e}")
        if violations:
            return fail(
                "baseline violations: " + "; ".join(violations)
            )
        print(
            f"fleet-bench: within {os.path.basename(args.baseline)} floors"
        )

    if not args.keep and not args.workdir:
        shutil.rmtree(work, ignore_errors=True)
    print(
        f"fleet-bench: PASS ({args.wus} WUs, "
        f"{stats['wus_per_hour_per_chip']} WUs/hour/chip, "
        f"0 recompiles after warmup)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
