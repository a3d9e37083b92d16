"""Adversarial volunteer-fabric soak: the zero-false-grants gate.

Drives the work-fabric simulator (``fabric/workfabric.py``) with a large
fleet of concurrent volunteer streams — honest hosts plus every
adversary model ``fabric/hosts.py`` knows (bit-flipped powers, reordered
rows, stale template-bank epochs, echoed result files, deadline stalls,
forged quarantine gaps) — and proves the control plane holds the line:

* **zero false grants** — every granted workunit's candidate section is
  byte-identical to the single-process reference result the real driver
  computed for that payload, and no host's lied report was ever the
  winning replica;
* **zero starvation** — every workunit reaches GRANTED despite the
  adversaries (nothing FAILED, nothing PENDING at exit);
* **every adversary kind detected** — each misbehaving replica is
  rejected with a named reason (``fabric.reject.*`` counters) and the
  host demoted; stall hosts show up as timeouts;
* **bounded re-issue overhead** — replicas issued stay under
  ``--overhead`` x the quorum-minimum (an adversary can waste work, but
  only linearly);
* **auditable** — every validation round's signed ``erp-quorum/1``
  verdict artifact passes ``metrics_report.py --check``, as does the
  soak's own metrics run report; the per-WU lifecycle export
  (``erp-wu-lifecycle/1``) and signed verdicts are then rolled up into
  an ``erp-fleet-report/1`` (``tools/fleet_report.py`` — grant/
  validation-latency percentiles, re-issue overhead, per-adversary
  detection counts) which is SLO-gated against the committed
  ``FLEET_BASELINE.json`` and cached at
  ``.erp_cache/fleet_report_ci.json`` for ``bench_history --strict``.

Environmental corruption is layered ON TOP of the deliberate
adversaries: the soak arms ``result_report:corrupt`` (honest hosts'
payloads mutated in flight) and ``validate:exc`` (the validator itself
crashing transiently, recovered by the scheduler's bounded
``RetryPolicy``) through ``runtime/faultinject.py``.

Reference results come from REAL driver subprocesses (one per payload
class, forced-CPU, shared compile cache, pinned ``ERP_RESULT_DATE``), so
the byte-identity assertion is against the actual pipeline, not a
synthetic fixture.  Chip-free; run it anywhere.

Usage:
    python tools/fabric_soak.py                  # 64 streams (make fabric-soak)
    python tools/fabric_soak.py --streams 256    # acceptance-scale soak
    python tools/fabric_soak.py --keep --workdir DIR
"""

from __future__ import annotations

import argparse
import glob
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

RESULT_DATE = "2008-11-12T00:00:00+00:00"

# padded observation time of the 4096-sample / 500 us synthetic workunits
# below (freq = f0_bin / t_obs; oracle/pipeline.py derives it from the
# padded sample count, and 4096 is already a power of two) — the
# validator needs it to reconstruct exact frequency-bin identities
T_OBS = 4096 * 500.0e-6


def fail(msg: str) -> int:
    print(f"fabric-soak: FAIL: {msg}", file=sys.stderr)
    return 1


def build_reference(work: str, name: str, *, f_signal: float, seed_amp: float,
                    env_base: dict, server=None) -> bytes:
    """One payload class: synthesize a workunit + bank, run the real
    driver once, return the reference candidate-file bytes.

    ``server`` (a ``fabric.ServerBackend``, present when
    ``ERP_FABRIC_BACKEND=server``) routes the run through the resident
    in-process serving tier instead of a driver subprocess; the
    correlation id then flows through the Session's scoped ObsContext
    rather than the ``ERP_CORR_ID`` env."""
    from fixtures import small_bank, synthetic_timeseries

    from boinc_app_eah_brp_tpu.io import write_template_bank, write_workunit

    ts = synthetic_timeseries(
        4096, f_signal=f_signal, P_orb=2.2, tau=0.04, psi0=1.2, amp=seed_amp
    )
    wu = os.path.join(work, f"{name}.bin4")
    write_workunit(wu, ts, tsample_us=500.0, scale=1.0, dm=55.5)
    bank = os.path.join(work, f"{name}.bank.dat")
    write_template_bank(
        bank, small_bank(P_true=2.2, tau_true=0.04, psi_true=1.2)
    )
    out = os.path.join(work, f"{name}.ref.cand")
    cp = os.path.join(work, f"{name}.cpt")
    if server is not None:
        from boinc_app_eah_brp_tpu.runtime.driver import DriverArgs

        return server.compute(
            DriverArgs(
                inputfile=wu, outputfile=out, templatebank=bank,
                checkpointfile=cp, window=200, batch_size=2,
            ),
            corr_id=f"ref-{name}",
        )
    env = dict(env_base)
    # reference runs carry a correlation id too, so their flight-recorder
    # context / metrics run report stitch into the same fleet timeline as
    # the fabric's replica lanes (runtime/metrics.py CORR_ID_ENV)
    env["ERP_CORR_ID"] = f"ref-{name}"
    cmd = [
        sys.executable, "-m", "boinc_app_eah_brp_tpu",
        "-i", wu, "-o", out, "-t", bank, "-c", cp,
        "-B", "200", "--batch", "2",
    ]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=600)
    if r.returncode != 0:
        sys.stderr.write(r.stderr[-4000:])
        raise RuntimeError(f"reference driver for {name} exited {r.returncode}")
    with open(out, "rb") as f:
        return f.read()


def build_fleet(streams: int, seed: int):
    """Host fleet: ~2/3 honest, the rest cycling every adversary kind
    (each kind present at least twice once streams >= 20)."""
    from boinc_app_eah_brp_tpu import fabric as fb

    kinds = []
    n_adv = max(len(fb.ADVERSARY_KINDS), streams // 3)
    for i in range(streams):
        if i < streams - n_adv:
            kinds.append("honest")
        else:
            kinds.append(fb.ADVERSARY_KINDS[i % len(fb.ADVERSARY_KINDS)])
    return [
        fb.HostModel(host_id=i + 1, kind=k, seed=seed, date_iso=RESULT_DATE)
        for i, k in enumerate(kinds)
    ]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Adversarial volunteer-fabric soak (chip-free)."
    )
    ap.add_argument("--streams", type=int, default=64,
                    help="concurrent volunteer streams (default 64)")
    ap.add_argument("--wus", type=int, default=0,
                    help="workunits (default: streams // 2, min 16)")
    ap.add_argument("--overhead", type=float, default=4.0,
                    help="max replicas-issued / (wus * quorum) ratio")
    ap.add_argument("--deadline", type=float, default=3.0,
                    help="per-assignment report deadline (s)")
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="whole-soak convergence timeout (s)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", help="reuse this dir instead of a tmp one")
    ap.add_argument("--keep", action="store_true",
                    help="keep the workdir (default: removed when green)")
    args = ap.parse_args(argv)

    n_wus = args.wus or max(16, args.streams // 2)
    work = args.workdir or tempfile.mkdtemp(prefix="erp-fabric-")
    os.makedirs(work, exist_ok=True)
    print(f"fabric-soak: workdir {work}")

    env_base = dict(os.environ)
    env_base.update(
        {
            "JAX_PLATFORMS": "cpu",
            "ERP_RESULT_DATE": RESULT_DATE,
            "PYTHONPATH": REPO + os.pathsep + env_base.get("PYTHONPATH", ""),
        }
    )
    # verdict artifacts are signed with a REAL per-run key, never the
    # forgeable dev fallback, so the phase-3 --check gate is
    # authoritative (a dev-signed artifact would be flagged)
    quorum_key = os.environ.get("ERP_QUORUM_KEY") or (
        f"fabric-soak-{os.urandom(8).hex()}"
    )
    os.environ["ERP_QUORUM_KEY"] = quorum_key
    env_base["ERP_QUORUM_KEY"] = quorum_key

    # --- phase 1: references through the real pipeline — one driver
    # subprocess per payload class, or (ERP_FABRIC_BACKEND=server) the
    # in-process fleet serving tier
    from boinc_app_eah_brp_tpu import fabric as fb

    backend = fb.compute_backend()
    server = None
    if backend == "server":
        # the serving tier runs in THIS process: pin the chip-free env
        # before anything imports jax
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ["ERP_RESULT_DATE"] = RESULT_DATE
        server = fb.ServerBackend(name="fabric-ref")
        print("fabric-soak: compute backend = server (in-process fleet tier)")
    t0 = time.monotonic()
    try:
        refs = {
            "A": build_reference(work, "payloadA", f_signal=33.0,
                                 seed_amp=7.0, env_base=env_base,
                                 server=server),
            "B": build_reference(work, "payloadB", f_signal=41.0,
                                 seed_amp=6.0, env_base=env_base,
                                 server=server),
        }
    finally:
        if server is not None:
            srv_stats = server.stats()
            server.close()
            print(f"fabric-soak: server backend {json.dumps(srv_stats)}")
    # the stale adversary reports a plausible-but-wrong toplist with an
    # old epoch claim: the OTHER payload's reference is exactly that
    stale = {"A": refs["B"], "B": refs["A"]}
    print(
        f"fabric-soak: references built in {time.monotonic() - t0:.1f}s "
        f"({', '.join(f'{k}:{len(v)}B' for k, v in sorted(refs.items()))})"
    )

    # --- phase 2: the fabric run, with environmental faults armed
    os.environ["ERP_RESULT_DATE"] = RESULT_DATE
    from boinc_app_eah_brp_tpu.io.results import split_result_sections
    from boinc_app_eah_brp_tpu.runtime import faultinject, metrics

    metrics_file = os.path.join(work, "fabric-metrics.jsonl")
    metrics.configure(metrics_file=metrics_file, interval=0)
    faultinject.configure(
        f"result_report:corrupt@p=0.02;validate:exc@n=3;seed={args.seed + 7}"
    )

    cfg = fb.FabricConfig(
        t_obs=T_OBS,
        seed=args.seed,
        deadline_s=args.deadline,
        trust_after=3,
        spot_check_rate=0.1,
        spool_dir="spool",
        verdict_dir="verdicts",
        granted_dir="granted",
    )
    wus = [
        fb.WorkUnit(
            wu_id=f"wu{i:04d}", payload="AB"[i % 2], epoch=cfg.bank_epoch,
            target=cfg.quorum,
        )
        for i in range(n_wus)
    ]
    hosts = build_fleet(args.streams, args.seed)
    n_adv = sum(1 for h in hosts if h.kind != "honest")
    print(
        f"fabric-soak: {args.streams} streams ({n_adv} adversarial: "
        f"{', '.join(fb.ADVERSARY_KINDS)}), {n_wus} workunits, "
        f"quorum {cfg.quorum}"
    )
    fabric = fb.Fabric(cfg, wus, refs, work)
    converged = fb.run_streams(
        fabric, hosts, stale_references=stale, timeout_s=args.timeout
    )
    summary = fabric.summary()
    report = metrics.finish("ok")
    faultinject.configure(None)
    print(f"fabric-soak: {json.dumps(summary)}")

    # --- phase 3: the gates
    if not converged:
        return fail(f"fabric did not converge within {args.timeout}s")
    if summary["failed"] or summary["pending"]:
        return fail(
            f"starvation: {summary['failed']} failed, "
            f"{summary['pending']} pending of {n_wus}"
        )
    if summary["granted"] != n_wus:
        return fail(f"only {summary['granted']}/{n_wus} granted")

    # zero false grants: granted candidate sections byte-identical to the
    # single-process references
    ref_sections = {
        k: split_result_sections(v.decode("utf-8"))[1]
        for k, v in refs.items()
    }
    for wu in fabric.granted():
        with open(wu.granted_path, "rb") as f:
            _, got, done = split_result_sections(f.read().decode("utf-8"))
        if not done or got != ref_sections[wu.payload]:
            return fail(
                f"{wu.wu_id}: granted candidates differ from the "
                f"single-process reference (payload {wu.payload})"
            )
    print(f"fabric-soak: all {n_wus} granted toplists byte-identical "
          f"to references")

    # no lied report was the granted winner
    lied_by_host = {h.host_id: h.lied_wus() for h in hosts}
    reps = fabric.reputation_snapshot()
    for wu in fabric.granted():
        winners = [
            a.host_id
            for a in wu.assignments
            if a.state == "valid"
        ]
        for host_id in winners:
            if wu.wu_id in lied_by_host.get(host_id, set()):
                return fail(
                    f"{wu.wu_id}: lying host {host_id} was credited valid"
                )

    # every adversary that actually lied must have been caught
    counters = (report.get("metrics") or {}).get("counters") or {}

    def cval(name: str) -> float:
        return float((counters.get(name) or {}).get("value", 0.0))

    uncaught = []
    for h in hosts:
        if h.kind == "honest":
            continue
        lied = h.lied_wus()
        if not lied:
            continue  # p_lie lottery never fired / no eligible WU
        rep = reps.get(h.host_id)
        caught = rep is not None and (rep.total_invalid or rep.total_timeout)
        if not caught:
            uncaught.append((h.host_id, h.kind, sorted(lied)[:3]))
    if uncaught:
        return fail(f"adversaries never caught: {uncaught}")
    detected = cval("fabric.adversary_detected")
    timeouts = cval("fabric.timeouts")
    reject_tags = sorted(
        n.split("fabric.reject.", 1)[1]
        for n in counters
        if n.startswith("fabric.reject.")
    )
    print(
        f"fabric-soak: {detected:.0f} bad replicas rejected, "
        f"{timeouts:.0f} timeouts; reject reasons: {', '.join(reject_tags)}"
    )
    if n_adv and not (detected or timeouts):
        return fail("adversaries present but nothing was ever rejected")

    # bounded re-issue overhead
    floor = n_wus * cfg.quorum
    ratio = summary["replicas_issued"] / max(1, floor)
    if ratio > args.overhead:
        return fail(
            f"re-issue overhead {ratio:.2f}x exceeds {args.overhead:.1f}x "
            f"({summary['replicas_issued']} replicas for a {floor} floor)"
        )
    print(f"fabric-soak: replica overhead {ratio:.2f}x (bound "
          f"{args.overhead:.1f}x)")

    # fleet rollup: lifecycle export + signed verdicts + metrics stream
    # -> erp-fleet-report/1 (tools/fleet_report.py), SLO-gated against
    # the committed baseline when one exists
    import fleet_report as fleet_mod

    lifecycle_path = os.path.join(work, "fabric-lifecycle.json")
    fabric.export_lifecycle(lifecycle_path)
    fleet_doc = fleet_mod.build_report(
        lifecycle_path, os.path.join(work, "verdicts"),
        metrics_path=metrics_file,
    )
    fleet_errs = fleet_mod.validate_fleet_report(fleet_doc)
    if fleet_errs:
        return fail(f"fleet report invalid: {fleet_errs[:3]}")
    fleet_path = os.path.join(work, "fabric-fleet.json")
    ci_fleet = os.path.join(REPO, ".erp_cache", "fleet_report_ci.json")
    os.makedirs(os.path.dirname(ci_fleet), exist_ok=True)
    for path in (fleet_path, ci_fleet):
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(fleet_doc, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
    baseline_path = os.path.join(REPO, "FLEET_BASELINE.json")
    if os.path.exists(baseline_path):
        with open(baseline_path, encoding="utf-8") as f:
            slo_errs = fleet_mod.evaluate_slo(fleet_doc, json.load(f))
        if slo_errs:
            for e in slo_errs:
                print(f"fabric-soak: {e}", file=sys.stderr)
            return fail("fleet report violates FLEET_BASELINE.json SLOs")
        print("fabric-soak: fleet report within FLEET_BASELINE.json SLOs")
    print(fleet_mod.render(fleet_doc))

    # every verdict artifact + the run report + the fleet rollup must
    # pass --check
    verdicts = sorted(glob.glob(os.path.join(work, "verdicts", "*.quorum.json")))
    if not verdicts:
        return fail("no erp-quorum/1 verdict artifacts written")
    check = verdicts + [metrics_file, fleet_path]
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metrics_report.py"),
         "--check", *check],
        env=env_base, capture_output=True, text=True,
    )
    if rc.returncode != 0:
        sys.stderr.write(rc.stdout[-3000:])
        return fail("verdict/metrics artifacts failed --check")
    print(f"fabric-soak: {len(verdicts)} signed verdicts + run report "
          f"pass --check")

    print(
        f"fabric-soak: PASS ({args.streams} streams, {n_wus} WUs, "
        f"{summary['quorum1_grants']} quorum-1 grants, "
        f"{summary['hosts_demoted']} hosts demoted, 0 false grants)"
    )
    if not args.keep and args.workdir is None:
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
