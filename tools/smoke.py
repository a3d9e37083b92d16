"""Observability smoke: a tiny end-to-end run with every telemetry layer
on, then schema-check everything it leaves behind.

The fast CI gate (``make smoke``): generates a synthetic workunit and a
small template bank, runs the real driver subprocess with the health
watchdog at maximum cadence (``ERP_HEALTH_EVERY=1``), structured metrics
(``--metrics-file``) and the flight recorder armed, then verifies

* the driver exited 0 and wrote a parseable candidate file,
* the metrics run report validates (``metrics_report.py --check``),
* the host span trace (``ERP_TRACE_FILE``) and its Chrome export
  validate, and ``trace_report.py`` attributes >= 95% of the run wall
  to named spans,
* the checkpoint audit sidecar exists and verifies against the
  checkpoint bytes,
* the watchdog ran (health.checks > 0) with zero violations, and
* NO black-box dump appeared (a dump on a clean run is itself a bug).

With ``--hosts N`` it instead runs the multi-host elastic gate: N real
driver subprocesses, each a forced-4-device CPU "host"
(``--xla_force_host_platform_device_count=4`` via ``ERP_LOCAL_DEVICES``),
sharding one bank over a shared lease board.  All hosts must exit 0, the
merge winner must write a parseable result plus an audit sidecar whose
topology record names the process count, every lease (including the
merge pseudo-shard) must be complete, and a CLEAN run must record ZERO
``resilience.rebalance`` events — a false adoption is a heartbeat bug.
``make chaos-hosts`` covers the host-kill half of the story.

With ``--fabric`` it runs the clean volunteer-fabric gate instead: one
real driver run builds the reference result, then 8 honest volunteer
streams push 8 workunits through the quorum scheduler
(``fabric/workfabric.py``).  Every workunit must grant with candidate
sections byte-identical to the reference, ZERO replicas may be rejected
and ZERO re-issues may happen (a flag on an all-honest fleet is a
validator false positive), and every signed ``erp-quorum/1`` verdict
must pass ``metrics_report.py --check``.  The adversarial half lives in
``make fabric-soak`` (``tools/fabric_soak.py``).

Usage:
    python tools/smoke.py [--keep] [--workdir DIR] [--hosts N] [--fabric]

Exit code 0 = all green.  Runs on the CPU backend in ~a minute; no
accelerator required.
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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))


def fail(msg: str) -> int:
    print(f"smoke: FAIL: {msg}", file=sys.stderr)
    return 1


def check_precision_artifacts() -> str | None:
    """Precision-observatory artifact gate (layer 12): the committed
    PRECISION_BASELINE.json must exist and validate, and the cached
    audit artifact — when the precision-audit gate has run — must carry
    a valid erp-precision-audit/1 schema.  Returns an error string or
    None (chip-free, pure schema checks)."""
    from boinc_app_eah_brp_tpu.runtime.precision import (
        validate_precision_audit,
        validate_precision_baseline,
    )

    base_path = os.path.join(REPO, "PRECISION_BASELINE.json")
    if not os.path.exists(base_path):
        return "no committed PRECISION_BASELINE.json"
    try:
        with open(base_path, encoding="utf-8") as f:
            base = json.load(f)
    except (OSError, ValueError) as e:
        return f"PRECISION_BASELINE.json unreadable: {e}"
    errs = validate_precision_baseline(base)
    if errs:
        return f"PRECISION_BASELINE.json invalid: {'; '.join(errs)}"
    audit_cache = os.path.join(REPO, ".erp_cache", "precision_audit_ci.json")
    if os.path.exists(audit_cache):
        try:
            with open(audit_cache, encoding="utf-8") as f:
                audit = json.load(f)
        except (OSError, ValueError) as e:
            return f"{audit_cache} unreadable: {e}"
        errs = validate_precision_audit(audit)
        if errs:
            return f"{audit_cache} invalid: {'; '.join(errs)}"
        print("smoke: precision artifacts OK (baseline + cached audit)")
    else:
        print("smoke: precision artifacts OK (baseline; no cached audit)")
    return None


def _report_counter(metrics_path: str, name: str) -> float:
    """Counter value from the run report riding a metrics JSONL stream."""
    value = 0.0
    for line in open(metrics_path):
        try:
            doc = json.loads(line)
        except json.JSONDecodeError:
            continue
        report = doc.get("report") if isinstance(doc.get("report"), dict) else doc
        if isinstance(report, dict) and report.get("schema") == "erp-run-report/1":
            c = (report.get("metrics") or {}).get("counters") or {}
            value = float((c.get(name) or {}).get("value", 0.0))
    return value


def run_hosts_smoke(args, work: str) -> int:
    """Clean multi-host elastic gate (no kill — ``make chaos-hosts`` does
    that): N uncoordinated driver processes over one shard board."""
    from fixtures import small_bank, synthetic_timeseries

    from boinc_app_eah_brp_tpu.io import (
        parse_result_file,
        write_template_bank,
        write_workunit,
    )
    from boinc_app_eah_brp_tpu.io.checkpoint import audit_path
    from boinc_app_eah_brp_tpu.runtime.resilience import LeaseBoard, MERGE_SHARD

    hosts = args.hosts
    ts = synthetic_timeseries(
        4096, f_signal=33.0, P_orb=2.2, tau=0.04, psi0=1.2, amp=7.0
    )
    wu = os.path.join(work, "smoke.bin4")
    write_workunit(wu, ts, tsample_us=500.0, scale=1.0, dm=55.5)
    bank = os.path.join(work, "bank.dat")
    write_template_bank(
        bank, small_bank(P_true=2.2, tau_true=0.04, psi_true=1.2)
    )
    out = os.path.join(work, "results.cand")
    cp = os.path.join(work, "checkpoint.cpt")
    shard_dir = os.path.join(work, "shards")

    procs = []
    for i in range(hosts):
        env = dict(os.environ)
        env.update(
            {
                "JAX_PLATFORMS": "cpu",
                "ERP_NUM_PROCESSES": str(hosts),
                "ERP_PROCESS_ID": str(i),
                "ERP_LOCAL_DEVICES": "4",  # forced 4-device CPU platform
                "ERP_SHARD_DIR": shard_dir,
                "ERP_METRICS_FILE": os.path.join(
                    work, f"metrics-host{i}.jsonl"
                ),
                "ERP_BLACKBOX_DIR": work,
                "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
            }
        )
        cmd = [
            sys.executable, "-m", "boinc_app_eah_brp_tpu",
            "-i", wu, "-o", out, "-t", bank, "-c", cp,
            "-B", "200", "--batch", "2",
            "--metrics-file", env["ERP_METRICS_FILE"],
        ]
        procs.append(subprocess.Popen(
            cmd, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE, text=True,
        ))
    print(f"smoke: {hosts} elastic hosts launched (4 CPU devices each)")
    for i, p in enumerate(procs):
        try:
            _, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            return fail(f"host {i} did not finish within 600s")
        if p.returncode != 0:
            sys.stderr.write((err or "")[-4000:])
            return fail(f"host {i} exited {p.returncode}")
    print(f"smoke: all {hosts} hosts exited 0")

    if not os.path.exists(out):
        return fail("no candidate file written by the merge winner")
    if not parse_result_file(out).done:
        return fail("result file is not marked DONE")

    board = LeaseBoard(shard_dir, "smoke-checker")
    for shard in list(range(hosts)) + [MERGE_SHARD]:
        lease = board.read_lease(shard)
        if lease is None or not lease.complete:
            return fail(f"lease {shard} incomplete after a clean run")
    print("smoke: every shard lease (and the merge) is complete")

    audit = json.load(open(audit_path(cp)))
    topo = audit.get("topology") or {}
    if topo.get("process_count") != hosts:
        return fail(
            f"audit topology records process_count="
            f"{topo.get('process_count')}, expected {hosts}"
        )

    shards_run = rebalances = 0.0
    for i in range(hosts):
        mpath = os.path.join(work, f"metrics-host{i}.jsonl")
        shards_run += _report_counter(mpath, "elastic.shards_run")
        rebalances += _report_counter(mpath, "resilience.rebalance")
    if shards_run < hosts:
        return fail(
            f"only {shards_run:.0f} shards ran across {hosts} hosts"
        )
    if rebalances:
        return fail(
            f"{rebalances:.0f} rebalance(s) on a CLEAN run — a live "
            f"host's heartbeat was mistaken for a dead one"
        )
    err = check_precision_artifacts()
    if err:
        return fail(err)

    print(
        f"smoke: PASS ({hosts} hosts, {shards_run:.0f} shards, topology "
        f"audit OK, 0 spurious rebalances)"
    )
    return 0


def run_fabric_smoke(args, work: str) -> int:
    """Clean volunteer-fabric gate: 8 honest streams over one driver
    reference.  Everything must grant, NOTHING may be flagged — a
    rejection or re-issue with zero adversaries is a validator or
    scheduler bug (``make fabric-soak`` covers the adversarial half)."""
    from fixtures import small_bank, synthetic_timeseries

    from boinc_app_eah_brp_tpu.io import write_template_bank, write_workunit

    date = "2008-11-12T00:00:00+00:00"
    ts = synthetic_timeseries(
        4096, f_signal=33.0, P_orb=2.2, tau=0.04, psi0=1.2, amp=7.0
    )
    wu = os.path.join(work, "smoke.bin4")
    write_workunit(wu, ts, tsample_us=500.0, scale=1.0, dm=55.5)
    bank = os.path.join(work, "bank.dat")
    write_template_bank(
        bank, small_bank(P_true=2.2, tau_true=0.04, psi_true=1.2)
    )
    ref = os.path.join(work, "reference.cand")
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "ERP_RESULT_DATE": date,
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        }
    )
    # sign verdicts with a real per-run key so the --check gate below is
    # authoritative (dev-fallback-signed artifacts are forgeable)
    quorum_key = os.environ.get("ERP_QUORUM_KEY") or (
        f"fabric-smoke-{os.urandom(8).hex()}"
    )
    os.environ["ERP_QUORUM_KEY"] = quorum_key
    env["ERP_QUORUM_KEY"] = quorum_key
    cmd = [
        sys.executable, "-m", "boinc_app_eah_brp_tpu",
        "-i", wu, "-o", ref, "-t", bank,
        "-c", os.path.join(work, "ref.cpt"), "-B", "200", "--batch", "2",
    ]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=600)
    if r.returncode != 0:
        sys.stderr.write(r.stderr[-4000:])
        return fail(f"reference driver exited {r.returncode}")
    with open(ref, "rb") as f:
        ref_bytes = f.read()
    print(f"smoke: fabric reference built ({len(ref_bytes)} B)")

    from boinc_app_eah_brp_tpu import fabric as fb
    from boinc_app_eah_brp_tpu.io.results import split_result_sections
    from boinc_app_eah_brp_tpu.runtime import metrics

    os.environ["ERP_RESULT_DATE"] = date
    metrics.configure(force=True)
    # padded observation time of the 4096-sample / 500 us workunit above
    # (freq = f0_bin / t_obs; oracle/pipeline.py derives it from the
    # padded sample count, and 4096 is already a power of two)
    t_obs = 4096 * 500.0e-6
    cfg = fb.FabricConfig(
        t_obs=t_obs, seed=1, deadline_s=60.0, spool_dir="spool",
        verdict_dir="verdicts", granted_dir="granted",
    )
    wus = [
        fb.WorkUnit(wu_id=f"wu{i:02d}", payload="ref", epoch=cfg.bank_epoch,
                    target=cfg.quorum)
        for i in range(8)
    ]
    hosts = [
        fb.HostModel(host_id=i + 1, kind="honest", seed=1, date_iso=date)
        for i in range(8)
    ]
    fabric = fb.Fabric(cfg, wus, {"ref": ref_bytes}, work)
    ok = fb.run_streams(fabric, hosts, timeout_s=300.0)
    summary = fabric.summary()
    report = metrics.finish("ok")
    print(f"smoke: fabric {summary}")
    if not ok or summary["granted"] != len(wus):
        return fail(f"fabric granted {summary['granted']}/{len(wus)}")
    counters = (report.get("metrics") or {}).get("counters") or {}
    flagged = float(
        (counters.get("fabric.adversary_detected") or {}).get("value", 0.0)
    )
    if flagged:
        return fail(
            f"{flagged:.0f} replicas rejected on an all-honest run — "
            f"the validator flagged a clean result"
        )
    if summary["reissues"]:
        return fail(
            f"{summary['reissues']} spurious re-issue(s) on a clean run"
        )
    _, ref_lines, _ = split_result_sections(ref_bytes.decode("utf-8"))
    for w in fabric.granted():
        with open(w.granted_path, "rb") as f:
            _, got, done = split_result_sections(f.read().decode("utf-8"))
        if not done or got != ref_lines:
            return fail(f"{w.wu_id}: granted bytes differ from reference")
    verdicts = glob.glob(os.path.join(work, "verdicts", "*.quorum.json"))
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metrics_report.py"),
         "--check", *verdicts],
        env=env, capture_output=True, text=True,
    )
    if rc.returncode != 0:
        sys.stderr.write(rc.stdout[-2000:])
        return fail("fabric verdicts failed --check")
    print(
        f"smoke: PASS (fabric: {len(wus)} WUs granted by 8 honest streams, "
        f"0 rejections, 0 re-issues, {len(verdicts)} verdicts OK)"
    )
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description="Observability smoke test.")
    ap.add_argument("--workdir", help="reuse this dir instead of a tmp one")
    ap.add_argument(
        "--keep", action="store_true",
        help="keep the workdir (default: removed when the run is green)",
    )
    ap.add_argument(
        "--hosts", type=int, default=0,
        help="run the multi-host elastic gate with N emulated hosts "
        "instead of the observability smoke",
    )
    ap.add_argument(
        "--fabric", action="store_true",
        help="run the clean volunteer-fabric gate (8 honest streams, "
        "everything grants, nothing flagged) instead of the "
        "observability smoke",
    )
    args = ap.parse_args(argv)

    from fixtures import small_bank, synthetic_timeseries

    from boinc_app_eah_brp_tpu.io import write_template_bank, write_workunit
    from boinc_app_eah_brp_tpu.io.checkpoint import (
        audit_path,
        read_checkpoint,
        verify_checkpoint_audit,
    )

    work = args.workdir or tempfile.mkdtemp(prefix="erp-smoke-")
    os.makedirs(work, exist_ok=True)
    print(f"smoke: workdir {work}")

    if args.hosts:
        rc = run_hosts_smoke(args, work)
        if rc == 0 and not args.keep and args.workdir is None:
            shutil.rmtree(work, ignore_errors=True)
        return rc

    if args.fabric:
        rc = run_fabric_smoke(args, work)
        if rc == 0 and not args.keep and args.workdir is None:
            shutil.rmtree(work, ignore_errors=True)
        return rc

    ts = synthetic_timeseries(
        4096, f_signal=33.0, P_orb=2.2, tau=0.04, psi0=1.2, amp=7.0
    )
    wu = os.path.join(work, "smoke.bin4")
    write_workunit(wu, ts, tsample_us=500.0, scale=1.0, dm=55.5)
    bank = os.path.join(work, "bank.dat")
    write_template_bank(
        bank, small_bank(P_true=2.2, tau_true=0.04, psi_true=1.2)
    )
    out = os.path.join(work, "results.cand")
    cp = os.path.join(work, "checkpoint.cpt")
    metrics_file = os.path.join(work, "metrics.jsonl")
    trace_file = os.path.join(work, "run.trace.jsonl")

    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": env.get("JAX_PLATFORMS", "cpu"),
            "ERP_COMPILATION_CACHE": "off",
            "ERP_HEALTH_EVERY": "1",
            "ERP_HEALTH_ACTION": "abort",  # a violation must fail the smoke
            "ERP_BLACKBOX_DIR": work,
            "ERP_TRACE_FILE": trace_file,  # host span timeline (layer 7)
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        }
    )
    cmd = [
        sys.executable, "-m", "boinc_app_eah_brp_tpu",
        "-i", wu, "-o", out, "-t", bank, "-c", cp,
        "-B", "200", "--batch", "2", "--metrics-file", metrics_file,
    ]
    print(f"smoke: running {' '.join(cmd)}")
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=600)
    if r.returncode != 0:
        sys.stderr.write(r.stderr[-4000:])
        return fail(f"driver exited {r.returncode}")

    # --- artifacts
    if not os.path.exists(out):
        return fail("no candidate file written")
    from boinc_app_eah_brp_tpu.io import parse_result_file

    parse_result_file(out)  # raises on malformed output

    chrome_file = trace_file + ".chrome.json"
    for p in (trace_file, chrome_file):
        if not os.path.exists(p):
            return fail(f"no trace artifact {p}")

    report_paths = glob.glob(os.path.join(work, "*.report.json"))
    check = [metrics_file, trace_file, chrome_file] + report_paths
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metrics_report.py"),
         "--check", *check],
        env=env, capture_output=True, text=True,
    )
    print(rc.stdout.rstrip())
    if rc.returncode != 0:
        return fail("metrics/trace artifacts failed --check")

    # the stall table must account for (nearly) the whole run wall —
    # an unattributed gap means a pipeline stage lost its span
    tr = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         "--json", trace_file],
        env=env, capture_output=True, text=True,
    )
    if tr.returncode != 0:
        sys.stderr.write(tr.stderr[-2000:])
        return fail("trace_report failed on the trace stream")
    stalls = json.loads(tr.stdout)
    if stalls["coverage"] < 0.95:
        return fail(
            f"trace attributes only {stalls['coverage']:.1%} of the run "
            f"wall (need >= 95%): {stalls['categories']}"
        )
    top = sorted(
        stalls["categories"].items(), key=lambda kv: -kv[1]["self_s"]
    )[:4]
    print(
        f"smoke: trace OK ({stalls['coverage']:.1%} of "
        f"{stalls['wall_s']:.2f}s wall attributed; top: "
        + ", ".join(f"{c}={r['self_s']:.2f}s" for c, r in top)
    )

    if not os.path.exists(audit_path(cp)):
        return fail("no checkpoint audit sidecar")
    verify_checkpoint_audit(cp, read_checkpoint(cp))
    print(f"smoke: checkpoint audit OK ({audit_path(cp)})")

    # --- health counters from the run report
    report = None
    for line in open(metrics_file):
        rec = json.loads(line)
        if rec.get("kind") == "run_report":
            report = rec["report"]
    if report is None:
        return fail("no run_report in metrics stream")
    counters = (report.get("metrics") or {}).get("counters") or {}
    checks = (counters.get("health.checks") or {}).get("value", 0)
    violations = (counters.get("health.violations") or {}).get("value", 0)
    if not checks:
        return fail("health watchdog never ran (health.checks == 0)")
    if violations:
        return fail(f"{violations} health violations on a clean run")
    print(f"smoke: watchdog OK ({checks} checks, 0 violations)")

    dumps = glob.glob(os.path.join(work, "erp-blackbox-*.json"))
    if dumps:
        return fail(f"black-box dump on a clean run: {dumps}")

    err = check_precision_artifacts()
    if err:
        return fail(err)

    print("smoke: PASS")
    if not args.keep and args.workdir is None:
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
