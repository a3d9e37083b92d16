"""Kill/resume chaos soak: prove the crash contract end to end.

The reference app's core promise is surviving a hostile volunteer host —
BOINC can SIGKILL the process at any template and the resumed run must
produce the same toplist.  This harness manufactures that hostility
against the real driver:

1. run a small workunit uninterrupted -> the reference result file;
2. run the same workunit under a kill schedule: wait for a fresh
   checkpoint, then SIGKILL or SIGTERM the process, resume, repeat —
   with ``ckpt_write:eio`` faults injected (``ERP_FAULT_SPEC``) so the
   checkpoint writer's retry path is exercised while being shot at;
3. once a backup generation exists, corrupt the latest checkpoint in
   place and verify the next resume falls back to the previous
   generation (``io/checkpoint.py`` rotation);
4. let a final clean run complete and require the result file to be
   BYTE-identical to the uninterrupted reference
   (``ERP_RESULT_DATE`` pins the provenance header's timestamp).

A second mode soaks HOST loss instead of process restarts
(``--hosts N --kill-host k``): N driver processes model an N-host pod
chip-free (forced multi-device CPU platform per process, shard leases on
a shared board dir — ``parallel/distributed.py`` / ``parallel/elastic.py``).
One host is SIGKILLed right after it commits mid-shard progress; the
survivors must declare it dead, adopt its unfinished template range from
the last committed shard state (``resilience.rebalance`` >= 1 in a
survivor's run report), and the merge winner's final result file must be
byte-identical to an uninterrupted single-process reference.

A third mode soaks HANGS instead of crashes (``--hang``): deterministic
wedges (``hang`` faults, runtime/faultinject.py) are planted at the
dispatch, lease-IO, and merge sites, and the watchdog
(runtime/watchdog.py) must convert each indefinite stall into a
bounded-time supervised restart (rc 99 -> tools/supervise.py re-exec,
resume from the last committed checkpoint):

A. a dispatch wedge under supervision completes with a final result
   file BYTE-identical to the uninterrupted reference;
B. a poison template (``@tmpl=``, wedging on every visit) wedges K
   times, is quarantined, and the run then COMPLETES with the gap named
   in the result header and counted in ``resilience.quarantined``;
C. a 2-host elastic run survives a lease-IO wedge on one host (self-
   fence -> restart) plus a merge wedge on the winner, still
   byte-identical to the single-process reference.

Usage:
    python tools/chaos_soak.py --quick          # 5 cycles (CI: make chaos)
    python tools/chaos_soak.py --cycles 12 --seed 3 --keep
    python tools/chaos_soak.py --hosts 4 --kill-host 1   # make chaos-hosts
    python tools/chaos_soak.py --hang            # make chaos-hang

Runs on the CPU backend; a shared XLA compilation cache inside the
workdir keeps each resume to seconds after the first compile.  Exit
code 0 = soak passed.
"""

from __future__ import annotations

import argparse
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

# pinned header date: result files from different runs must be comparable
# by byte (io/results.py::ResultHeader.render)
RESULT_DATE = "2008-11-12T00:00:00+00:00"
FALLBACK_MARKER = "Resuming from previous checkpoint generation"


def log(msg: str) -> None:
    print(f"chaos: {msg}", flush=True)


def fail(msg: str) -> int:
    print(f"chaos: FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def build_inputs(work: str, n_templates: int, seed: int) -> tuple[str, str]:
    """Synthetic workunit + a template bank big enough that the kill
    schedule lands many checkpoints before the run could complete."""
    from fixtures import synthetic_timeseries

    from boinc_app_eah_brp_tpu.io import write_template_bank, write_workunit
    from boinc_app_eah_brp_tpu.io.templates import TemplateBank

    ts = synthetic_timeseries(
        4096, f_signal=33.0, P_orb=2.2, tau=0.04, psi0=1.2, amp=7.0
    )
    wu = os.path.join(work, "chaos.bin4")
    write_workunit(wu, ts, tsample_us=500.0, scale=1.0, dm=55.5)

    rng = np.random.default_rng(seed)
    P = np.concatenate([[1000.0, 2.2], rng.uniform(1.5, 3.5, n_templates - 2)])
    tau = np.concatenate([[0.0, 0.04], rng.uniform(0.01, 0.08, n_templates - 2)])
    psi = np.concatenate([[0.0, 1.2], rng.uniform(0.0, 2 * np.pi, n_templates - 2)])
    bank = os.path.join(work, "bank.dat")
    write_template_bank(bank, TemplateBank(P, tau, psi))
    return wu, bank


def child_env(work: str, fault_spec: str | None) -> dict:
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.update(
        {
            # checkpoint after every batch: maximizes kill/resume coverage
            "ERP_CHECKPOINT_PERIOD": "0",
            "ERP_LOOKAHEAD": "1",
            "ERP_RESULT_DATE": RESULT_DATE,
            # generous budget: the p-triggered EIO faults also hit retries
            "ERP_RETRY_BUDGET": "16",
            "ERP_RETRY_BASE_S": "0.01",
            "ERP_RESIL_SNAPSHOT_S": "0",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        }
    )
    if fault_spec:
        env["ERP_FAULT_SPEC"] = fault_spec
    else:
        env.pop("ERP_FAULT_SPEC", None)
    return env


def driver_cmd(wu: str, bank: str, out: str, cp: str) -> list[str]:
    return [
        sys.executable, "-m", "boinc_app_eah_brp_tpu",
        "-i", wu, "-o", out, "-t", bank, "-c", cp,
        "-B", "200", "--batch", "2", "--mesh", "1",
    ]


def launch(cmd: list[str], env: dict, log_path: str) -> subprocess.Popen:
    logf = open(log_path, "w")
    return subprocess.Popen(
        cmd, env=env, stdout=logf, stderr=subprocess.STDOUT,
        cwd=os.path.dirname(log_path),
    )


def describe_result_mismatch(ref_path: str, got_path: str) -> str:
    """Structured candidate-level context for a failed byte-identity gate
    (io/results.parse_result — the round-trip API, not an ad-hoc grep)."""
    try:
        from boinc_app_eah_brp_tpu.io.results import parse_result

        ref, got = parse_result(ref_path), parse_result(got_path)
        bits = [
            f"candidates {len(ref.candidates)} vs {len(got.candidates)}",
            f"done {ref.done} vs {got.done}",
        ]
        rq = ref.header.quarantined if ref.header else []
        gq = got.header.quarantined if got.header else []
        if rq != gq:
            bits.append(f"quarantine gaps {rq} vs {gq}")
        n = min(len(ref.candidates), len(got.candidates))
        for i in range(n):
            if ref.candidates[i] != got.candidates[i]:
                bits.append(f"first differing candidate: line {i}")
                break
        return "; ".join(bits)
    except Exception as exc:  # diagnostics must never mask the failure
        return f"(result unparseable: {exc})"


def checkpoint_stamp(cp: str) -> int:
    try:
        return os.stat(cp).st_mtime_ns
    except OSError:
        return 0


def read_cp_n(cp: str) -> int | None:
    """n_template of the live checkpoint, or None while missing or torn
    (a read can race the writer's rename)."""
    from boinc_app_eah_brp_tpu.io.checkpoint import read_checkpoint

    try:
        return read_checkpoint(cp).n_template
    except Exception:
        return None


def wait_for_fresh_checkpoint(
    proc: subprocess.Popen, cp: str, stamp0: int, timeout_s: float
) -> str:
    """Block until the driver writes a NEW readable checkpoint
    ("advanced"), exits ("exited"), or the deadline passes ("timeout")."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if checkpoint_stamp(cp) != stamp0 and read_cp_n(cp) is not None:
            return "advanced"
        if proc.poll() is not None:
            return "exited"
        time.sleep(0.05)
    return "timeout"


def corrupt_checkpoint(cp: str) -> None:
    """Flip bytes in the middle of the live generation: the audit digest
    check must reject it and resume must fall back to ``<cp>.1``."""
    size = os.path.getsize(cp)
    with open(cp, "r+b") as f:
        f.seek(size // 2)
        chunk = bytearray(f.read(64))
        f.seek(size // 2)
        f.write(bytes(b ^ 0xFF for b in chunk))


def run_to_completion(
    cmd: list[str], env: dict, log_path: str, timeout_s: float
) -> int:
    with open(log_path, "w") as logf:
        r = subprocess.run(
            cmd, env=env, stdout=logf, stderr=subprocess.STDOUT,
            cwd=os.path.dirname(log_path), timeout=timeout_s,
        )
    return r.returncode


def _read_json_lines(path: str) -> list[dict]:
    import json

    docs = []
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    docs.append(json.loads(line))
                except ValueError:
                    pass
    except OSError:
        pass
    return docs


def report_counter(metrics_path: str, name: str) -> float:
    """Value of counter ``name`` in the run report inside a metrics
    JSONL stream (0.0 when absent).  The report rides the stream as a
    ``{"kind": "report", "report": {schema: erp-run-report/1, ...}}``
    line (and standalone report files hold the bare document)."""
    for doc in _read_json_lines(metrics_path):
        report = doc.get("report") if isinstance(doc.get("report"), dict) else doc
        if report.get("schema") == "erp-run-report/1":
            c = (report.get("metrics") or {}).get("counters") or {}
            if name in c:
                return float(c[name].get("value", 0.0))
    return 0.0


def stream_counter(metrics_path: str, name: str) -> float:
    """Max value of counter ``name`` seen anywhere in the metrics stream
    — heartbeat snapshots included.  A watchdog hard exit ships its
    counters via an emergency heartbeat (seq -1); the run report in the
    same file belongs to the final CLEAN pass, which never saw them."""
    best = 0.0
    for doc in _read_json_lines(metrics_path):
        if doc.get("kind") == "heartbeat":
            c = (doc.get("metrics") or {}).get("counters") or {}
        else:
            report = (
                doc.get("report") if isinstance(doc.get("report"), dict)
                else doc
            )
            c = (report.get("metrics") or {}).get("counters") or {}
        if name in c:
            best = max(best, float(c[name].get("value", 0.0)))
    return best


def host_env(
    work: str, hosts: int, host_id: int, shard_dir: str
) -> dict:
    """Child env for one emulated host: process identity + a 2-device
    forced-CPU local mesh + aggressive lease/commit cadences so the soak
    exercises adoption in seconds."""
    env = child_env(work, None)
    env.update(
        {
            "ERP_NUM_PROCESSES": str(hosts),
            "ERP_PROCESS_ID": str(host_id),
            "ERP_LOCAL_DEVICES": "2",
            "ERP_SHARD_DIR": shard_dir,
            # a killed host must be declared dead in ~2s, not 60
            "ERP_LEASE_TIMEOUT_S": "2",
            "ERP_LEASE_GRACE_S": "30",
            # commit shard state at every progress callback so the kill
            # always lands on a mid-range committed state
            "ERP_SHARD_COMMIT_S": "0",
            "ERP_METRICS_FILE": os.path.join(
                work, f"metrics-host{host_id}.jsonl"
            ),
            # per-host span stream: ERP_PROCESS_ID gives each stream a
            # stable host<N> lane, so tools/fleet_timeline.py can merge
            # the soak's artifacts into one cross-host Chrome trace
            "ERP_TRACE_FILE": os.path.join(
                work, f"trace-host{host_id}.jsonl"
            ),
        }
    )
    return env


def hosts_cmd(wu: str, bank: str, out: str, cp: str) -> list[str]:
    """No --mesh: each host autosizes over its forced 2-device platform.
    --batch 1 keeps the global batch at 2 templates so every shard spans
    many commit boundaries — the kill must land on committed MID-shard
    progress for the adoption path to be exercised."""
    return [
        sys.executable, "-m", "boinc_app_eah_brp_tpu",
        "-i", wu, "-o", out, "-t", bank, "-c", cp,
        "-B", "200", "--batch", "1",
    ]


def wait_for_shard_commit(
    shard_dir: str, shard: int, proc: subprocess.Popen, timeout_s: float
) -> str:
    """Block until ``lease-<shard>.json`` records committed progress that
    is strictly inside the range (n_done > start, not complete) — the
    state a kill must land on so survivors have something to adopt —
    or the owning process exits first."""
    import json

    path = os.path.join(shard_dir, f"lease-{shard}.json")
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
            if (
                not doc.get("complete")
                and doc.get("state_path")
                and int(doc.get("n_done", 0)) > int(doc.get("start", 0))
            ):
                return "committed"
        except (OSError, ValueError):
            pass
        if proc.poll() is not None:
            return "exited"
        time.sleep(0.01)
    return "timeout"


def run_hosts_soak(args, work: str, wu: str, bank: str) -> int:
    """--hosts mode: kill one emulated host mid-shard, require byte-
    identical results from the survivors plus a recorded rebalance."""
    hosts, victim = args.hosts, args.kill_host
    if not 0 <= victim < hosts:
        return fail(f"--kill-host {victim} out of range for --hosts {hosts}")

    # --- 1. uninterrupted single-process reference
    ref_out = os.path.join(work, "ref.cand")
    ref_cp = os.path.join(work, "ref.cpt")
    t0 = time.monotonic()
    rc = run_to_completion(
        driver_cmd(wu, bank, ref_out, ref_cp), child_env(work, None),
        os.path.join(work, "run-ref.log"), args.timeout * 2,
    )
    if rc != 0 or not os.path.exists(ref_out):
        sys.stderr.write(open(os.path.join(work, "run-ref.log")).read()[-4000:])
        return fail(f"reference run exited {rc}")
    ref_bytes = open(ref_out, "rb").read()
    log(f"reference run done in {time.monotonic() - t0:.1f}s "
        f"({len(ref_bytes)} result bytes)")

    # --- 2. N-host elastic run; SIGKILL the victim after its first
    # mid-shard commit
    shard_dir = os.path.join(work, "shards")
    os.makedirs(shard_dir, exist_ok=True)
    out = os.path.join(work, "elastic.cand")
    cp = os.path.join(work, "elastic.cpt")
    cmd = hosts_cmd(wu, bank, out, cp)
    procs: dict[int, subprocess.Popen] = {}
    try:
        for h in range(hosts):
            procs[h] = launch(
                cmd, host_env(work, hosts, h, shard_dir),
                os.path.join(work, f"run-host{h}.log"),
            )
        state = wait_for_shard_commit(
            shard_dir, victim, procs[victim], args.timeout
        )
        if state == "timeout":
            return fail(
                f"host {victim} never committed mid-shard progress"
            )
        if state == "exited":
            return fail(
                f"host {victim} exited rc={procs[victim].returncode} "
                f"before it could be killed"
            )
        procs[victim].send_signal(signal.SIGKILL)
        procs[victim].wait()
        log(f"host {victim} SIGKILLed after its first mid-shard commit")

        survivors = [h for h in range(hosts) if h != victim]
        deadline = time.monotonic() + args.timeout * 2
        for h in survivors:
            budget = max(1.0, deadline - time.monotonic())
            try:
                rc = procs[h].wait(timeout=budget)
            except subprocess.TimeoutExpired:
                return fail(f"surviving host {h} still running at deadline")
            if rc != 0:
                sys.stderr.write(
                    open(os.path.join(work, f"run-host{h}.log")).read()[-4000:]
                )
                return fail(f"surviving host {h} exited {rc}")
        log(f"all {len(survivors)} surviving hosts exited 0")
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait()

    # --- 3. verdicts
    if not os.path.exists(out):
        return fail("no result file was written by the surviving hosts")
    got = open(out, "rb").read()
    if got != ref_bytes:
        return fail(
            f"elastic result differs from the single-process reference "
            f"({len(got)} vs {len(ref_bytes)} bytes) — host-loss recovery "
            f"is not bit-identical: {describe_result_mismatch(ref_out, out)}"
        )
    rebalances = sum(
        report_counter(
            os.path.join(work, f"metrics-host{h}.jsonl"),
            "resilience.rebalance",
        )
        for h in range(hosts)
    )
    lost = sum(
        report_counter(
            os.path.join(work, f"metrics-host{h}.jsonl"),
            "resilience.host_lost",
        )
        for h in range(hosts)
    )
    if rebalances < 1:
        return fail(
            "no surviving host recorded a resilience.rebalance event — "
            "the dead host's shard was never adopted"
        )
    log(
        f"PASS: host {victim} of {hosts} killed mid-shard; "
        f"{int(rebalances)} rebalance / {int(lost)} host-lost events "
        f"recorded; result byte-identical to the single-process reference"
    )
    if not args.keep and args.workdir is None:
        shutil.rmtree(work, ignore_errors=True)
    return 0


def hang_env(
    work: str,
    spec: str,
    *,
    watchdog_spec: str,
    fault_state: str | None = None,
    metrics_path: str | None = None,
    quarantine_k: int | None = None,
) -> dict:
    """Child env for a hang-soak pass: short per-stage deadlines so a
    planted wedge is detected in seconds, a short grace so the hard exit
    (rc 99) follows promptly, and an effectively-infinite hang so only
    the watchdog — never the sleep running out — ends the stall."""
    env = child_env(work, spec)
    env.update(
        {
            "ERP_FAULT_HANG_S": "3600",
            "ERP_WATCHDOG_SPEC": watchdog_spec,
            "ERP_WATCHDOG_GRACE_S": "2",
        }
    )
    if fault_state:
        env["ERP_FAULT_STATE"] = fault_state
    else:
        env.pop("ERP_FAULT_STATE", None)
    if metrics_path:
        env["ERP_METRICS_FILE"] = metrics_path
    if quarantine_k is not None:
        env["ERP_QUARANTINE_K"] = str(quarantine_k)
    return env


def supervised_run(
    cmd: list[str], env: dict, work: str, tag: str, max_restarts: int,
    timeout_s: float,
) -> tuple[int, list[int]]:
    """Run ``cmd`` under the real supervision loop
    (runtime/supervise.py), one log file per pass, no backoff sleeps.
    Returns (final rc, per-pass rc list).  A wedge the watchdog misses
    trips the per-pass subprocess timeout and raises — bounded wall
    time is part of what this soak proves."""
    from boinc_app_eah_brp_tpu.runtime.supervise import run_supervised

    rcs: list[int] = []

    def runner(c: list[str], e: dict | None) -> int:
        log_path = os.path.join(work, f"{tag}-pass{len(rcs):02d}.log")
        rc = run_to_completion(c, e, log_path, timeout_s)
        rcs.append(rc)
        return rc

    final = run_supervised(
        cmd, env=env, max_restarts=max_restarts,
        sleep=lambda s: None, runner=runner,
    )
    return final, rcs


def _tail_logs(work: str, tag: str) -> None:
    import glob

    for p in sorted(glob.glob(os.path.join(work, f"{tag}-pass*.log"))):
        sys.stderr.write(f"--- {os.path.basename(p)} ---\n")
        sys.stderr.write(open(p).read()[-3000:])


def run_hang_soak(args, work: str, wu: str, bank: str) -> int:
    """--hang mode: planted wedges at dispatch / lease IO / merge must
    end in supervised restarts (or a quarantine), never a stuck run."""
    import json

    # --- 0. uninterrupted reference
    ref_out = os.path.join(work, "ref.cand")
    ref_cp = os.path.join(work, "ref.cpt")
    t0 = time.monotonic()
    rc = run_to_completion(
        driver_cmd(wu, bank, ref_out, ref_cp), child_env(work, None),
        os.path.join(work, "run-ref.log"), args.timeout * 2,
    )
    if rc != 0 or not os.path.exists(ref_out):
        sys.stderr.write(open(os.path.join(work, "run-ref.log")).read()[-4000:])
        return fail(f"reference run exited {rc}")
    ref_bytes = open(ref_out, "rb").read()
    log(f"reference run done in {time.monotonic() - t0:.1f}s")

    # --- A. dispatch wedge -> watchdog hard exit -> supervised restart,
    # byte-identical completion.  The fault-state file makes the wedge
    # fire exactly once across all passes (a transient fault, not a
    # groundhog-day one).
    out = os.path.join(work, "hangA.cand")
    cp = os.path.join(work, "hangA.cpt")
    env = hang_env(
        work, f"dispatch:hang@n=4;seed={args.seed}",
        watchdog_spec="dispatch=6",
        fault_state=os.path.join(work, "hangA-fault-state.json"),
        metrics_path=os.path.join(work, "hangA-metrics.jsonl"),
    )
    final, rcs = supervised_run(
        driver_cmd(wu, bank, out, cp), env, work, "hangA", 3, args.timeout
    )
    if final != 0 or not os.path.exists(out):
        _tail_logs(work, "hangA")
        return fail(f"phase A: supervised run ended rc={final} (passes {rcs})")
    if rcs.count(99) < 1:
        return fail(f"phase A: no watchdog temporary exit observed ({rcs})")
    if open(out, "rb").read() != ref_bytes:
        return fail("phase A: result differs from reference after a "
                    "dispatch wedge + supervised restart")
    incidents = json.load(open(cp + ".incidents.json"))
    n_dispatch = sum(
        1 for r in incidents["incidents"] if r["stage"] == "dispatch"
    )
    if n_dispatch < 1:
        return fail("phase A: no dispatch incident recorded")
    log(f"phase A PASS: dispatch wedge -> {rcs.count(99)} supervised "
        f"restart(s), byte-identical result, {n_dispatch} incident(s)")

    # --- B. poison template: wedges on EVERY visit (tmpl rules ignore
    # the fault-state file) until K incidents quarantine its window;
    # the run must then complete with a named gap.
    poison = (args.templates // 2) & ~1  # even: batch windows stay aligned
    out = os.path.join(work, "hangB.cand")
    cp = os.path.join(work, "hangB.cpt")
    metrics_b = os.path.join(work, "hangB-metrics.jsonl")
    env = hang_env(
        work, f"dispatch:hang@tmpl={poison};seed={args.seed}",
        watchdog_spec="dispatch=6",
        metrics_path=metrics_b,
        quarantine_k=2,
    )
    final, rcs = supervised_run(
        driver_cmd(wu, bank, out, cp), env, work, "hangB", 4, args.timeout
    )
    if final != 0 or not os.path.exists(out):
        _tail_logs(work, "hangB")
        return fail(f"phase B: supervised run ended rc={final} (passes {rcs})")
    if rcs.count(99) < 2:
        return fail(
            f"phase B: expected >= 2 wedge passes before quarantine ({rcs})"
        )
    from boinc_app_eah_brp_tpu.io.results import parse_result

    parsed_b = parse_result(out)
    if parsed_b.header is None or not parsed_b.header.quarantined:
        return fail("phase B: result header does not name the quarantine gap")
    if not parsed_b.done:
        return fail("phase B: quarantined result is not %DONE%-terminated")
    quarantined_n = report_counter(metrics_b, "resilience.quarantined")
    if quarantined_n < 1:
        return fail("phase B: resilience.quarantined counter not recorded")
    from boinc_app_eah_brp_tpu.runtime.watchdog import validate_incident_log

    problems = validate_incident_log(json.load(open(cp + ".incidents.json")))
    if problems:
        return fail(f"phase B: incident log invalid: {problems}")
    log(f"phase B PASS: template {poison} wedged {rcs.count(99)}x, "
        f"quarantined ({int(quarantined_n)} template(s)), run completed "
        f"with a named gap")

    # --- C. 2-host elastic: lease-IO wedge on host 0 (self-fence ->
    # restart) and a merge wedge on whichever host wins the merge lease;
    # the final result must still be byte-identical to the reference.
    import threading

    hosts = 2
    shard_dir = os.path.join(work, "hang-shards")
    os.makedirs(shard_dir, exist_ok=True)
    out = os.path.join(work, "hangC.cand")
    cp = os.path.join(work, "hangC.cpt")
    cmd = hosts_cmd(wu, bank, out, cp)
    specs = [
        f"lease_io:hang@n=2;merge:hang@n=1;seed={args.seed}",
        f"merge:hang@n=1;seed={args.seed + 1}",
    ]
    results: dict[int, tuple[int, list[int]]] = {}
    errors: list[str] = []

    def run_host(h: int) -> None:
        henv = host_env(work, hosts, h, shard_dir)
        henv.update(
            hang_env(
                work, specs[h],
                watchdog_spec="lease_io=3,merge=6",
                fault_state=os.path.join(work, f"hangC-state-h{h}.json"),
                metrics_path=os.path.join(work, f"hangC-metrics-h{h}.jsonl"),
            )
        )
        # host_env's metrics path loses to hang_env's — keep ONE file per
        # host so report_counter sees every pass
        try:
            results[h] = supervised_run(
                cmd, henv, work, f"hangC-h{h}", 4, args.timeout
            )
        except Exception as e:  # timeout = the watchdog missed a wedge
            errors.append(f"host {h}: {e!r}")

    threads = [
        threading.Thread(target=run_host, args=(h,)) for h in range(hosts)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        for h in range(hosts):
            _tail_logs(work, f"hangC-h{h}")
        return fail(f"phase C: {'; '.join(errors)}")
    rc99_total = sum(results[h][1].count(99) for h in results)
    for h, (final, rcs) in sorted(results.items()):
        if final != 0:
            _tail_logs(work, f"hangC-h{h}")
            return fail(f"phase C: host {h} ended rc={final} (passes {rcs})")
    if not os.path.exists(out):
        return fail("phase C: no result file written")
    if open(out, "rb").read() != ref_bytes:
        return fail("phase C: elastic result differs from the reference "
                    "after lease/merge wedges")
    if rc99_total < 2:
        return fail(
            f"phase C: expected >= 2 watchdog restarts across hosts "
            f"(lease wedge + merge wedge), saw {rc99_total}"
        )
    fenced = sum(
        stream_counter(
            os.path.join(work, f"hangC-metrics-h{h}.jsonl"),
            "watchdog.self_fenced",
        )
        for h in range(hosts)
    )
    if fenced < 1:
        return fail("phase C: lease wedge never triggered a self-fence")
    log(f"phase C PASS: {rc99_total} watchdog restarts across {hosts} "
        f"hosts ({int(fenced)} self-fence), result byte-identical")

    log("PASS: hang soak — dispatch, poison-template, lease and merge "
        "wedges all ended in bounded-time recoveries")
    if not args.keep and args.workdir is None:
        shutil.rmtree(work, ignore_errors=True)
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description="Kill/resume chaos soak.")
    ap.add_argument("--cycles", type=int, default=8,
                    help="kill/resume cycles to run (default 8)")
    ap.add_argument("--quick", action="store_true",
                    help="5-cycle CI profile (make chaos)")
    ap.add_argument("--templates", type=int, default=40,
                    help="template bank size (default 40)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--timeout", type=float, default=300.0,
                    help="per-wait timeout in seconds")
    ap.add_argument("--workdir", help="reuse this dir instead of a tmp one")
    ap.add_argument("--keep", action="store_true",
                    help="keep the workdir (default: removed on PASS)")
    ap.add_argument("--hosts", type=int, default=0,
                    help="host-loss mode: emulate N hosts chip-free and "
                         "kill one mid-run (0 = classic kill/resume soak)")
    ap.add_argument("--kill-host", type=int, default=1,
                    help="which emulated host to SIGKILL (--hosts mode)")
    ap.add_argument("--hang", action="store_true",
                    help="hang-soak mode: planted wedges at dispatch / "
                         "lease IO / merge must end in supervised "
                         "restarts or a quarantine (make chaos-hang)")
    args = ap.parse_args(argv)
    cycles_wanted = 5 if args.quick else args.cycles

    work = args.workdir or tempfile.mkdtemp(prefix="erp-chaos-")
    os.makedirs(work, exist_ok=True)
    log(f"workdir {work}")
    if args.hang:
        wu, bank = build_inputs(work, args.templates, args.seed)
        return run_hang_soak(args, work, wu, bank)
    if args.hosts:
        # host-loss mode wants enough templates that every shard spans
        # several commit boundaries
        n_templates = max(args.templates, 16 * args.hosts)
        wu, bank = build_inputs(work, n_templates, args.seed)
        return run_hosts_soak(args, work, wu, bank)
    wu, bank = build_inputs(work, args.templates, args.seed)

    # --- 1. uninterrupted reference run
    ref_out = os.path.join(work, "ref.cand")
    ref_cp = os.path.join(work, "ref.cpt")
    t0 = time.monotonic()
    rc = run_to_completion(
        driver_cmd(wu, bank, ref_out, ref_cp), child_env(work, None),
        os.path.join(work, "run-ref.log"), args.timeout * 2,
    )
    if rc != 0 or not os.path.exists(ref_out):
        sys.stderr.write(open(os.path.join(work, "run-ref.log")).read()[-4000:])
        return fail(f"reference run exited {rc}")
    ref_bytes = open(ref_out, "rb").read()
    log(f"reference run done in {time.monotonic() - t0:.1f}s "
        f"({len(ref_bytes)} result bytes)")

    # --- 2. kill/resume cycles with injected checkpoint-write EIO
    out = os.path.join(work, "chaos.cand")
    cp = os.path.join(work, "chaos.cpt")
    cycles = 0
    run_no = 0
    corrupted = False
    fallback_seen = False
    while cycles < cycles_wanted:
        run_no += 1
        spec = f"ckpt_write:eio@p=0.1;seed={args.seed + run_no}"
        log_path = os.path.join(work, f"run-{run_no:02d}.log")
        stamp0 = checkpoint_stamp(cp)
        proc = launch(driver_cmd(wu, bank, out, cp), child_env(work, spec),
                      log_path)
        try:
            state = wait_for_fresh_checkpoint(proc, cp, stamp0, args.timeout)
            if state == "timeout":
                proc.kill()
                proc.wait()
                sys.stderr.write(open(log_path).read()[-4000:])
                return fail(f"run {run_no} never wrote a fresh checkpoint")
            if state == "exited":
                rc = proc.returncode
                if rc != 0:
                    sys.stderr.write(open(log_path).read()[-4000:])
                    return fail(f"run {run_no} exited {rc} before the kill")
                if os.path.exists(out):
                    # completed the whole WU between kills: reset and keep
                    # soaking (small WU + fast host)
                    log(f"run {run_no} completed early; resetting state")
                    for p in (out, cp, cp + ".1", cp + ".audit.json",
                              cp + ".1.audit.json"):
                        if os.path.exists(p):
                            os.remove(p)
                continue
            # fresh checkpoint on disk: shoot the process
            sig = signal.SIGKILL if cycles % 2 == 0 else signal.SIGTERM
            proc.send_signal(sig)
            try:
                proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
                return fail(f"run {run_no} ignored {sig!r} for 120s")
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        cycles += 1
        n = read_cp_n(cp)
        log(f"cycle {cycles}/{cycles_wanted}: run {run_no} killed with "
            f"{sig.name} at checkpoint n_template={n}")
        if fallback_seen is False and os.path.exists(log_path):
            if FALLBACK_MARKER in open(log_path).read():
                fallback_seen = True
                log(f"generation fallback observed in run {run_no}")
        # once a backup generation exists, corrupt the live checkpoint
        # exactly once: the NEXT resume must survive via <cp>.1
        if not corrupted and cycles >= 2 and os.path.exists(cp + ".1"):
            corrupt_checkpoint(cp)
            corrupted = True
            log("corrupted live checkpoint generation in place")

    # --- 3. final clean run to completion (no faults)
    rc = run_to_completion(
        driver_cmd(wu, bank, out, cp), child_env(work, None),
        os.path.join(work, "run-final.log"), args.timeout * 2,
    )
    final_log = open(os.path.join(work, "run-final.log")).read()
    if rc != 0 or not os.path.exists(out):
        sys.stderr.write(final_log[-4000:])
        return fail(f"final resumed run exited {rc}")
    if not fallback_seen and FALLBACK_MARKER in final_log:
        fallback_seen = True
        log("generation fallback observed in the final run")

    # --- 4. verdicts
    if corrupted and not fallback_seen:
        return fail(
            "live checkpoint was corrupted but no resume ever logged the "
            "generation fallback"
        )
    chaos_bytes = open(out, "rb").read()
    if chaos_bytes != ref_bytes:
        return fail(
            f"final result differs from the uninterrupted reference "
            f"({len(chaos_bytes)} vs {len(ref_bytes)} bytes) — resume is "
            f"not bit-identical: {describe_result_mismatch(ref_out, out)}"
        )
    log(f"PASS: {cycles} kill/resume cycles, corrupt-generation fallback "
        f"{'exercised' if corrupted else 'not reached'}, result byte-identical")
    if not args.keep and args.workdir is None:
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
