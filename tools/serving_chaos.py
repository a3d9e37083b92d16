"""Serving chaos soak: SIGKILL the resident server mid-queue and prove
nothing is lost.

The per-WU driver earned its crash story through ``chaos_soak.py``;
this soak applies the same discipline to the fleet serving tier
(``serving/server.py`` + ``serving/journal.py``).  One run drives a
real server subprocess through three injuries and four gates:

1. **Kill + journal EIO** (phase A): a ``--serve`` child accepts every
   workunit into the WU journal while ``journal_write:eio`` faults
   (``runtime/faultinject.py``) hit the WAL appends; the parent
   SIGKILLs it as soon as the first grant lands — mid-queue, torn tail
   and all.
2. **Wedge + supervised restart** (phase B): the child relaunches with
   ``--supervised`` (the ``tools/supervise.py``-style wrapper on the
   server entry), replays the journal, and a planted
   ``serving_dispatch:hang`` wedges the dispatch thread; the watchdog's
   ``serving_dispatch`` deadline converts the stall into rc 99 and the
   supervisor restarts the server into another replay, which completes
   every remaining workunit.
3. **Gates**: every submitted WU's result file must be BYTE-IDENTICAL
   to a one-process-per-WU driver reference (half-done WUs resumed
   mid-bank from their Session checkpoints, exactly like
   ``chaos_soak.py``); the final pass must report
   ``recompiles_after_warmup == 0`` (warm resume on the shared AOT
   cache) and ``resumed_wus >= 1``; both the mid-crash journal
   snapshot and the final journal must validate under
   ``metrics_report --check``.
4. **Overload**: a bounded-queue shed check (in-process, stub
   scheduler) proves saturation rejects with an explicit retry-after,
   ``/healthz`` flips 503 with a ``Retry-After`` header while
   shedding, and every ACCEPTED workunit is still granted.

Usage:
    python tools/serving_chaos.py --quick        # the make serving-chaos gate
    python tools/serving_chaos.py --wus 6 --keep --workdir DIR
    python tools/serving_chaos.py --serve --workdir DIR   # child mode
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))
sys.path.insert(0, os.path.join(REPO, "tools"))

RESULT_DATE = "2008-11-12T00:00:00+00:00"
MANIFEST = "manifest.json"
STATS = "serving-stats.json"
SERVE_TIMEOUT_S = 600


def log(msg: str) -> None:
    print(f"serving-chaos: {msg}", flush=True)


def fail(msg: str) -> int:
    print(f"serving-chaos: FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def serve_env(work: str, fault_spec: str | None, state_name: str,
              extra: dict | None = None) -> dict:
    """Child env, mirroring ``chaos_soak.child_env``: chip-free,
    deterministic result headers, frequent checkpoints, a shared AOT
    cache so every resume warm-starts."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.update(
        {
            "ERP_CHECKPOINT_PERIOD": "0",
            "ERP_LOOKAHEAD": "1",
            "ERP_RESULT_DATE": RESULT_DATE,
            "ERP_RETRY_BUDGET": "16",
            "ERP_RETRY_BASE_S": "0.01",
            "ERP_RESIL_SNAPSHOT_S": "0",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        }
    )
    env.pop("ERP_FAULT_SPEC", None)
    env.pop("ERP_SLO_FILE", None)
    if fault_spec:
        env["ERP_FAULT_SPEC"] = fault_spec
        env["ERP_FAULT_STATE"] = os.path.join(work, state_name)
    if extra:
        env.update(extra)
    return env


def serve_cmd(work: str, supervised: int | None = None) -> list[str]:
    cmd = [
        sys.executable, os.path.abspath(__file__), "--serve",
        "--workdir", work,
    ]
    if supervised is not None:
        cmd += ["--supervised", str(supervised)]
    return cmd


# ---------------------------------------------------------------------------
# child: the server entry


def serve(work: str) -> int:
    """Run a durable FleetServer over the manifest: replay the journal,
    submit what was never accepted, block until every known ticket is
    granted, write the scoreboard."""
    from boinc_app_eah_brp_tpu.runtime.driver import DriverArgs
    from boinc_app_eah_brp_tpu.serving import (
        FleetServer,
        journal_path,
        replay,
    )

    import fleet_bench

    with open(os.path.join(work, MANIFEST), encoding="utf-8") as f:
        manifest = json.load(f)
    known = {f.name for f in dataclasses.fields(DriverArgs)}
    args_list = [
        DriverArgs(**{k: v for k, v in m.items() if k in known})
        for m in manifest
    ]

    jpath = journal_path(work)
    state = replay(jpath)
    accepted_outputs = {
        (r.get("args") or {}).get("outputfile")
        for r in state.submits.values()
    }
    replayed_tickets = [r["ticket"] for r in state.pending]

    # warm exactly like fleet_bench: WU 1 of every pass (including the
    # post-crash resume) must already run on a resident executable
    specs = [fleet_bench.warm_spec_for(args_list[0])]
    server = FleetServer(resume_dir=work, warm_specs=specs, name="chaos")
    try:
        new_tickets = [
            server.submit(a, corr_id=f"chaos-{i}")
            for i, a in enumerate(args_list)
            if a.outputfile not in accepted_outputs
        ]
        log(
            f"serve pid={os.getpid()}: replayed {len(replayed_tickets)}, "
            f"submitted {len(new_tickets)} new"
        )
        bad = []
        for t in replayed_tickets + new_tickets:
            res = server.result(t, timeout=SERVE_TIMEOUT_S)
            if not res.ok:
                bad.append(f"{t}:{res.code}")
        stats = server.stats()
    finally:
        server.close()
    tmp = os.path.join(work, f"{STATS}.tmp.{os.getpid()}")
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(stats, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, os.path.join(work, STATS))
    if bad:
        print(
            f"serving-chaos: serve: failed sessions: {', '.join(bad)}",
            file=sys.stderr,
        )
        return 1
    return 0


# ---------------------------------------------------------------------------
# parent: injuries and gates


def wait_for_first_grant(jpath: str, proc: subprocess.Popen,
                         timeout: float = 300.0):
    """Poll the journal until the first ``done`` record lands while
    work is still pending — the mid-queue moment to SIGKILL."""
    from boinc_app_eah_brp_tpu.serving import replay

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            return None
        st = replay(jpath)
        pending = len(st.pending)
        if st.done and pending > 0:
            return len(st.done), pending
        time.sleep(0.05)
    return None


def shed_check() -> str | None:
    """Bounded-queue backpressure, in-process with a stub scheduler (no
    sessions — this proves the ADMISSION contract, fleet_bench proves
    accepted WUs meet the baseline floors).  Returns an error string or
    None."""
    from boinc_app_eah_brp_tpu.runtime.driver import DriverArgs
    from boinc_app_eah_brp_tpu.runtime.scheduler import SessionResult
    from boinc_app_eah_brp_tpu.serving import FleetServer, ServerOverloaded
    from boinc_app_eah_brp_tpu.serving.introspect import Introspector

    class _StubCache:
        hits = misses = 0

        def __len__(self):
            return 0

        def keys(self):
            return []

    class _StubScheduler:
        def __init__(self):
            self.step_cache = _StubCache()
            self.inter_wu_gaps_s = []
            self.warmed = False
            self.gate = threading.Event()
            self.entered = threading.Event()

        def n_devices(self):
            return 1

        def arm_slo(self, monitor):
            pass

        def warm(self, specs):
            return {}

        def build_session(self, args, corr_id=None, name=None):
            return types.SimpleNamespace(args=args, corr_id=corr_id, name=name)

        def prepare_async(self, session):
            return None

        def execute(self, session, prep_future=None):
            self.entered.set()
            self.gate.wait(timeout=30)
            return SessionResult(
                name=session.name, code=0, corr_id=session.corr_id,
                outputfile=session.args.outputfile, wall_s=0.01,
            )

        def close(self):
            pass

    sched = _StubScheduler()
    sched.gate.clear()
    server = FleetServer(scheduler=sched, queue_max=2, name="shed")
    intro = Introspector(port=0, server=server, name="shed")
    try:
        mk = lambda i: DriverArgs(  # noqa: E731
            inputfile=f"in{i}", outputfile=f"out{i}", templatebank="bank"
        )
        tickets = [server.submit(mk(0))]
        if not sched.entered.wait(timeout=10):
            return "dispatch never started"
        tickets += [server.submit(mk(1)), server.submit(mk(2))]
        try:
            server.submit(mk(3))
            return "queue at ERP_SERVING_QUEUE_MAX accepted a submit"
        except ServerOverloaded as e:
            if e.retry_after_s < 1.0:
                return f"shed without a usable retry-after ({e.retry_after_s})"
        import urllib.error
        import urllib.request

        try:
            with urllib.request.urlopen(intro.url("/healthz"), timeout=10):
                return "/healthz answered 200 while shedding"
        except urllib.error.HTTPError as e:
            if e.code != 503:
                return f"/healthz answered {e.code} while shedding, want 503"
            if not e.headers.get("Retry-After"):
                return "503 shed response carries no Retry-After header"
        sched.gate.set()
        for t in tickets:
            res = server.result(t, timeout=30)
            if not res.ok:
                return f"accepted WU {t} failed under shed load"
        code, _doc = intro.healthz()
        if code != 200:
            return f"/healthz still {code} after the queue drained"
        stats = server.stats()
        if stats["shed_total"] != 1:
            return f"shed_total {stats['shed_total']}, want 1"
    finally:
        intro.close()
        server.close()
    return None


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)

    # --serve --supervised N: become the restart supervisor (the
    # tools/supervise.py-style wrapper on the server entry) and re-exec
    # the worker minus the flag whenever it exits rc 99
    if "--serve" in argv and "--supervised" in argv:
        from boinc_app_eah_brp_tpu.runtime.supervise import (
            run_supervised,
            strip_supervised_flag,
        )

        worker_argv, budget = strip_supervised_flag(argv)
        return run_supervised(
            [sys.executable, os.path.abspath(__file__), *worker_argv],
            max_restarts=max(0, budget or 0),
        )

    ap = argparse.ArgumentParser(
        description="Serving chaos soak: SIGKILL + journal EIO + "
        "dispatch wedge against a durable FleetServer."
    )
    ap.add_argument("--wus", type=int, default=5,
                    help="workunits to stream (default 5)")
    ap.add_argument("--quick", action="store_true",
                    help="CI preset (same as the defaults today)")
    ap.add_argument("--workdir", help="reuse this dir instead of a tmp one")
    ap.add_argument("--keep", action="store_true",
                    help="keep the workdir (default: removed when green)")
    ap.add_argument("--serve", action="store_true",
                    help="child mode: run the durable server over the "
                         "workdir manifest")
    ap.add_argument("--supervised", type=int, default=None,
                    help="(with --serve) restart budget for the rc-99 "
                         "supervision loop")
    args = ap.parse_args(argv)

    if args.serve:
        if not args.workdir:
            return fail("--serve needs --workdir")
        return serve(args.workdir)
    if args.wus < 3:
        return fail("--wus must be >= 3 (kill mid-queue needs a backlog)")

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["ERP_RESULT_DATE"] = RESULT_DATE
    os.environ.setdefault("ERP_SUPERVISE_BACKOFF_S", "0.1")
    work = args.workdir or tempfile.mkdtemp(prefix="erp-serving-chaos-")
    os.makedirs(work, exist_ok=True)
    log(f"workdir {work}")

    import fleet_bench
    import metrics_report

    from boinc_app_eah_brp_tpu.serving import journal_path, replay

    wus, _bank = fleet_bench.build_workunits(work, args.wus)
    with open(os.path.join(work, MANIFEST), "w", encoding="utf-8") as f:
        json.dump([dataclasses.asdict(a) for a in wus], f, indent=1)
        f.write("\n")

    # references first: the one-process-per-WU byte oracle, and the
    # subprocess runs also populate the shared AOT cache the server's
    # warm resume relies on
    env_base = serve_env(work, None, "")
    t0 = time.monotonic()
    refs = {}
    for i, a in enumerate(wus):
        refs[a.outputfile] = fleet_bench.run_reference(a, env_base)
    log(
        f"{len(refs)} per-WU driver references in "
        f"{time.monotonic() - t0:.1f}s"
    )

    jpath = journal_path(work)

    # -- phase A: journal EIO + SIGKILL mid-queue -------------------------
    env_a = serve_env(work, "seed=7;journal_write:eio@n=3", "fault-a.json")
    log_a = os.path.join(work, "serve-a.log")
    with open(log_a, "w") as logf:
        proc = subprocess.Popen(
            serve_cmd(work), env=env_a, stdout=logf,
            stderr=subprocess.STDOUT,
        )
        hit = wait_for_first_grant(jpath, proc)
        if hit is None:
            proc.kill()
            proc.wait()
            return fail(
                f"phase A: no mid-queue kill point (see {log_a})"
            )
        done_a, pending_a = hit
        proc.send_signal(signal.SIGKILL)
        proc.wait()
    log(
        f"phase A: SIGKILL mid-queue after {done_a} grant(s), "
        f"{pending_a} pending (journal EIO injected and retried)"
    )

    # mid-crash journal snapshot: must validate even with a possibly
    # torn tail from the kill
    snap = os.path.join(work, "journal-after-kill.jsonl")
    shutil.copyfile(jpath, snap)
    if metrics_report.main(["--check", snap]) != 0:
        return fail("mid-crash journal snapshot failed metrics_report --check")
    st = replay(snap)
    if not st.pending:
        return fail("phase A: nothing pending in the journal after the kill")
    for t, rec in st.done.items():
        if not rec.get("digest"):
            return fail(f"phase A: done record for {t} has no payload digest")

    # -- phase B: dispatch wedge under supervision, then finish -----------
    env_b = serve_env(
        work, "seed=7;serving_dispatch:hang@n=1", "fault-b.json",
        extra={
            "ERP_FAULT_HANG_S": "120",
            "ERP_WATCHDOG_SPEC": "serving_dispatch=2,serving_result=30",
            "ERP_WATCHDOG_GRACE_S": "2",
            "ERP_WATCHDOG_POLL_S": "0.25",
        },
    )
    log_b = os.path.join(work, "serve-b.log")
    t0 = time.monotonic()
    with open(log_b, "w") as logf:
        rc = subprocess.call(
            serve_cmd(work, supervised=3), env=env_b, stdout=logf,
            stderr=subprocess.STDOUT, timeout=SERVE_TIMEOUT_S,
        )
    if rc != 0:
        sys.stderr.write(open(log_b).read()[-4000:])
        return fail(f"phase B: supervised server exited {rc}")
    blog = open(log_b).read()
    if "restarting in" not in blog:
        return fail(
            "phase B: the dispatch wedge never triggered a supervised "
            f"restart (see {log_b})"
        )
    log(
        f"phase B: wedge -> rc 99 -> supervised restart -> drained in "
        f"{time.monotonic() - t0:.1f}s"
    )

    # -- gates ------------------------------------------------------------
    for a in wus:
        try:
            with open(a.outputfile, "rb") as f:
                got = f.read()
        except OSError as e:
            return fail(f"{os.path.basename(a.outputfile)}: not granted ({e})")
        if got != refs[a.outputfile]:
            return fail(
                f"{os.path.basename(a.outputfile)}: differs from the "
                f"per-WU driver reference (bytes {len(got)} vs "
                f"{len(refs[a.outputfile])})"
            )
    log(f"all {len(wus)} results byte-identical to per-WU references")

    if metrics_report.main(["--check", jpath]) != 0:
        return fail("final journal failed metrics_report --check")

    with open(os.path.join(work, STATS), encoding="utf-8") as f:
        stats = json.load(f)
    if stats.get("recompiles_after_warmup", -1) != 0:
        return fail(
            f"recompiles_after_warmup = "
            f"{stats.get('recompiles_after_warmup')} after warm resume "
            "(must be 0)"
        )
    if stats.get("resumed_wus", 0) < 1:
        return fail(
            f"final pass replayed {stats.get('resumed_wus')} WUs, want >= 1"
        )
    log(
        f"final pass: resumed_wus={stats['resumed_wus']}, "
        f"0 recompiles after warm resume"
    )

    err = shed_check()
    if err:
        return fail(f"shed check: {err}")
    log("overload: bounded queue sheds with retry-after, /healthz flips 503")

    if not args.keep and not args.workdir:
        shutil.rmtree(work, ignore_errors=True)
    log(
        f"PASS ({args.wus} WUs through SIGKILL + journal EIO + dispatch "
        "wedge; zero lost, zero drift)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
