"""Resident Scheduler: devices, compiled executables and the AOT cache
owned for the life of the serving process.

The one-process-per-WU driver pays JAX init, XLA compilation and cold
device buffers for every workunit the fabric grants.  The serving tier
(``serving/server.py``, ROADMAP item 3) amortizes all of it: ONE
Scheduler holds

* the device view (selection happens once, like the reference's
  ``initialize_cuda``);
* a :class:`StepCache` of jitted ``make_bank_step`` instances keyed by
  ``models/search.py::step_cache_key`` — a same-geometry WU reuses the
  exact executable instance, so after warmup the ``jax.recompiles``
  counter stays flat (the headline gate, ``tools/fleet_bench.py``);
* the persistent XLA compilation cache (``driver.enable_compilation_
  cache``), warmed at startup via :meth:`warm` — the server-resident
  growth of ``tools/aot_prewarm.py``'s record/check modes, with
  ``fleet.aot_hit`` / ``fleet.aot_miss`` counting how many warm compiles
  the persistent cache absorbed;
* a one-thread prep pool so WU k+1's :meth:`~.session.Session.prepare`
  (parse, whiten, geometry) overlaps WU k's device drain — the cross-WU
  analogue of the exact-mean prefetch.

Per-Session isolation: every :meth:`execute` arms the hang watchdog
with THAT session's incident log, begins a fresh resilience retry
budget, and catches the driver's mapped error classes — a poisoned WU
produces a failed :class:`SessionResult` (and quarantine provenance on
its next visit) without restarting the server.

Serving-tier scope (v1): single-device, non-elastic sessions.  Mesh
sharding and the elastic board keep their one-process driver entry —
``docs/serving.md`` has the packing rules and the roadmap for folding
them in.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

from . import faultinject, metrics, resilience, steptime, tracing, watchdog
from . import logging as erplog
from .obs import ObsContext
from .session import Session, SessionEnv, exit_code_for


class StepCache:
    """Mapping of ``step_cache_key`` -> jitted bank step, with hit/miss
    accounting into the ``fleet.*`` metrics family.

    The mapping contract matches what ``models/search.py::
    _run_bank_attempt`` expects (``get`` + ``__setitem__``); entries are
    never evicted — a serving process sees a handful of distinct
    geometries, and each entry is a callable wrapper whose weight is the
    XLA executable the whole design exists to keep resident."""

    def __init__(self):
        self._d: dict = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key):
        with self._lock:
            step = self._d.get(key)
            if step is None:
                self.misses += 1
                metrics.counter("fleet.step_cache_miss").inc()
            else:
                self.hits += 1
                metrics.counter("fleet.step_cache_hit").inc()
            return step

    def __setitem__(self, key, step) -> None:
        with self._lock:
            self._d[key] = step

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._d

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)

    def keys(self):
        with self._lock:
            return list(self._d.keys())


@dataclass
class SessionResult:
    """Outcome of one Session through the resident scheduler — the
    queue-out half of the serving API."""

    name: str
    code: int
    outputfile: str | None = None
    corr_id: str | None = None
    error: str | None = None
    wall_s: float = 0.0
    prepare_s: float = 0.0
    recompiles: int = 0
    step_cache_hits: int = 0
    step_cache_misses: int = 0

    @property
    def ok(self) -> bool:
        return self.code == 0


@dataclass
class WarmSpec:
    """One executable to pre-build at server startup: the geometry and
    batch shape of an expected workunit class."""

    geom: object  # models/search.SearchGeometry
    batch_size: int
    with_health: bool = False
    allow_pallas: bool = True
    bank_P: np.ndarray | None = field(default=None, repr=False)
    bank_tau: np.ndarray | None = field(default=None, repr=False)
    bank_psi0: np.ndarray | None = field(default=None, repr=False)


def plan_packing(requests: list) -> list:
    """Order queued requests so same-executable WUs run back to back.

    ``requests`` is a list of (key, request) pairs where ``key`` is the
    request's ``step_cache_key`` (or any hashable geometry proxy).  A
    stable grouping — first-seen key order, FIFO within a key — keeps
    the resident step hot across consecutive WUs and bounds a request's
    queue delay by the backlog of its own class plus earlier classes
    (no starvation: groups are not re-sorted by size).  This is the
    serving tier's packing rule; see docs/serving.md."""
    order: dict = {}
    for key, _ in requests:
        if key not in order:
            order[key] = len(order)
    return [
        pair[1] for _, pair in sorted(
            enumerate(requests), key=lambda e: (order[e[1][0]], e[0])
        )
    ]


class Scheduler:
    """Owns what must outlive any single workunit; executes Sessions
    serially on the device while overlapping the next Session's host
    prep."""

    def __init__(self, *, prep_workers: int = 1, artifacts_dir: str | None = None):
        from .driver import enable_compilation_cache

        enable_compilation_cache()
        self.step_cache = StepCache()
        self.artifacts_dir = artifacts_dir
        self._exec_lock = threading.Lock()
        self._prep_pool = ThreadPoolExecutor(
            max_workers=max(1, prep_workers),
            thread_name_prefix="erp-fleet-prep",
        )
        self._seq = 0
        self._seq_lock = threading.Lock()
        self._last_exec_end: float | None = None
        self.inter_wu_gaps_s: list[float] = []
        self.warmed = False
        self.slo = None  # serving/slo.SLOMonitor, attached via arm_slo
        self._closed = False

    def arm_slo(self, monitor) -> None:
        """Attach a live serving-SLO monitor (``serving/slo.SLOMonitor``):
        every executed Session feeds it its inter-WU gap, recompile delta
        and measured step latencies.  The monitor's warmup boundary
        follows this scheduler's."""
        self.slo = monitor
        if monitor is not None:
            monitor.warmed = self.warmed

    # -- device view ------------------------------------------------------

    def n_devices(self) -> int:
        import jax

        return len(jax.devices())

    # -- warmup -----------------------------------------------------------

    def warm(self, specs) -> dict:
        """Pre-build the bank-step executables for the expected workunit
        classes, before the first WU is queued.

        Each spec compiles by CALLING the jitted step once on dummy
        operands of the exact production shapes — that both populates
        the in-memory jit dispatch cache (zero retrace for the real WU)
        and routes through the persistent XLA cache.  ``fleet.aot_hit``
        counts warm compiles the persistent cache (or an existing
        step-cache entry) absorbed; ``fleet.aot_miss`` counts cold
        builds.  Returns ``{"aot_hit": .., "aot_miss": .., "steps": ..}``
        — the same tallies ``tools/aot_prewarm.py --warm`` prints."""
        import jax
        import jax.numpy as jnp

        from ..models.search import (
            bank_params_host,
            init_state,
            make_bank_step,
            prepare_ts,
            step_cache_key,
            upload_bank,
        )

        hit_c = metrics.counter("fleet.aot_hit")
        miss_c = metrics.counter("fleet.aot_miss")
        hits = misses = built = 0
        for spec in specs:
            geom = spec.geom
            key = step_cache_key(
                geom, spec.batch_size, spec.with_health, spec.allow_pallas
            )
            if key in self.step_cache:
                hits += 1
                hit_c.inc()
                continue
            # representative operands: shapes/dtypes are what the compile
            # keys on; values are irrelevant
            B = int(spec.batch_size)
            # the compiled signature keys on the UPLOADED bank length
            # (padded to a batch multiple), so a warm spec must carry the
            # real bank to hit the production shapes; the fallback
            # synthesizes a B-template stand-in
            if spec.bank_P is not None:
                P = np.asarray(spec.bank_P, dtype=np.float64)
                tau = np.asarray(spec.bank_tau, dtype=np.float64)
                psi0 = np.asarray(spec.bank_psi0, dtype=np.float64)
            else:
                P = np.full(B, 1000.0)
                tau = np.full(B, 0.01)
                psi0 = np.zeros(B)
            params = bank_params_host(P, tau, psi0, geom.dt)
            dev_bank = upload_bank(params, B)
            ts_args = prepare_ts(
                geom, np.zeros(geom.n_unpadded, dtype=np.float32)
            )
            M, T = init_state(geom)
            # compilation-cache traffic delta tells warm-vs-cold apart:
            # a persistent-cache hit emits compile_time_saved, a cold
            # build emits backend_compile (runtime/metrics.py jax bridge)
            probe = metrics.MetricsContext(name="fleet-warm-probe")
            probe.configure(force=True)
            t0 = time.perf_counter()
            step = make_bank_step(
                geom, B, with_health=spec.with_health,
                allow_pallas=spec.allow_pallas,
            )
            args = [ts_args, *dev_bank, jnp.int32(0), jnp.int32(B), M, T]
            if geom.exact_mean:
                args += [
                    jnp.asarray(np.full(B, geom.nsamples, dtype=np.int32)),
                    jnp.asarray(np.zeros(B, dtype=np.float32)),
                ]
            out = step(*args)
            jax.block_until_ready(out[0])
            saved = probe.registry().counter(
                "jax.cache_time_saved_s", unit="s"
            ).value
            compiled = probe.registry().counter("jax.recompiles").value
            probe.finish(0)
            self.step_cache[key] = step
            built += 1
            # a persistent-cache deserialize (compile_time_saved) or a
            # zero-compile call both mean the AOT work was already paid
            warm_hit = saved > 0 or compiled == 0
            if warm_hit:
                hits += 1
                hit_c.inc()
            else:
                misses += 1
                miss_c.inc()
            erplog.debug(
                "Warm step %s batch %d in %.2fs (%s).\n",
                "hit" if warm_hit else "miss", B,
                time.perf_counter() - t0,
                "persistent cache" if warm_hit else "cold compile",
            )
        self.warmed = True
        if self.slo is not None:
            self.slo.warmed = True
        metrics.gauge("fleet.warm_steps").set(len(self.step_cache))
        return {"aot_hit": hits, "aot_miss": misses, "steps": built}

    # -- session lifecycle ------------------------------------------------

    def build_session(
        self, args, *, corr_id: str | None = None, name: str | None = None
    ) -> Session:
        """A Session wearing its own scoped ObsContext, wired for this
        scheduler.  Env knobs (`ERP_LOOKAHEAD`, checkpoint cadence, ...)
        are snapshotted NOW — per Session, never per server process."""
        with self._seq_lock:
            self._seq += 1
            seq = self._seq
        sname = name or f"session-{seq}"
        obs = ObsContext(name=sname)
        dump_dir = self.artifacts_dir
        if dump_dir is None:
            for p in (args.checkpointfile, args.outputfile):
                if p:
                    dump_dir = os.path.dirname(os.path.abspath(p))
                    break
        obs.configure(
            force_metrics=True,
            dump_dir=dump_dir,
            context={
                "session": sname,
                "inputfile": args.inputfile,
                **({"corr_id": corr_id} if corr_id else {}),
            },
        )
        env = SessionEnv.capture()
        return Session(
            args, env.make_adapter(), env=env, obs=obs, corr_id=corr_id
        )

    def prepare_async(self, session: Session) -> Future:
        """Stage the session's host-side prep on the prep pool — called
        for WU k+1 while WU k still owns the device."""
        return self._prep_pool.submit(session.prepare, 1, None)

    def execute(self, session: Session, prep_future: Future | None = None) -> SessionResult:
        """Run one (possibly pre-prepared) Session on the device,
        serialized against every other Session.  Never raises for the
        driver's mapped error classes: a poisoned WU yields a failed
        SessionResult and the server lives on."""
        args = session.args
        name = session.obs.name if session.obs is not None else "session"
        corr_id = session.corr_id
        t_q = time.perf_counter()
        prep_s = 0.0
        code: int | None = None
        err: str | None = None
        rec0 = self._session_recompiles(session)
        gap_s: float | None = None
        step_cursor = steptime.count()
        with self._exec_lock, tracing.span("wu", ticket=name, corr_id=corr_id):
            t0 = time.perf_counter()
            if self._last_exec_end is not None:
                gap_s = t0 - self._last_exec_end
                self.inter_wu_gaps_s.append(gap_s)
                metrics.histogram(
                    "fleet.inter_wu_gap_ms", metrics.LATENCY_BUCKETS_MS,
                    unit="ms",
                ).observe(gap_s * 1e3)
            # per-Session attach: fresh retry budget, fresh fault
            # schedule, THIS session's incident log on the hang watchdog
            # — quarantine state stays per-WU, not per-server
            faultinject.configure()
            resilience.begin_run()
            incident_path = watchdog.default_incident_path(args.checkpointfile)
            watchdog.arm(
                incident_log=(
                    watchdog.IncidentLog(incident_path)
                    if incident_path else None
                )
            )
            hits0, misses0 = self.step_cache.hits, self.step_cache.misses
            try:
                try:
                    if prep_future is not None:
                        t_p = time.perf_counter()
                        with tracing.span("prep-wait"):
                            prep_future.result()
                        prep_s = time.perf_counter() - t_p
                    elif not session.prepared:
                        t_p = time.perf_counter()
                        session.prepare(n_mesh=1, dist=None)
                        prep_s = time.perf_counter() - t_p
                    code = session.execute(step_cache=self.step_cache)
                except Exception as e:  # mapped driver errors -> result
                    mapped = exit_code_for(e)
                    if mapped is None:
                        raise
                    erplog.error("%s\n", str(e))
                    if session.obs is not None and session.obs.flightrec.armed():
                        session.obs.flightrec.dump(
                            f"session-exit-{mapped}", exc=e
                        )
                    code = mapped
                    err = f"{type(e).__name__}: {e}"
            finally:
                watchdog.disarm()
                self._last_exec_end = time.perf_counter()
            wall = self._last_exec_end - t0
        recompiles = self._session_recompiles(session) - rec0
        metrics.counter("fleet.sessions").inc()
        if code != 0:
            metrics.counter("fleet.sessions_failed").inc()
        metrics.counter("fleet.session_wall_s", unit="s").inc(wall)
        if session.obs is not None:
            session.obs.close(
                code, context={
                    "session": name,
                    **({"corr_id": corr_id} if corr_id else {}),
                },
            )
        result = SessionResult(
            name=name,
            code=int(code) if code is not None else -1,
            outputfile=args.outputfile,
            corr_id=corr_id,
            error=err,
            wall_s=wall,
            prepare_s=prep_s,
            recompiles=recompiles,
            step_cache_hits=self.step_cache.hits - hits0,
            step_cache_misses=self.step_cache.misses - misses0,
        )
        if self.slo is not None:
            try:  # monitoring must never take down serving
                from ..serving.slo import slo_key

                self.slo.observe_session(
                    slo_key(args), result,
                    step_ms=[
                        r["ms"] for r in steptime.records(since=step_cursor)
                    ],
                    gap_s=gap_s,
                )
            except Exception:
                pass
        return result

    def process(self, args, *, corr_id: str | None = None) -> SessionResult:
        """build + prepare + execute, blocking — the in-process
        equivalent of one driver subprocess."""
        session = self.build_session(args, corr_id=corr_id)
        return self.execute(session)

    @staticmethod
    def _session_recompiles(session: Session) -> int:
        """The session's scoped view of the process compile count (the
        jax.monitoring listeners fan out to every live context, so a
        scoped window counts exactly the compiles inside it)."""
        if session.obs is None or not session.obs.metrics.enabled():
            return 0
        return int(
            session.obs.metrics.registry().counter("jax.recompiles").value
        )

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._prep_pool.shutdown(wait=True, cancel_futures=True)
