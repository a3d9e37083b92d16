"""Template-bank sharding over an ICI mesh with ``shard_map``.

The reference runs one template at a time on one device
(``demod_binary.c:1180-1443``); its only multi-device story is BOINC handing
different *workunits* to different hosts. Here a global batch of ``n_dev *
per_dev`` templates runs per step: each device slices its block of the
device-resident parameter bank, vmaps it through the per-template pipeline,
reduces it to per-bin (max power, first-achieving template index), and the
shards are combined with a **recursive-doubling max/argmax all-reduce** over
the mesh axis — ceil(log2(n)) ``ppermute`` exchanges of the tiny
(5, fund_hi) state instead of gathering any spectra. The merged state is
replicated, so the host sees one consistent (M, T) after every step and
checkpointing/resume logic is identical to the single-chip path.

The feed contract matches ``models.search.run_bank``'s async pipeline: the
whole bank is uploaded once (replicated), each step receives only two int32
scalars, (M, T) are donated, and the host dispatches up to ``lookahead``
steps ahead before draining (JAX async dispatch keeps the mesh busy).

Tie-breaking matches the reference's keep-first-seen toplist semantics
(``demod_binary.c:1360``): strictly greater power wins; on equal power the
smaller global template index wins (shards hold contiguous ascending index
blocks, so "earlier shard" == "earlier template").

Padded batch slots (bank size not divisible by the global batch) are masked
to -inf before the block reduction so they can never claim a bin; validity
is derived on device from ``n_total``, never shipped from the host.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.search import (
    ExactMeanPrefetch,
    SearchGeometry,
    bank_params_host,
    init_state,
    prepare_ts,
    template_sumspec_fn,
    upload_bank,
    validate_bank_bounds,
)
from ..runtime import faultinject, flightrec, metrics, tracing
from ..runtime import watchdog as hangdog
from ..runtime.devicecost import stage_scope
from .mesh import TEMPLATE_AXIS

_NEG = np.float32(-3.0e38)  # sentinel below any real summed power


def _merge_take(oM, oT, M, T):
    """Elementwise lexicographic (power desc, template index asc) merge."""
    take = (oM > M) | ((oM == M) & (oT < T))
    return jnp.where(take, oM, M), jnp.where(take, oT, T)


def _allreduce_merge(axis_name: str, n: int, M, T):
    """Recursive-doubling all-reduce over a ring: after ceil(log2(n)) rounds
    of modular ppermute shifts (1, 2, 4, ...) every shard has merged a
    contiguous window of >= n ranks. The merge is idempotent (elementwise
    max with deterministic tie-break), so window wrap-around re-merging the
    same ranks is harmless — works for any n, not just powers of two."""
    with stage_scope("allreduce"):
        step = 1
        while step < n:
            perm = [(i, (i + step) % n) for i in range(n)]
            oM = jax.lax.ppermute(M, axis_name, perm)
            oT = jax.lax.ppermute(T, axis_name, perm)
            M, T = _merge_take(oM, oT, M, T)
            step *= 2
        return M, T


def make_sharded_batch_step(
    geom: SearchGeometry,
    mesh: Mesh,
    per_device_batch: int,
    axis_name: str = TEMPLATE_AXIS,
    with_health: bool = False,
):
    """Jitted (ts_args, btau, bomega, bpsi0, bs0, t_offset, n_total, M, T
    [, n_steps[B], mean[B]]) -> (M, T): the sharded twin of
    ``models.search.make_bank_step``.

    ``btau``.. are the :func:`upload_bank` device arrays of the whole bank,
    replicated over the mesh; each shard slices its ``per_device_batch``
    block at ``t_offset + shard * per_dev``, so the global batch is
    ``n_dev * per_dev`` contiguous templates with no per-batch parameter
    h2d. Validity of each slot (final partial batch) is computed on device
    from ``n_total``. ``t_offset`` is the global index of the batch's first
    template; returned ``T`` entries are global bank indices.

    (M, T) are donated — callers must treat the passed-in state as
    consumed. The ``n_steps``/``mean`` host-exact overrides (iff
    ``geom.exact_mean``) stay per-batch sharded operands.
    """
    per_template = template_sumspec_fn(geom)
    n_dev = mesh.shape[axis_name]
    per_dev = int(per_device_batch)

    def local_step(ts_args, btau, bomega, bpsi0, bs0, t_offset, n_total,
                   M, T, n_steps=None, mean=None):
        # ts_args, bank, t_offset, M, T replicated; each shard slices its
        # contiguous block of the bank
        shard = jax.lax.axis_index(axis_name).astype(jnp.int32)
        offset = t_offset + shard * per_dev
        with stage_scope("bank-slice"):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, offset, per_dev)
            tau, omega, psi0, s0 = sl(btau), sl(bomega), sl(bpsi0), sl(bs0)
        valid = offset + jnp.arange(per_dev, dtype=jnp.int32) < n_total
        if geom.exact_mean:
            sums = jax.vmap(
                lambda a, b, c, d, ns, mn: per_template(
                    ts_args, a, b, c, d, ns, mn
                )
            )(tau, omega, psi0, s0, n_steps, mean)
        else:
            sums = jax.vmap(
                lambda a, b, c, d: per_template(ts_args, a, b, c, d)
            )(tau, omega, psi0, s0)  # (per_dev, 5, W)
        with stage_scope("merge"):
            masked = jnp.where(valid[:, None, None], sums, _NEG)
            bmax = jnp.max(masked, axis=0)
            barg = jnp.argmax(masked, axis=0).astype(jnp.int32)  # first max in block
            btidx = offset + barg
        bmax, btidx = _allreduce_merge(axis_name, n_dev, bmax, btidx)
        with stage_scope("merge"):
            # fold into the carried state: carry indices are always smaller
            # (earlier batches), so strict > keeps first-seen on ties
            better = bmax > M
            Mn = jnp.where(better, bmax, M)
            Tn = jnp.where(better, btidx, T)
        if not with_health:
            return Mn, Tn
        with stage_scope("health"):
            # mesh-global health scalars (runtime/health.py): the per-shard
            # stats are reduced over the axis so the watchdog sees the whole
            # global batch; Mn is already replicated post all-reduce
            validb = valid[:, None, None]
            fin = jnp.isfinite(sums)
            nf_local = jnp.sum((validb & ~fin).astype(jnp.int32))
            ok = validb & fin
            fmax_local = jnp.max(jnp.where(ok, sums, _NEG))
            fmin_local = jnp.min(jnp.where(ok, sums, -_NEG))
            nf_batch = jax.lax.psum(nf_local, axis_name)
            fmax = jax.lax.pmax(fmax_local, axis_name)
            fmin = jax.lax.pmin(fmin_local, axis_name)
            nf_state = jnp.sum((~jnp.isfinite(Mn)).astype(jnp.int32))
            health = jnp.stack(
                [
                    nf_batch.astype(jnp.float32),
                    nf_state.astype(jnp.float32),
                    fmax,
                    fmin,
                ]
            )
        return Mn, Tn, health

    in_specs = [
        P(),  # ts_args (tuple; replicated leaves)
        P(),  # btau (bank-resident, replicated)
        P(),  # bomega
        P(),  # bpsi0
        P(),  # bs0
        P(),  # t_offset
        P(),  # n_total
        P(),  # M
        P(),  # T
    ]
    if geom.exact_mean:
        in_specs += [P(axis_name), P(axis_name)]  # n_steps, mean
    out_specs = (P(), P(), P()) if with_health else (P(), P())
    # check_vma off: the ppermute butterfly yields replicated outputs the
    # checker can't prove
    sharded = jax.shard_map(
        local_step, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=out_specs, check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(7, 8))


def run_bank_sharded(
    ts: np.ndarray,
    bank_P: np.ndarray,
    bank_tau: np.ndarray,
    bank_psi0: np.ndarray,
    geom: SearchGeometry,
    mesh: Mesh,
    per_device_batch: int = 16,
    axis_name: str = TEMPLATE_AXIS,
    state=None,
    start_template: int = 0,
    stop_template: int | None = None,
    progress_cb=None,
    lookahead: int = 2,
):
    """Resilient wrapper around the sharded dispatch loop.

    Same recovery ladder as ``models.search.run_bank`` (minus the Pallas
    rung — the sharded step has no Pallas path): transient failures
    restart from the last host snapshot, device OOM halves the
    PER-DEVICE batch, all bounded by the shared per-run retry budget.
    ``ERP_RETRY_BUDGET=0`` disables wrapper and snapshot d2h alike.  See
    :func:`_run_bank_sharded_attempt` for the loop contract.

    ``stop_template`` bounds the covered range to ``[start_template,
    stop_template)`` — the multi-host path runs one such window per shard
    lease (``parallel/elastic.py``); None keeps the whole-bank behavior.
    """
    from ..runtime import resilience

    pol = resilience.policy()
    if pol is None:
        return _run_bank_sharded_attempt(
            ts, bank_P, bank_tau, bank_psi0, geom, mesh,
            per_device_batch=per_device_batch, axis_name=axis_name,
            state=state, start_template=start_template,
            stop_template=stop_template,
            progress_cb=progress_cb, lookahead=lookahead,
        )
    snap = resilience.DispatchSnapshot(state, start_template)
    ladder = resilience.DegradationLadder(pol, per_device_batch)
    cur_state, cur_start = state, start_template
    while True:
        try:
            return _run_bank_sharded_attempt(
                ts, bank_P, bank_tau, bank_psi0, geom, mesh,
                per_device_batch=ladder.batch_size, axis_name=axis_name,
                state=cur_state, start_template=cur_start,
                stop_template=stop_template,
                progress_cb=progress_cb, lookahead=lookahead,
                snapshot=snap,
            )
        except Exception as e:
            if not ladder.record_failure("dispatch", e):
                raise
            ladder.sleep()
            # failed donated dispatch: rebuild replicated state from the
            # snapshot's host copies and re-dispatch from the last commit
            host_state, cur_start = snap.restore()
            cur_state = (
                None
                if host_state is None
                else (jnp.asarray(host_state[0]), jnp.asarray(host_state[1]))
            )
            flightrec.record(
                "redispatch", start=cur_start,
                per_device_batch=ladder.batch_size, attempt=ladder.attempt,
            )


def _run_bank_sharded_attempt(
    ts: np.ndarray,
    bank_P: np.ndarray,
    bank_tau: np.ndarray,
    bank_psi0: np.ndarray,
    geom: SearchGeometry,
    mesh: Mesh,
    per_device_batch: int = 16,
    axis_name: str = TEMPLATE_AXIS,
    state=None,
    start_template: int = 0,
    stop_template: int | None = None,
    progress_cb=None,
    lookahead: int = 2,
    snapshot=None,
):
    """Async dispatch loop over mesh-wide template batches; same contract
    as ``models.search.run_bank`` (global template indices in ``T``,
    ``progress_cb`` sees live device arrays and may stop early, dispatch
    runs up to ``lookahead`` steps ahead) but each step covers
    ``n_dev * per_device_batch`` templates.

    ``stop_template`` caps the covered range for shard-windowed runs: the
    device ``n_total`` operand becomes the window end, so templates past
    it are masked exactly like final-batch padding.  ``n_total`` is a
    traced scalar operand — a different window reuses the one compiled
    step unchanged.

    Every step runs at the same static shape — short banks just carry more
    masked padding — so there is exactly one compilation.
    """
    validate_bank_bounds(geom, bank_P, bank_tau, bank_psi0)
    from ..runtime.health import watchdog as _make_watchdog

    wd = _make_watchdog()
    step = make_sharded_batch_step(
        geom, mesh, per_device_batch, axis_name, with_health=wd is not None
    )
    if state is None:
        state = init_state(geom)
    M, T = state
    ts_np = np.asarray(ts, dtype=np.float32)
    ts_args = prepare_ts(geom, ts_np)

    n = len(bank_P)
    n_stop = n if stop_template is None else min(n, int(stop_template))
    n_dev = mesh.shape[axis_name]
    B = n_dev * per_device_batch
    params = bank_params_host(bank_P, bank_tau, bank_psi0, geom.dt)
    faultinject.fault_point("h2d", loop="run_bank_sharded")
    dev_bank = upload_bank(params, B)
    # the series, bank and state live replicated on every mesh device
    # (P() specs): placed once here, not re-sent from device 0 per step
    rep = NamedSharding(mesh, P())
    ts_args, dev_bank, M, T = jax.device_put((ts_args, dev_bank, M, T), rep)
    n_total = jnp.int32(n_stop)
    lookahead = max(1, int(lookahead))
    starts = range(start_template, n_stop, B)

    # per-shard batch timing lands in its own histogram so mesh runs are
    # distinguishable from the single-chip loop in a run report; shared
    # counters (templates, stalls, occupancy) use the search.* names
    metrics.gauge("sharded.mesh_devices").set(int(n_dev))
    metrics.gauge("sharded.per_device_batch").set(int(per_device_batch))
    m_batches = metrics.counter("search.batches")
    m_templates = metrics.counter("search.templates")
    m_dispatch_s = metrics.counter("search.dispatch_wall_s", unit="s")
    m_stall_s = metrics.counter("search.drain_stall_s", unit="s")
    m_prefetch_s = metrics.counter("search.prefetch_wait_s", unit="s")
    m_h2d = metrics.counter("search.h2d_bytes", unit="B")
    m_batch_ms = metrics.histogram(
        "sharded.batch_ms", metrics.LATENCY_BUCKETS_MS, unit="ms"
    )
    m_stall_ms = metrics.histogram(
        "search.drain_stall_ms", metrics.LATENCY_BUCKETS_MS, unit="ms"
    )
    m_occupancy = metrics.histogram(
        "search.lookahead_occupancy", metrics.OCCUPANCY_BUCKETS
    )
    m_h2d.inc(sum(int(a.nbytes) for a in dev_bank) + int(ts_np.nbytes))

    prefetch = None
    if geom.exact_mean:
        prefetch = ExactMeanPrefetch(
            ts_np, params, geom, starts, B, depth=lookahead
        )
    inflight = 0
    try:
        for start in starts:
            # one trace context per dispatch window (runtime/tracing.py)
            tracing.new_context()
            stop = min(start + B, n_stop)
            args = [ts_args, *dev_bank, jnp.int32(start), n_total, M, T]
            if prefetch is not None:
                t0 = time.perf_counter()
                with tracing.span("prefetch-wait", start=start):
                    ns, mn = prefetch.get(start)
                m_prefetch_s.inc(time.perf_counter() - t0)
                ns, mn = np.asarray(ns), np.asarray(mn)
                m_h2d.inc(int(ns.nbytes) + int(mn.nbytes))
                args += [jnp.asarray(ns), jnp.asarray(mn)]
            t0 = time.perf_counter()
            with hangdog.guard("dispatch", start=start, stop=stop):
                faultinject.fault_point("dispatch", start=start, stop=stop)
                with tracing.span("dispatch", start=start, stop=stop):
                    if wd is not None:
                        M, T, health_vec = step(*args)
                        wd.push(start, stop, health_vec)
                    else:
                        M, T = step(*args)
            dt_dispatch = time.perf_counter() - t0
            m_dispatch_s.inc(dt_dispatch)
            m_batch_ms.observe(dt_dispatch * 1e3)
            inflight += 1
            m_occupancy.observe(inflight)
            m_batches.inc()
            m_templates.inc(stop - start)
            flightrec.record(
                "dispatch", start=start, stop=stop,
                ms=round(dt_dispatch * 1e3, 3),
            )
            flightrec.note_dispatch(
                loop="run_bank_sharded", start=start, stop=stop,
                n_total=n_stop,
                mesh_devices=n_dev, per_device_batch=per_device_batch,
                inflight=inflight, lookahead=lookahead,
            )
            if inflight >= lookahead:
                t0 = time.perf_counter()
                with hangdog.guard("drain", stop=stop), tracing.span(
                    "drain", stop=stop
                ):
                    jax.block_until_ready(M)
                dt_stall = time.perf_counter() - t0
                m_stall_s.inc(dt_stall)
                m_stall_ms.observe(dt_stall * 1e3)
                flightrec.record(
                    "drain", stop=stop, stall_ms=round(dt_stall * 1e3, 3)
                )
                inflight = 0
                if snapshot is not None:
                    # drained = every template before `stop` is merged into
                    # (M, T); commit the host-side recovery point here
                    snapshot.maybe_commit(M, T, stop)
            if wd is not None:
                wd.maybe_check("run_bank_sharded")
            if progress_cb is not None:
                with tracing.span("progress", stop=stop):
                    go_on = progress_cb(stop, n_stop, M, T)
                if go_on is False:
                    break
        if wd is not None:
            wd.check("run_bank_sharded")
    finally:
        if prefetch is not None:
            prefetch.close()
    return M, T
