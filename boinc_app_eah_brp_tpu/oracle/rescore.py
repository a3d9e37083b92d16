"""Host-oracle rescoring of winning candidates (VERDICT r03 #5, r04 #8).

The device pipeline's candidate powers can differ from the compiled
reference by XLA's unconditional FP contraction (``llvm.fmuladd`` in the
phase chain flips ~1e-7-level nearest-neighbour indices; the reference
builds with ``no_ffp_contract.patch`` for exactly this reason — see
NOTES_r03 "Full-bank golden diff").  No XLA flag disables it.  Instead of
accepting a validator-tolerance mismatch class (~1/100 candidates at full
density), the driver erases it at the output boundary: after the
(M, T) -> toplist conversion, the <= 100 candidates that would be emitted
are re-scored through the bit-exact host oracle (``oracle/resample.py``'s
reference-semantics chain + NumPy FFT + point-evaluated harmonic sums),
so the written powers carry no device-contraction artifacts.

Cost: one oracle pipeline pass per *unique* winning template (~95 for a
full WU, ~1.8 s serial each at production size), run on a thread pool
(NumPy releases the GIL in the FFT and the big elementwise ops).  On a
CPU-class backend that is a few percent of WU wall; on a fast chip the
search itself is ~10 s (roofline: 686 t/s on v5e) and a *serial-at-the-
end* rescore would become the wall.  The fast-chip plan is OVERLAP:
:class:`IncrementalRescorer` piggybacks on the checkpoint cadence — every
committed checkpoint already fetches (M, T) and builds the current
toplist, so the driver hands that toplist to ``observe()``, which scores
any not-yet-scored winning template in the background WHILE the device
keeps searching.  By the final batch the winner set has long stabilized
(winners only churn near the fA threshold), so ``rescore_winners`` finds
nearly every template pre-scored in the cache and the end-of-run rescore
wall collapses to the few stragglers from the last checkpoint interval.
The scores are bit-identical either way: the per-template oracle pass is
deterministic and cached values are only reused for the exact
(template, harmonic, bin) triples they were computed for.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..runtime import faultinject, flightrec, metrics, tracing
from .harmonic import harmonic_power_at
from .pipeline import DerivedParams
from .resample import ResampleParams, resample
from .spectrum import power_spectrum


def rescore_enabled() -> bool:
    """ERP_RESCORE=off disables output-boundary rescoring (it is on by
    default; the golden-diff gate relies on it)."""
    return os.environ.get("ERP_RESCORE", "").strip().lower() not in (
        "off",
        "0",
        "none",
    )


def overlap_enabled() -> bool:
    """ERP_RESCORE_OVERLAP=off disables checkpoint-cadence background
    rescoring (on by default; harmless where rescoring itself is off)."""
    return os.environ.get("ERP_RESCORE_OVERLAP", "").strip().lower() not in (
        "off",
        "0",
        "none",
    )


def _template_key(P, tau, psi) -> tuple:
    return (np.float32(P), np.float32(tau), np.float32(psi))


def _winning_pairs(candidates_all: np.ndarray, emitted: np.ndarray):
    """(wanted, entry_key): ``wanted`` maps each unique winning template
    triple to the set of (k, f0) harmonic/bin pairs its toplist entries
    need; ``entry_key[i]`` is (tpl, k, f0) for patchable entries of
    ``candidates_all`` and None otherwise."""
    live = emitted[emitted["n_harm"] > 0]
    templates = {
        _template_key(r["P_b"], r["tau"], r["Psi"]) for r in live
    }
    wanted: dict[tuple, set] = {t: set() for t in templates}
    entry_key: list = []
    for i in range(len(candidates_all)):
        n_harm = int(candidates_all["n_harm"][i])
        tpl = _template_key(
            candidates_all["P_b"][i],
            candidates_all["tau"][i],
            candidates_all["Psi"][i],
        )
        if n_harm <= 0 or tpl not in wanted:
            entry_key.append(None)
            continue
        k = n_harm.bit_length() - 1
        f0 = int(candidates_all["f0"][i])
        wanted[tpl].add((k, f0))
        entry_key.append((tpl, k, f0))
    return wanted, entry_key


def _score_template(
    ts: np.ndarray, derived: DerivedParams, tpl: tuple, pairs
) -> dict:
    """One oracle pipeline pass for ``tpl``, point-evaluated at the
    requested (k, f0) pairs — the bit-exact reference-semantics chain."""
    P, tau, psi0 = tpl
    params = ResampleParams.from_template(
        P, tau, psi0, derived.dt, derived.nsamples, derived.n_unpadded
    )
    resampled, _, _ = resample(ts, params)
    ps = power_spectrum(resampled, 1.0 / derived.nsamples)
    return {
        (k, f0): harmonic_power_at(
            ps,
            f0,
            k,
            derived.window_2,
            derived.fundamental_idx_hi,
            derived.harmonic_idx_hi,
        )
        for (k, f0) in pairs
    }


def unique_winner_count(emitted: np.ndarray) -> int:
    """Number of distinct winning templates among the live emitted rows —
    the meaningful denominator for overlap-hit accounting.  The rescorer's
    cache also holds displaced ever-winners (templates that led at some
    checkpoint but lost their bins later), so ``len(cache)`` overstates
    how much of the FINAL winner set was pre-scored."""
    live = emitted[emitted["n_harm"] > 0]
    return len({_template_key(r["P_b"], r["tau"], r["Psi"]) for r in live})


def rescore_winners(
    ts: np.ndarray,
    candidates_all: np.ndarray,
    emitted: np.ndarray,
    derived: DerivedParams,
    max_workers: int | None = None,
    cache: dict | None = None,
) -> tuple[np.ndarray, int]:
    """Patch the 500-entry toplist with oracle powers for every template
    that appears among the ``emitted`` winners; returns (patched copy,
    number of fresh oracle template evaluations).

    ``cache`` (from :class:`IncrementalRescorer`): ``{tpl: {(k, f0):
    power}}`` of already-scored pairs.  A template re-runs its pipeline
    pass only for pairs the cache is missing; fully covered templates
    cost nothing here.

    The caller re-runs ``finalize_candidates`` on the patched toplist so
    the fA statistics, sigma scaling, sort and dedup all see the corrected
    raw powers (selection near the cap may legitimately shift — toward the
    reference's own ordering).
    """
    if len(emitted) == 0:
        return candidates_all, 0
    wanted, entry_key = _winning_pairs(candidates_all, emitted)
    if not wanted:
        return candidates_all, 0
    ts = np.asarray(ts, dtype=np.float32)
    cache = cache or {}

    scored: dict[tuple, dict] = {}
    todo: dict[tuple, set] = {}
    for tpl, pairs in wanted.items():
        have = cache.get(tpl, {})
        hit = {p: have[p] for p in pairs if p in have}
        missing = pairs - hit.keys()
        scored[tpl] = hit
        if missing:
            todo[tpl] = missing

    def one(tpl):
        with tracing.span("rescore-template"):
            return tpl, _score_template(ts, derived, tpl, todo[tpl])

    workers = max_workers or min(8, os.cpu_count() or 1, len(todo) or 1)
    if workers > 1 and len(todo) > 1:
        with ThreadPoolExecutor(max_workers=workers) as pool:
            fresh = dict(pool.map(one, sorted(todo)))
    else:
        fresh = dict(one(t) for t in sorted(todo))
    for tpl, pairs in fresh.items():
        scored[tpl].update(pairs)

    out = candidates_all.copy()
    for i, key in enumerate(entry_key):
        if key is None:
            continue
        tpl, k, f0 = key
        out["power"][i] = scored[tpl][(k, f0)]
    flightrec.record(
        "rescore", what="final", templates=len(wanted), fresh=len(fresh)
    )
    return out, len(fresh)


class IncrementalRescorer:
    """Overlap oracle rescoring with the device search (VERDICT r04 #8).

    The driver calls :meth:`observe` with the toplist each committed
    checkpoint already builds from the fetched (M, T) — zero extra
    device traffic.  Each observe computes the currently-emitted winner
    set (``finalize_candidates`` on 500 host entries, ~ms) and submits
    any template/pair not yet scored to a background thread pool.  The
    whitened host series is fetched LAZILY by the first worker (on the
    device-resident split path that is the one 17 MB d2h, overlapped
    with the remaining search instead of serializing after it).

    :meth:`finalize` drains the pool and returns the score cache for
    ``rescore_winners(cache=...)`` — which then only pays for pairs that
    appeared after the last checkpoint.  Displaced ever-winners waste a
    background pass each; that is the price of overlap and is bounded by
    winner churn, not bank size.
    """

    def __init__(
        self,
        get_ts,
        derived: DerivedParams,
        t_obs: float,
        max_workers: int | None = None,
    ):
        self._get_ts = get_ts
        self._derived = derived
        self._t_obs = float(t_obs)
        self._ts: np.ndarray | None = None
        self._ts_lock = threading.Lock()
        self._scored: dict[tuple, dict] = {}
        self._scored_lock = threading.Lock()
        self._pending: dict[tuple, set] = {}
        self._futures: list = []
        workers = max_workers or max(1, min(4, (os.cpu_count() or 1) - 1))
        self._pool: ThreadPoolExecutor | None = ThreadPoolExecutor(
            max_workers=workers
        )
        # single feed worker: serializes observes (``_pending`` needs no
        # lock) and keeps the toplist build off the dispatch thread
        self._feed: ThreadPoolExecutor | None = ThreadPoolExecutor(
            max_workers=1
        )
        self.observed = 0
        self.submitted = 0
        self.failed = 0

    def _series(self) -> np.ndarray:
        with self._ts_lock:
            if self._ts is None:
                self._ts = np.asarray(self._get_ts(), dtype=np.float32)
            return self._ts

    def _run(self, tpl: tuple, pairs: frozenset) -> None:
        scores = _score_template(self._series(), self._derived, tpl, pairs)
        with self._scored_lock:
            self._scored.setdefault(tpl, {}).update(scores)

    def observe(self, candidates_all: np.ndarray) -> None:
        """Submit unscored winners of the current toplist; non-blocking
        (caller-thread cost is the 500-entry finalize + set algebra)."""
        pool = self._pool
        if pool is None:
            return
        # an injected failure here propagates into this observe's futures
        # and is counted in finalize()'s `failed` tally — the end-of-run
        # rescore recomputes whatever the background pass lost, which is
        # exactly the degradation the harness wants to exercise
        faultinject.fault_point("rescore_feed", seq=self.observed + 1)
        from .toplist import finalize_candidates

        t0 = time.perf_counter()
        self.observed += 1
        metrics.counter("rescore.observes").inc()
        flightrec.record("rescore", what="observe", seq=self.observed)
        try:
            emitted = finalize_candidates(candidates_all, self._t_obs)
            if len(emitted) == 0:
                return
            wanted, _ = _winning_pairs(candidates_all, emitted)
            for tpl, pairs in wanted.items():
                with self._scored_lock:
                    have = set(self._scored.get(tpl, {}))
                missing = pairs - have - self._pending.get(tpl, set())
                if not missing:
                    continue
                self._pending.setdefault(tpl, set()).update(missing)
                self.submitted += 1
                metrics.counter("rescore.submitted").inc()
                try:
                    self._futures.append(
                        pool.submit(self._run, tpl, frozenset(missing))
                    )
                except RuntimeError:
                    # finalize()/abort() shut the pool down mid-observe; the
                    # end-of-run rescore recomputes whatever is missing
                    return
        finally:
            metrics.histogram(
                "rescore.observe_ms", metrics.LATENCY_BUCKETS_MS, unit="ms"
            ).observe((time.perf_counter() - t0) * 1e3)
            # backlog visible to the heartbeat: background passes queued
            # or running (each future is one template's scoring batch or
            # one queued feed observe)
            metrics.gauge("rescore.queue_depth").set(
                sum(1 for f in self._futures if not f.done())
            )

    def observe_async(self, build) -> None:
        """Feed the rescorer without blocking the dispatch thread:
        ``build()`` (the toplist construction from host state snapshots —
        relayout + threshold scan, ~10 ms at production size) runs on the
        dedicated feed worker, then flows into :meth:`observe`.  The
        caller must capture HOST copies in ``build``'s closure — by the
        time the worker runs, the next dispatched step has donated (and
        so invalidated) the device state buffers."""
        feed = self._feed
        if feed is None:
            return
        # propagate the dispatch window's trace context onto the feed
        # worker so its span lines up with the checkpoint that queued it
        ctx = tracing.context()

        def _feed_observe():
            tracing.set_context(ctx)
            from ..runtime import watchdog

            with watchdog.guard("rescore_feed"), tracing.span(
                "rescore-feed", tid="rescore-feed"
            ):
                self.observe(build())

        try:
            self._futures.append(feed.submit(_feed_observe))
        except RuntimeError:
            pass  # shutdown raced the submit; nothing to feed

    def finalize(self) -> dict:
        """Drain the feed worker and the pool; returns the score cache
        (tpl -> pairs).

        A failed worker only shrinks the cache — ``rescore_winners``
        recomputes whatever is missing, so the result is correct either
        way; ``failed`` is exposed for the driver's log line."""
        feed, self._feed = self._feed, None
        if feed is not None:
            # flush queued observes first: they submit scoring work
            feed.shutdown(wait=True)
        pool, self._pool = self._pool, None
        if pool is None:
            return self._scored
        pool.shutdown(wait=True)
        for f in self._futures:
            if f.exception() is not None:
                self.failed += 1
        if self.failed:
            metrics.counter("rescore.failed").inc(self.failed)
        metrics.gauge("rescore.queue_depth").set(0)
        return self._scored

    def series_if_fetched(self) -> np.ndarray | None:
        """The host series a worker already fetched, or None — lets the
        end-of-run rescore reuse it instead of paying a second d2h of
        the device-resident halves."""
        with self._ts_lock:
            return self._ts

    def abort(self) -> None:
        """Quit/error path: drop queued work, don't wait for results
        (a checkpointed resume rebuilds the winner set anyway).  Safe to
        call more than once and after :meth:`finalize`."""
        feed, self._feed = self._feed, None
        if feed is not None:
            feed.shutdown(wait=False, cancel_futures=True)
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=False, cancel_futures=True)
