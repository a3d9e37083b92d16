"""Per-workunit Session: the unit of work the serving tier schedules.

Historically ``runtime/driver.py`` was a monolithic per-WU main — one
process, one workunit, exit.  The fleet serving tier (ROADMAP item 3,
``docs/serving.md``) runs MANY workunits through one resident process,
so the per-WU state and logic live here as a :class:`Session`:

* **state**: parsed bank, checkpoint resume point, quarantine ranges,
  workunit samples, whitened series, search geometry, toplist seeds,
  result paths — everything owned by exactly one WU;
* **phases**: :meth:`prepare` (host-side parse/whiten/geometry — safe to
  run on a prep thread while the previous Session drains the device)
  and :meth:`execute` (the dispatch loop, checkpoint cadence, rescore
  and the atomic result write);
* **observability**: an optional scoped ``runtime/obs.ObsContext`` so a
  fleet Session's lifecycle events, black box and ``jax.recompiles``
  window never bleed into a neighbouring Session's artifacts;
* **environment**: a :class:`SessionEnv` snapshot taken at construction
  — ``ERP_LOOKAHEAD`` / ``ERP_CHECKPOINT_PERIOD`` / knobs are re-read
  per Session instead of captured once per process, so a resident
  server picks up config changes between WUs (the config-staleness fix
  ISSUE 13 names).

The one-process-per-WU driver path (``runtime/driver.py``) now builds a
Session per run and delegates; its observable behaviour — log lines,
artifacts, error codes, result bytes — is unchanged.  The resident path
(``runtime/scheduler.py``) builds one Session per queued WU and passes
a shared step cache (``models/search.py::step_cache_key``) so
same-geometry WUs reuse compiled executables: zero recompiles after
warmup.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass

import numpy as np

from ..io.checkpoint import (
    Checkpoint,
    empty_candidates,
    load_resumable_checkpoint,
    topology_record,
    write_checkpoint,
)
from ..io.formats import N_BINS_SS, N_CAND
from ..io.results import ResultFile, ResultHeader, write_result_file
from ..io.templates import read_template_bank
from ..io.workunit import read_workunit
from ..io.zaplist import read_zaplist
from ..oracle.pipeline import DerivedParams, SearchConfig
from ..oracle.stats import base_thresholds
from ..oracle.toplist import finalize_candidates, update_toplist_from_maxima
from . import flightrec, metrics, profiling, resilience, steptime, tracing, watchdog
from . import logging as erplog
from .boinc import BoincAdapter
from .errors import (
    RADPUL_EFILE,
    RADPUL_EIO,
    RADPUL_EVAL,
    RADPUL_TEMPORARY_EXIT,
    RadpulError,
)
from .health import HealthError


def sky_position_radians(header) -> tuple[float, float]:
    """HHMMSS.S / DDMMSS.S -> radians (``demod_binary.c:746-771``)."""
    ra = float(header["RA"])
    hrs = math.floor(ra / 10000.0)
    mins = math.floor((ra - 10000.0 * hrs) / 100.0)
    sec = ra - 10000.0 * hrs - 100.0 * mins
    rac = math.pi * (hrs / 12.0 + mins / 720.0 + sec / 43200.0)

    dec = float(header["DEC"])
    if dec < 0.0:
        hrs = math.floor(-dec / 10000.0)
        mins = math.floor(-(dec + 10000.0 * hrs) / 100.0)
        sec = -(dec + 10000.0 * hrs + 100.0 * mins)
        decr = -math.pi * (hrs / 180.0 + mins / 10800.0 + sec / 648000.0)
    else:
        hrs = math.floor(dec / 10000.0)
        mins = math.floor((dec - 10000.0 * hrs) / 100.0)
        sec = dec - 10000.0 * hrs - 100.0 * mins
        decr = math.pi * (hrs / 180.0 + mins / 10800.0 + sec / 648000.0)
    return rac, decr


def binned_spectrum(sumspec4: np.ndarray, fund_hi: int) -> bytes:
    """40-bin screensaver downsample of the 4-harmonic spectrum
    (``demod_binary.c:1383-1393``)."""
    powerscale = 100.0 / 255.0
    stepscale = float(N_BINS_SS) / float(fund_hi)
    bins = (stepscale * np.arange(len(sumspec4))).astype(np.int32)
    # bins is nondecreasing: one segmented max per screensaver bin
    boundaries = np.searchsorted(bins, np.arange(N_BINS_SS), side="left")
    out = np.zeros(N_BINS_SS, dtype=np.uint8)
    valid = boundaries < len(sumspec4)
    seg_max = np.zeros(N_BINS_SS, dtype=np.float32)
    if valid.any():
        seg_max[valid] = np.maximum.reduceat(sumspec4, boundaries[valid])
    out[:] = np.minimum(seg_max / powerscale, 255.0).astype(np.uint8)
    return out.tobytes()


def _dump_header(h) -> None:
    """Debug header dump (``demod_binary.c:706-737``)."""
    erplog.info("Header contents:\n")
    for label, key in [
        ("Original WAPP file: %s", "originalfile"),
        ("Sample time in microseconds: %g", "tsample"),
        ("Observation time in seconds: %.8g", "tobs"),
        ("Time stamp (MJD): %.17g", "timestamp"),
        ("Center freq in MHz: %.10g", "fcenter"),
        ("RA (J2000): %.12g", "RA"),
        ("DEC (J2000): %.12g", "DEC"),
        ("Number of samples: %d", "nsamples"),
        ("Trial dispersion measure: %g cm^-3 pc", "DM"),
        ("Scale factor: %g", "scale"),
    ]:
        value = h[key]
        if value.dtype.kind == "S":
            value = bytes(value).split(b"\x00", 1)[0].decode("latin-1")
        elif "%d" in label:
            value = int(value)
        else:
            value = float(value)
        erplog.log_message(erplog.Level.INFO, False, label + "\n", value)


def _dump_thresholds(fA: float, fft_size: int) -> None:
    """Debug threshold dump (``demod_binary.c:1155-1166``)."""
    from ..oracle.stats import chisq_Qinv, single_bin_prob

    prob = float(single_bin_prob(fA, fft_size))
    erplog.info("Derived global search parameters:\n")
    erplog.log_message(erplog.Level.INFO, False, "f_A probability = %g\n", fA)
    erplog.log_message(
        erplog.Level.INFO, False, "single bin prob(P_noise > P_thr) = %g\n", prob
    )
    for label, nu in [("thr1", 2.0), ("thr2", 4.0), ("thr4", 8.0), ("thr8", 16.0), ("thr16", 32.0)]:
        erplog.log_message(
            erplog.Level.INFO, False, "%s = %g\n", label, 0.5 * chisq_Qinv(prob, int(nu))
        )


def _samples_to_host(samples, scale: float | None = None) -> np.ndarray:
    """Host float32 series from either form the search consumes: the
    device-resident (even, odd) parity halves (single-device whitened
    path) are fetched and re-interleaved; anything else is a plain
    host/device array.

    ``scale``: the deferred whitening renormalization (Session.ts_scale)
    when the resident resample chain shipped the series unscaled — the
    host view re-applies it so the oracle-facing consumers (sentinel
    probe, rescorer) see exactly the renormalized bits the non-deferred
    path would have produced (same IEEE f32 multiply)."""
    if isinstance(samples, tuple):
        ev = np.asarray(samples[0], dtype=np.float32)
        od = np.asarray(samples[1], dtype=np.float32)
        out = np.empty(len(ev) + len(od), dtype=np.float32)
        out[0::2] = ev
        out[1::2] = od
    else:
        out = np.asarray(samples, dtype=np.float32)
    if scale is not None:
        out = out * np.float32(scale)
    return out


def _state_to_candidates(M, T, params_P, params_tau, params_psi, base_thr, geom):
    from ..models.search import state_to_natural

    return update_toplist_from_maxima(
        empty_candidates(),
        state_to_natural(M, geom),
        state_to_natural(T, geom),
        params_P,
        params_tau,
        params_psi,
        base_thr,
        geom.window_2,
    )


def exit_code_for(e: BaseException) -> int | None:
    """The RADPUL_* exit code the driver maps ``e`` to, or None for an
    exception outside the mapped set (which then propagates).  One
    shared table so the subprocess driver (``runtime/driver.py``) and
    the resident serving tier (``runtime/scheduler.py``) classify
    failures identically."""
    from ..io.checkpoint import CheckpointError
    from ..io.templates import TemplateBankError

    if isinstance(e, RadpulError):
        return e.code
    if isinstance(e, CheckpointError):
        return RADPUL_EFILE
    if isinstance(e, TemplateBankError):
        return RADPUL_EVAL
    if isinstance(e, HealthError):
        # watchdog abort (ERP_HEALTH_ACTION=abort): numerics are wrong,
        # same class as a validation failure
        return RADPUL_EVAL
    if isinstance(e, ValueError):
        return RADPUL_EVAL
    if isinstance(e, FileNotFoundError):
        return RADPUL_EIO
    if isinstance(e, EOFError):
        return RADPUL_EIO
    return None


@dataclass(frozen=True)
class SessionEnv:
    """Per-Session snapshot of the runtime env knobs a resident server
    must re-read between workunits.

    The one-process driver read these mid-run (``ERP_LOOKAHEAD`` deep in
    ``_run_search``) or at adapter construction (checkpoint cadence in
    ``runtime/boinc.py``) — equivalent for a process that lives exactly
    one WU, silently stale for a server that lives thousands.  Captured
    once per Session at construction: a knob change applies from the
    next WU on, never mid-dispatch."""

    lookahead: int = 2
    checkpoint_period_s: float = 60.0
    progress_min_delta: float = 0.001

    @classmethod
    def capture(cls) -> "SessionEnv":
        from .boinc import _default_checkpoint_period, _default_progress_min_delta

        try:
            lookahead = max(1, int(os.environ.get("ERP_LOOKAHEAD", "2")))
        except ValueError:
            lookahead = 2
        return cls(
            lookahead=lookahead,
            checkpoint_period_s=_default_checkpoint_period(),
            progress_min_delta=_default_progress_min_delta(),
        )

    def make_adapter(self) -> BoincAdapter:
        """A fresh BOINC adapter honouring this snapshot's cadence."""
        return BoincAdapter(
            checkpoint_period_s=self.checkpoint_period_s,
            progress_min_delta=self.progress_min_delta,
        )


class Session:
    """One workunit's search, resumable-checkpoint to result file.

    ``args`` is a ``runtime/driver.DriverArgs`` (duck-typed; only its
    fields are read).  ``adapter`` defaults to a fresh
    :class:`BoincAdapter` built from the :class:`SessionEnv` snapshot.
    ``obs`` is an optional scoped ``ObsContext`` — the serving tier's
    per-Session observability bundle; the classic driver path leaves it
    None and keeps using the process-global layers.  ``corr_id`` threads
    the fabric's workunit correlation id through the Session's scoped
    artifacts (the subprocess path passes it via ``$ERP_CORR_ID``
    instead).
    """

    def __init__(
        self,
        args,
        adapter: BoincAdapter | None = None,
        *,
        env: SessionEnv | None = None,
        obs=None,
        corr_id: str | None = None,
        init_data=None,
    ):
        self.args = args
        self.env = env or SessionEnv.capture()
        self.adapter = adapter or self.env.make_adapter()
        self.obs = obs
        self.corr_id = corr_id or os.environ.get(metrics.CORR_ID_ENV) or None
        self.init_data = init_data
        self.prepared = False
        self.ts_scale = None  # deferred-renorm scale, set by prepare()

    # -- scoped-observability helpers -------------------------------------

    def _obs_record(self, event: str, **fields) -> None:
        """Lifecycle breadcrumb into the Session's OWN black box (no-op
        without a scoped bundle — the classic path's flightrec keeps its
        existing record points)."""
        if self.obs is None:
            return
        if self.corr_id:
            fields.setdefault("corr_id", self.corr_id)
        self.obs.flightrec.record(event, **fields)

    # -- phase 1: host-side preparation -----------------------------------

    def prepare(self, n_mesh: int = 1, dist=None) -> "Session":
        """Parse, validate and stage everything the dispatch loop needs:
        template bank, checkpoint resume point, quarantine ranges, the
        workunit itself, whitening, search geometry, batch size and the
        (virtual-template-seeded) initial state.

        Host-dominated by design: a resident scheduler runs this for WU
        k+1 on a prep thread while WU k drains the device (the cross-WU
        analogue of the exact-mean prefetch).  ``n_mesh``/``dist`` come
        from the caller because device selection is process-scoped, not
        Session-scoped."""
        # one span on the thread that prepares (the scheduler's prep pool
        # under the FleetServer), closed there
        with tracing.span("prepare"):
            self._prepare(n_mesh, dist)
        return self

    def _prepare(self, n_mesh: int, dist) -> None:
        args = self.args
        self._n_mesh = n_mesh
        self._dist = dist
        self._obs_record(
            "session-prepare", inputfile=args.inputfile,
            templatebank=args.templatebank,
        )

        # --- template bank: full parse doubles as validation
        # (demod_binary.c:507-544)
        with tracing.span("parse"):
            bank = read_template_bank(args.templatebank)
        template_total = len(bank)
        erplog.debug("Total amount of templates: %d\n", template_total)
        # fold out-of-range initial phases into [0, 2pi) once, up front:
        # the reference's LUT wraps per element (erp_utilities.cpp:176-209),
        # the blocked device LUT wants a nonnegative span — in-range banks
        # pass through bit-identical (models/search.py::normalize_psi0)
        from ..models.search import normalize_psi0

        psi0_n = normalize_psi0(bank.psi0)
        if not np.array_equal(psi0_n, bank.psi0):
            erplog.info(
                "Template bank psi0 values outside [0, 2pi) folded into range.\n"
            )
            from ..io.templates import TemplateBank

            bank = TemplateBank(bank.P, bank.tau, psi0_n)
        self.bank = bank
        self.template_total = template_total

        # --- checkpoint resume (demod_binary.c:546-652), walking the
        # on-disk generations newest-first so a corrupt latest checkpoint
        # falls back to the previous one instead of killing the run
        start_template = 0
        seed_cands = None
        process_count = dist.num_processes if dist is not None else 1
        resumed = (
            load_resumable_checkpoint(
                args.checkpointfile,
                template_total,
                args.inputfile,
                bank_path=args.templatebank,
                process_count=process_count,
            )
            if args.checkpointfile
            else None
        )
        if resumed is not None:
            cp, used_path, generation = resumed
            flightrec.record(
                "resume",
                n_template=cp.n_template,
                path=used_path,
                generation=generation,
            )
            if cp.n_template == template_total:
                erplog.info(
                    "Thank you but this work unit has already been processed completely...\n"
                )
            else:
                erplog.info(
                    "Continuing work on %s at template no. %d\n",
                    cp.originalfile,
                    cp.n_template,
                )
            start_template = cp.n_template
            seed_cands = cp.candidates
        else:
            erplog.info("Checkpoint file unavailable: %s\n", args.checkpointfile)
            erplog.log_message(erplog.Level.INFO, False, "Starting from scratch...\n")
        self.start_template = start_template
        self._process_count = process_count

        # --- poison-range quarantine (runtime/watchdog.py): template
        # windows that wedged/crashed the worker K times get skipped,
        # loudly and with provenance, instead of crash-looping forever —
        # the per-host analogue of BOINC's server-side per-WU error limit.
        # Single-host mode only: an elastic run's wedged ranges are
        # adopted by surviving hosts (a per-host incident tally would
        # punch gaps into coverage peers would have completed), so there
        # the lease board is the recovery story
        quarantined: list[tuple[int, int]] = []
        incident_path = watchdog.default_incident_path(args.checkpointfile)
        if incident_path and dist is None:
            raw_q = watchdog.IncidentLog(incident_path).quarantined()
            quarantined = [
                (max(0, a), min(template_total, b))
                for a, b in raw_q
                if a < template_total and b > 0 and max(0, a) < min(template_total, b)
            ]
        if quarantined:
            n_quarantined = sum(b - a for a, b in quarantined)
            metrics.counter("resilience.quarantined").inc(n_quarantined)
            flightrec.record(
                "quarantine", ranges=[[a, b] for a, b in quarantined]
            )
            erplog.warn(
                "Quarantined %d poison template(s) after repeated incidents: "
                "%s — skipping them, the gap is recorded in checkpoint and "
                "result provenance.\n",
                n_quarantined,
                ", ".join(f"[{a}, {b})" for a, b in quarantined),
            )
        self.quarantined = quarantined

        # --- workunit
        with tracing.span("parse"):
            wu = read_workunit(args.inputfile)
        samples = wu.samples
        if args.debug:
            _dump_header(wu.header)
        cfg = SearchConfig(
            f0=args.f0, padding=args.padding, fA=args.fA, window=args.window, white=args.white
        )
        derived = DerivedParams.derive(wu.nsamples, float(wu.header["tsample"]), cfg)

        # --- geometry (before whitening: the resident resample chain may
        # ask whitening to defer its final renormalization, a decision
        # gated on the geometry; models/search.resident_defers_renorm)
        from ..models.search import (
            SearchGeometry,
            init_state,
            lut_step_for_bank,
            lut_tiles_for_bank,
            max_slope_for_bank,
            resident_defers_renorm,
        )

        geom = SearchGeometry.from_derived(
            derived,
            use_lut=args.use_lut,
            max_slope=max_slope_for_bank(bank.P, bank.tau),
            lut_step=lut_step_for_bank(bank.P, derived.dt),
            lut_tiles=lut_tiles_for_bank(
                bank.P, bank.psi0, derived.n_unpadded, derived.dt
            ),
            # unwhitened data: replicate the reference's serial-f32 padding
            # mean on host (bit-parity; see SearchGeometry.exact_mean) —
            # whitened series are zero-mean and skip the host pass
            exact_mean=not cfg.white,
        )

        # --- whitening + RFI zapping (demod_binary.c:856-1079)
        # resident chain active on the packed device-split path: whitening
        # skips its sqrt(nsamples) renorm and the search step folds the
        # multiply into the resampler's gather (bitwise identical; the
        # host-facing views re-apply it via self.ts_scale)
        defer = args.white and n_mesh == 1 and resident_defers_renorm(geom)
        if args.white:
            from ..ops.whiten import whiten_and_zap

            if not args.zaplistfile:
                raise RadpulError(RADPUL_EFILE, "Whitening requires a zaplist file (-l).")
            zap_ranges = read_zaplist(args.zaplistfile)
            with profiling.phase("whitening", span="whiten"):
                # single-device searches keep the whitened parity halves
                # resident on device (no d2h/h2d round-trip; ops/whiten.py);
                # the mesh path still takes the host array for sharding.
                # 4-bit workunits ship the packed payload and split nibbles
                # on device — ~8x less H2D (ops/unpack.py)
                samples = whiten_and_zap(
                    samples, derived, cfg, zap_ranges,
                    return_device_split=(n_mesh == 1),
                    packed_payload=wu.raw,
                    packed_scale=float(wu.header["scale"]),
                    defer_renorm=defer,
                )
        if defer:
            import dataclasses

            geom = dataclasses.replace(geom, ts_prescaled=False)
        self.ts_scale = (
            float(np.sqrt(np.float32(derived.nsamples))) if defer else None
        )
        self.wu = wu
        self.samples = samples
        self.cfg = cfg
        self.derived = derived
        self.geom = geom
        self.base_thr = base_thresholds(cfg.fA, derived.fft_size)
        if args.debug:
            _dump_thresholds(cfg.fA, derived.fft_size)

        # sentinel drift probe (runtime/health.py): K fixed templates
        # re-run device-vs-oracle at checkpoint cadence, armed only when
        # the health watchdog itself is on (ERP_HEALTH_EVERY > 0)
        from .health import SentinelProbe, sentinel_count
        from .health import watchdog as make_watchdog

        sentinel = None
        sentinel_wd = make_watchdog()
        if (
            sentinel_wd is not None
            and sentinel_count() > 0
            and template_total > 0
        ):
            sentinel = SentinelProbe(
                lambda: _samples_to_host(self.samples, self.ts_scale),
                bank.P,
                bank.tau,
                bank.psi0,
                geom,
                derived,
                sentinel_wd,
            )
            erplog.debug(
                "Sentinel drift probe armed: templates %s.\n",
                sentinel.indices.tolist(),
            )
        self.sentinel = sentinel

        # batch size: pinned by --batch, else measured-sweep/memory-model
        # auto (runtime/autobatch.py); the choice is logged either way
        # (VERDICT r03 weak #3: "nothing records what the driver actually
        # used")
        from .autobatch import choose_batch

        if args.batch_size is not None:
            batch_size = args.batch_size
            erplog.info("Batch size %d (--batch).\n", batch_size)
        else:
            batch_size = choose_batch(geom.nsamples, log=erplog.info)
        self.batch_size = batch_size

        # bank params extended with checkpoint "virtual templates" for
        # resume
        from ..models.search import state_from_natural, state_to_natural

        params_P = bank.P.astype(np.float32)
        params_tau = bank.tau.astype(np.float32)
        params_psi = bank.psi0.astype(np.float32)
        M, T = init_state(geom)
        if seed_cands is not None:
            params_P = np.concatenate([params_P, seed_cands["P_b"].astype(np.float32)])
            params_tau = np.concatenate([params_tau, seed_cands["tau"].astype(np.float32)])
            params_psi = np.concatenate([params_psi, seed_cands["Psi"].astype(np.float32)])
            # seed in natural bin order, then back to the device layout
            M = state_to_natural(M, geom)
            T = state_to_natural(T, geom)
            for idx in range(N_CAND):
                n_harm = int(seed_cands["n_harm"][idx])
                if n_harm == 0:
                    continue
                k = n_harm.bit_length() - 1
                f0_bin = int(seed_cands["f0"][idx])
                power = np.float32(seed_cands["power"][idx])
                if f0_bin < geom.fund_hi and power > M[k, f0_bin]:
                    M[k, f0_bin] = power
                    T[k, f0_bin] = template_total + idx
            M = state_from_natural(M, geom)
            T = state_from_natural(T, geom)
        self.params_P = params_P
        self.params_tau = params_tau
        self.params_psi = params_psi
        self._seed_state = (M, T)

        rac, decr = sky_position_radians(wu.header)
        self.search_info = {
            "skypos_rac": rac,
            "skypos_dec": decr,
            "dispersion_measure": float(wu.header["DM"]),
        }
        self.prepared = True

    # -- phase 2: the search + finalize -----------------------------------

    def execute(self, step_cache=None) -> int:
        """Run the (prepared) search to completion: dispatch loop,
        checkpoint cadence, progress/screensaver reporting, oracle
        rescore, atomic result write.  Returns 0 or raises one of the
        exceptions :func:`exit_code_for` maps.

        ``step_cache`` (``models/search.py::step_cache_key`` -> jitted
        step) is the residency hook: the scheduler passes one mapping
        across Sessions so same-geometry WUs skip the retrace AND the
        compile.  None (the subprocess driver) keeps the per-run step
        exactly as before."""
        if not self.prepared:
            self.prepare()
        args = self.args
        adapter = self.adapter
        bank = self.bank
        template_total = self.template_total
        start_template = self.start_template
        quarantined = self.quarantined
        samples = self.samples
        cfg = self.cfg
        derived = self.derived
        geom = self.geom
        base_thr = self.base_thr
        sentinel = self.sentinel
        batch_size = self.batch_size
        params_P = self.params_P
        params_tau = self.params_tau
        params_psi = self.params_psi
        search_info = self.search_info
        dist = self._dist
        n_mesh = self._n_mesh
        init_data = self.init_data
        from ..parallel import distributed

        # --- the search
        cp_header_name = args.inputfile

        # fast-chip rescore overlap (oracle/rescore.py): background-score
        # the winners visible at each checkpoint while the device keeps
        # searching, so the end-of-run oracle pass only pays for
        # last-interval stragglers.  Gated on bank size: the overhead
        # isn't worth it for tiny test banks.
        import jax

        from ..oracle.rescore import (
            IncrementalRescorer,
            overlap_enabled,
            rescore_enabled,
            rescore_winners,
        )

        rescorer = None
        if (
            args.rescore
            and rescore_enabled()
            and overlap_enabled()
            and template_total >= 256
            # on a single-core host the background oracle passes would
            # steal the core from the device-feed thread instead of
            # overlapping with it
            and (os.cpu_count() or 1) >= 2
            # on a VIRTUAL (CPU-backend) mesh the n_mesh device threads
            # share the host cores with the oracle workers, and the
            # in-process communicator aborts any collective whose
            # rendezvous arrival skew exceeds 40 s — observed starving
            # the 8-thread CPU-mesh outright.  Real accelerator meshes
            # route collectives in hardware; only the CPU-emulated mesh
            # needs the guard.
            and (n_mesh == 1 or jax.default_backend() != "cpu")
            # elastic multi-host runs rescore only on the merge winner at
            # finalize; checkpoint-cadence overlap would score per-shard
            # partial toplists that the cross-host merge then invalidates
            and dist is None
        ):
            rescorer = IncrementalRescorer(
                lambda: _samples_to_host(samples, self.ts_scale),
                derived, derived.t_obs
            )
            erplog.debug("Rescore overlap armed (checkpoint cadence).\n")

        ckpt_count = metrics.counter("checkpoint.count")
        ckpt_bytes = metrics.counter("checkpoint.bytes", unit="B")
        d2h_bytes = metrics.counter("search.d2h_bytes", unit="B")

        # elastic runs persist progress as per-shard states on the board;
        # the GLOBAL checkpoint file is only written by the merge winner
        # at the end (the flag flips after the merge) so concurrent hosts
        # never race on one checkpoint path
        allow_global_ckpt = dist is None

        shard_layout = (
            distributed.shard_ranges(template_total, dist.num_processes)
            if dist is not None
            else None
        )
        ckpt_topology = topology_record(
            self._process_count, shard_layout, quarantined=quarantined
        )

        def checkpoint_now(n_done: int, M_now, T_now) -> None:
            if not allow_global_ckpt:
                return
            if not args.checkpointfile and rescorer is None:
                return
            with tracing.span("checkpoint", n_done=n_done):
                _checkpoint_now(n_done, M_now, T_now)

        def _checkpoint_now(n_done: int, M_now, T_now) -> None:
            # Host snapshot on the dispatch thread, at this sync point:
            # the next dispatched step DONATES the device buffers
            # (in-place state update, models/search.py::make_bank_step),
            # so any consumer that outlives this call — the rescorer's
            # feed worker in particular — must only ever see these host
            # copies, never the live handles.
            M_host = np.asarray(M_now)
            T_host = np.asarray(T_now)
            d2h_bytes.inc(M_host.nbytes + T_host.nbytes)
            if args.checkpointfile:
                # the checkpoint write needs the toplist NOW (it is the
                # durable state); the rescorer just reuses it
                cands = _state_to_candidates(
                    M_host, T_host, params_P, params_tau, params_psi, base_thr,
                    geom,
                )
                if rescorer is not None:
                    rescorer.observe_async(lambda: cands)
                # transient write failures (EIO, injected or real) spend
                # the shared retry budget instead of killing a healthy
                # run; a WEDGED write (NFS mount gone catatonic) trips
                # the watchdog
                with watchdog.guard("ckpt_write", n_done=n_done):
                    resilience.call_with_retry(
                        lambda: write_checkpoint(
                            args.checkpointfile,
                            Checkpoint(
                                n_template=n_done,
                                originalfile=cp_header_name,
                                candidates=cands,
                            ),
                            bank=(args.templatebank, template_total),
                            topology=ckpt_topology,
                        ),
                        site="ckpt_write",
                    )
                ckpt_count.inc()
                try:
                    ckpt_bytes.inc(os.path.getsize(args.checkpointfile))
                except OSError:
                    pass
            else:
                # rescorer-only cadence (standalone fast-chip runs): the
                # whole toplist build moves onto the feed worker — the
                # dispatch thread pays only the two d2h copies above
                rescorer.observe_async(
                    lambda: _state_to_candidates(
                        M_host, T_host, params_P, params_tau, params_psi,
                        base_thr, geom,
                    )
                )
            if sentinel is not None:
                with tracing.span("sentinel-probe"):
                    sentinel.probe("checkpoint")

        import jax.numpy as jnp

        M, T = self._seed_state
        state = (jnp.asarray(np.asarray(M)), jnp.asarray(np.asarray(T)))
        interrupted = False
        last_done = start_template

        metrics.gauge("driver.template_total").set(int(template_total))
        metrics.gauge("driver.start_template").set(int(start_template))
        fraction_g = metrics.gauge("driver.fraction_done")

        def progress_cb(done: int, total: int, M_now, T_now) -> bool:
            nonlocal interrupted, last_done
            last_done = done
            # the reference reports (counter+1)/total per template — an
            # off-by-one that overshoots 1.0 at the end
            # (demod_binary.c:1420); with batch granularity we report the
            # exact fraction instead
            adapter.fraction_done(done / total)
            fraction_g.set(done / total)
            if adapter.time_to_checkpoint():
                erplog.log_message(erplog.Level.DEBUG, False, "Committing checkpoint.\n")
                checkpoint_now(done, M_now, T_now)
                adapter.checkpoint_completed()
                erplog.info("Checkpoint committed!\n")
            # screensaver update from current maxima (4-harmonic row);
            # transfer and relayout only that row, and only when something
            # listens AND an update is due (wrapped mode throttles to ~1/s
            # — the payload costs a device sync, and the wrapper polls at
            # 5 Hz anyway)
            if adapter.search_info_due():
                from ..ops.harmonic import row_to_natural

                search_info["power_spectrum"] = binned_spectrum(
                    row_to_natural(np.asarray(M_now[2]), 2, geom.fund_hi),
                    geom.fund_hi,
                )
                search_info["fraction_done"] = done / total
                # current template's orbital parameters, live per update
                # (demod_binary.c:1213-1215: radius=tau, period=P,
                # phase=Psi0)
                t_cur = min(done, template_total) - 1
                if t_cur >= 0:
                    search_info["orbital_radius"] = float(bank.tau[t_cur])
                    search_info["orbital_period"] = float(bank.P[t_cur])
                    search_info["orbital_phase"] = float(bank.psi0[t_cur])
                adapter.update_shmem(search_info)
            # client-requested suspension parks here, between batches,
            # with device state resident (boinc_get_status().suspended
            # semantics)
            adapter.wait_while_suspended()
            if adapter.quit_requested():
                interrupted = True
                return False
            if watchdog.abort_requested():
                # cooperative leg of the escalation ladder: stop
                # dispatching so the run can checkpoint and exit with the
                # temporary-exit rc before the grace timer forces a hard
                # exit
                interrupted = True
                return False
            return True

        profiling.device_memory_status("search setup")
        try:
            # per-chip attainable bound (runtime/roofline.py; the
            # reference logs its GFLOPS estimate the same way,
            # cuda_utilities.c:163-182)
            from .roofline import roofline_report

            roof = roofline_report(
                geom.nsamples, geom.n_unpadded, geom.fund_hi, geom.harm_hi,
                max_slope=geom.max_slope,
            )
            erplog.debug(
                "Roofline (%s): attainable %.0f templates/s, model bound %s.\n",
                roof["chip"],
                roof["attainable_templates_per_sec"],
                roof["model_bound"],
            )
        except Exception:
            pass  # diagnostics only
        # in-flight dispatch window (models/search.py::run_bank): how many
        # steps the host may run ahead of the device. 1 = fully
        # synchronous (drain every step); the default 2 overlaps each
        # step's host work with the previous step's device execution
        # while keeping quit / checkpoint latency at one batch.  Captured
        # per Session (SessionEnv), not per process: a resident server
        # re-reads it for every WU.
        lookahead = self.env.lookahead
        metrics.gauge("search.lookahead").set(lookahead)
        metrics.gauge("search.batch_size").set(int(batch_size))
        flightrec.record(
            "run-config",
            template_total=int(template_total),
            start_template=int(start_template),
            batch_size=int(batch_size),
            lookahead=lookahead,
            n_mesh=int(n_mesh),
        )
        self._obs_record(
            "session-search",
            template_total=int(template_total),
            start_template=int(start_template),
            batch_size=int(batch_size),
            lookahead=lookahead,
        )

        # quarantined windows carve the bank into runnable segments; each
        # is a bounded [start, stop) dispatch window (the device masks
        # templates >= stop exactly like final-batch padding — traced
        # scalar, no recompile).  No quarantine -> one segment covering
        # the whole remaining bank.
        segments = watchdog.runnable_segments(
            template_total, quarantined, start=start_template
        )

        from ..models.search import run_bank

        elastic_result = None
        try:
            # ERP_STEPTIME_PROFILE=<dir> wraps the template loop in a
            # jax.profiler capture and merges the per-stage measured
            # device lane into the Chrome export (runtime/steptime.py)
            with steptime.maybe_capture_profile(), profiling.trace(
                args.profile_dir
            ), profiling.phase("template loop", span="template-loop"):
                if dist is not None:
                    # multi-host elastic search: this host runs (and, on
                    # peer death, adopts) template-range shards under
                    # leases; the cross-host merge happens once, on
                    # whichever host wins the merge lease
                    # (parallel/elastic.py)
                    from ..parallel import make_mesh, run_bank_elastic
                    from ..parallel.elastic import board_identity

                    erplog.info(
                        "Elastic search: host %s of %d, %d-device local "
                        "mesh, shard board at %s.\n",
                        dist.host_id, dist.num_processes, n_mesh,
                        dist.shard_dir,
                    )
                    max_shard = max(
                        [b - a for a, b in shard_layout] or [1]
                    )
                    per_dev = max(
                        1, min(batch_size, -(-max(1, max_shard) // n_mesh))
                    )
                    elastic_result = run_bank_elastic(
                        samples,
                        bank.P,
                        bank.tau,
                        bank.psi0,
                        geom,
                        make_mesh(n_mesh),
                        dist,
                        board_identity(
                            args.inputfile, args.templatebank, template_total
                        ),
                        per_device_batch=per_dev,
                        state=state,
                        progress_cb=progress_cb,
                        lookahead=lookahead,
                    )
                    if elastic_result.state is not None:
                        state = (
                            jnp.asarray(elastic_result.state[0]),
                            jnp.asarray(elastic_result.state[1]),
                        )
                elif n_mesh > 1:
                    # template-bank sharding over the ICI mesh; checkpoint
                    # / progress / shmem / resume logic is shared via the
                    # same state + progress_cb contract (bit-exact vs
                    # single-chip, tests/test_parallel.py)
                    from ..parallel import make_mesh, run_bank_sharded

                    erplog.info(
                        "Sharding template bank over a %d-device mesh.\n", n_mesh
                    )
                    # don't let the global batch (n_mesh * per_dev)
                    # overshoot the remaining bank: small banks would
                    # otherwise burn most of each step on masked padding
                    # slots
                    remaining_t = max(1, template_total - start_template)
                    per_dev = min(batch_size, -(-remaining_t // n_mesh))
                    # one bounded window per runnable segment; per_dev
                    # stays fixed across segments so the compiled step is
                    # reused
                    mesh = make_mesh(n_mesh)
                    for seg_a, seg_b in segments:
                        state = run_bank_sharded(
                            samples,
                            bank.P,
                            bank.tau,
                            bank.psi0,
                            geom,
                            mesh,
                            per_device_batch=per_dev,
                            state=state,
                            start_template=seg_a,
                            stop_template=seg_b,
                            progress_cb=progress_cb,
                            lookahead=lookahead,
                        )
                        if interrupted:
                            break
                else:
                    for seg_a, seg_b in segments:
                        state = run_bank(
                            samples,
                            bank.P,
                            bank.tau,
                            bank.psi0,
                            geom,
                            batch_size=batch_size,
                            state=state,
                            start_template=seg_a,
                            stop_template=seg_b,
                            progress_cb=progress_cb,
                            lookahead=lookahead,
                            step_cache=step_cache,
                        )
                        if interrupted:
                            break
        except BaseException:
            # any non-success exit (RadpulError, device failure,
            # KeyboardInterrupt): drop the rescorer's queued oracle passes
            # instead of letting its non-daemon pool join ~1.8 s workers
            # during interpreter teardown
            if rescorer is not None:
                rescorer.abort()
            raise

        if interrupted or (elastic_result is not None and elastic_result.interrupted):
            erplog.warn("Quit requested! Exiting prematurely...\n")
            if rescorer is not None:
                rescorer.abort()  # drop queued oracle work, exit fast
            # elastic: allow_global_ckpt is still False — the committed
            # shard states on the board are the durable resume point
            checkpoint_now(last_done, *state)
            if watchdog.abort_requested():
                # the watchdog asked for a cooperative stop: checkpoint
                # is committed, now exit with the temporary-exit rc so a
                # supervisor (tools/supervise.py) restarts from it — the
                # BOINC boinc_temporary_exit analogue
                raise RadpulError(
                    RADPUL_TEMPORARY_EXIT,
                    "Watchdog stall: checkpointed and exiting for a "
                    "supervised restart.",
                )
            self._obs_record("session-interrupted", last_done=last_done)
            return 0

        if elastic_result is not None and not elastic_result.merged:
            # another host won the merge lease and owns finalize + the
            # result write; this host's shards are complete and committed
            erplog.info(
                "Host %s done: all shards committed; the merge winner "
                "writes the result.\n", dist.host_id,
            )
            return 0
        if elastic_result is not None:
            # merge winner: from here on this host is the only writer, so
            # the global checkpoint path re-opens (final checkpoint +
            # audit with the topology record)
            allow_global_ckpt = True

        # --- final checkpoint (demod_binary.c:1495-1499)
        erplog.debug("Search done!\n")
        try:
            checkpoint_now(template_total, *state)

            # --- false-alarm stats + output (demod_binary.c:1501-1685)
            with tracing.span("finalize"):
                cands = _state_to_candidates(
                    *state, params_P, params_tau, params_psi, base_thr, geom
                )
                emitted = finalize_candidates(cands, derived.t_obs)
        except BaseException:
            # same rationale as the search-phase guard: never exit through
            # an error with the rescore pool still joining background
            # passes
            if rescorer is not None:
                rescorer.abort()
            raise

        # output-boundary oracle rescoring: erase the XLA FP-contraction
        # mismatch class before the file is written (oracle/rescore.py);
        # the overlap cache from the checkpoint-cadence rescorer makes
        # this pay only for winners that appeared after the last
        # checkpoint
        if rescorer is not None:
            with tracing.span("rescore-finalize"):
                cache = rescorer.finalize()
        else:
            cache = None
        if args.rescore and rescore_enabled() and len(emitted):
            import time as _time

            with profiling.phase("oracle rescore", span="rescore") as sp:
                t0 = _time.perf_counter()
                # the overlap worker already fetched + interleaved the
                # host series; don't pay the ~17 MB d2h a second time
                ts_host = (
                    rescorer.series_if_fetched() if rescorer is not None else None
                )
                if ts_host is None:
                    ts_host = _samples_to_host(samples, self.ts_scale)
                from ..oracle.rescore import unique_winner_count

                # count FINAL winners before patching: the overlap cache
                # also holds displaced ever-winners, so len(cache) would
                # overstate how much of the winning set was pre-scored
                n_winners = unique_winner_count(emitted)
                patched, n_eval = rescore_winners(
                    ts_host,
                    cands,
                    emitted,
                    derived,
                    cache=cache,
                )
                emitted = finalize_candidates(patched, derived.t_obs)
                rescore_wall = _time.perf_counter() - t0
                sp.set(n_winners=n_winners, n_eval=n_eval)
            if rescorer is not None:
                erplog.info(
                    "Rescored %d of %d winning templates through the host "
                    "oracle in %.1f s (%d pre-scored during the search "
                    "across %d checkpoints%s).\n",
                    n_eval,
                    n_winners,
                    rescore_wall,
                    n_winners - n_eval,
                    rescorer.observed,
                    f", {rescorer.failed} background failures"
                    if rescorer.failed
                    else "",
                )
            else:
                erplog.info(
                    "Rescored %d winning templates through the host oracle "
                    "in %.1f s.\n",
                    n_eval,
                    rescore_wall,
                )
        header = ResultHeader(exec_name=args.exec_name)
        # quarantine gaps are NAMED in the result header so a validator
        # comparing against another host's file knows the coverage differs
        header.quarantined = quarantined
        if init_data is not None:
            # provenance from the BOINC slot (demod_binary.c:1591-1602)
            header.user_id = init_data.userid
            header.user_name = init_data.user_name
            header.host_id = init_data.hostid
            header.host_cpid = init_data.host_cpid
        with tracing.span("result-write"), watchdog.guard("result_write"):
            resilience.call_with_retry(
                lambda: write_result_file(
                    args.outputfile,
                    ResultFile(
                        candidates=emitted,
                        t_obs=derived.t_obs,
                        header=header,
                    ),
                ),
                site="result_write",
            )
        if elastic_result is not None:
            # the result file is durable: completing the merge lease
            # tells waiting peers (and any future adopter) the search is
            # finished
            elastic_result.finalize_done()
        erplog.info("Data processing finished successfully!\n")
        self._obs_record("session-done", outputfile=args.outputfile)
        return 0

    def run(self, n_mesh: int = 1, dist=None, step_cache=None) -> int:
        """prepare + execute in one call — the classic driver shape."""
        self.prepare(n_mesh=n_mesh, dist=dist)
        return self.execute(step_cache=step_cache)
