"""The search driver: TPU equivalent of ``MAIN()`` (``demod_binary.c:117``).

Same observable behaviour — input/template/zaplist parsing and validation,
checkpoint resume, whitening, the search itself, checkpoint cadence,
progress/screensaver reporting, false-alarm statistics and the atomic
candidate-file write — but the template loop body is the batched TPU model
(``models/search.py``) instead of per-template kernel dispatch.

Since the fleet serving tier landed, this module is the PROCESS-scoped
half of the split: argument surface (:class:`DriverArgs`), process
observability arming, device selection, the persistent-compilation-cache
lifecycle, and the RADPUL_* error-code boundary.  The per-WORKUNIT half
— parse, checkpoint resume, whitening, the dispatch loop, rescore, the
result write — lives in ``runtime/session.py`` as a :class:`~.session.
Session`, which this driver runs exactly once per process while the
resident scheduler (``runtime/scheduler.py``) runs many per process.

Checkpoint compatibility: the device state is (M, T) per-bin maxima; at
checkpoint time it is converted to the reference's 500-candidate format
(which is exactly the information the reference itself retains). On resume,
checkpoint candidates are re-seeded into M as "virtual templates" — their
orbital parameters are appended after the bank so the (M, T) -> candidates
conversion is uniform.
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass, replace

from . import faultinject, flightrec, metrics, resilience, steptime, tracing, watchdog
from . import logging as erplog
from .boinc import BoincAdapter
from .errors import RADPUL_EIO, RADPUL_EVAL, RadpulError
from .session import (  # noqa: F401  (historical driver surface)
    Session,
    SessionEnv,
    _dump_header,
    _dump_thresholds,
    _samples_to_host,
    _state_to_candidates,
    binned_spectrum,
    exit_code_for,
    sky_position_radians,
)


@dataclass
class DriverArgs:
    """CLI surface of the reference (``demod_binary.c:217-445``) plus
    TPU-specific extensions."""

    inputfile: str
    outputfile: str
    templatebank: str
    checkpointfile: str | None = None
    zaplistfile: str | None = None
    f0: float = 250.0
    padding: float = 1.0
    fA: float = 0.04
    window: int = 1000
    white: bool = False
    debug: bool = False
    # TPU extensions
    # batch size: None = auto (measured sweep / HBM memory model,
    # runtime/autobatch.py); --batch N pins it
    batch_size: int | None = None
    use_lut: bool = True
    # host-oracle rescoring of emitted candidates (oracle/rescore.py);
    # --no-rescore / ERP_RESCORE=off disables
    rescore: bool = True
    exec_name: str = "eah_brp_tpu"
    # -D: pin the worker to one device ordinal (cuda_utilities.c:96-237's
    # role); --mesh N: shard the template bank over an N-device ICI mesh
    # (None = auto: mesh over all visible devices when more than one)
    device: int | None = None
    mesh_devices: int | None = None
    # native-wrapper protocol (runtime/boinc.py, native/erp_wrapper.cpp)
    status_file: str | None = None
    control_file: str | None = None
    shmem: str | None = None
    # profiler trace output dir (also via $ERP_PROFILE_DIR; runtime/profiling.py)
    profile_dir: str | None = None
    # structured metrics JSONL stream + run report (also via
    # $ERP_METRICS_FILE; runtime/metrics.py)
    metrics_file: str | None = None


_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def default_cache_dir() -> str:
    """The persistent compilation cache's one fixed home when
    ``JAX_COMPILATION_CACHE_DIR`` is unset: ``<repo>/.erp_cache/xla``
    (git-ignored).  The path is part of the cache key, so it never
    moves with the host or the run."""
    return os.path.join(_REPO, ".erp_cache", "xla")


def compilation_cache_dir() -> str | None:
    """Where the persistent cache lives: None under
    ``ERP_COMPILATION_CACHE=off``, else ``$JAX_COMPILATION_CACHE_DIR`` or
    :func:`default_cache_dir`."""
    if os.environ.get("ERP_COMPILATION_CACHE", "").strip().lower() == "off":
        return None
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or default_cache_dir()


def enable_compilation_cache() -> None:
    """Turn on JAX's persistent compilation cache.

    The FFTW-wisdom analogue (``create_wisdomf_eah_brp.sh``): the costly
    artifact here is the XLA compilation of the batched search step; with
    the cache warm (``tools/create_wisdom.py``) worker start-up skips the
    minutes-long compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
    reads it itself and no directory is set here; otherwise the cache is
    at :func:`default_cache_dir`.  ``ERP_COMPILATION_CACHE=off`` opts out.
    """
    cache = compilation_cache_dir()
    if cache is None:
        erplog.debug("XLA compilation cache disabled by request.\n")
        return
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        try:
            os.makedirs(cache, exist_ok=True)
        except OSError as e:
            # cache trouble must never take down the search — run cold
            erplog.warn("Compilation cache unavailable (%s); running cold.\n", e)
            return
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    erplog.debug("XLA compilation cache: %s\n", cache)


def run_search(args: DriverArgs, adapter: BoincAdapter | None = None) -> int:
    """Returns 0 on success, RADPUL_* error code otherwise."""
    metrics.configure(metrics_file=args.metrics_file)
    # host span timeline (runtime/tracing.py, $ERP_TRACE_FILE); armed
    # before any phase bracket so the trace epoch covers the whole run
    if tracing.configure():
        metrics.note_host_trace(os.environ.get(tracing.TRACE_FILE_ENV, ""))
    # black box: ring + crash hooks live for the whole run; the dump
    # lands next to the checkpoint (the one dir guaranteed writable)
    dump_dir = None
    for p in (args.checkpointfile, args.outputfile):
        if p:
            dump_dir = os.path.dirname(os.path.abspath(p))
            break
    fr_context = {
        "inputfile": args.inputfile,
        "templatebank": args.templatebank,
        "checkpointfile": args.checkpointfile,
    }
    # a fabric parent hands its workunit correlation id down via env so
    # this subprocess's blackbox/trace/metrics artifacts join the same
    # end-to-end WU lifecycle (metrics picks the env up on its own)
    corr_id = os.environ.get(metrics.CORR_ID_ENV)
    if corr_id:
        fr_context["corr_id"] = corr_id
    flightrec.arm(dump_dir=dump_dir, context=fr_context)
    # hang doctor (runtime/watchdog.py): per-stage deadlines turn an
    # indefinite wedge into a bounded-time supervised restart; the
    # incident log persists which template window was in flight so
    # repeat offenders get quarantined on a later pass
    incident_path = watchdog.default_incident_path(args.checkpointfile)
    watchdog.arm(
        incident_log=(
            watchdog.IncidentLog(incident_path) if incident_path else None
        )
    )
    # exit status threads into the run report; None survives to the
    # finally block only on an exception nobody below maps to a code
    code: int | None = None
    try:
        code = _run_search(args, adapter or BoincAdapter())
        return code
    except FileNotFoundError as e:
        # distinct message shape from the generic mapping below
        # (demod_binary.c's fopen error text)
        erplog.error("Couldn't open file: %s\n", e)
        code = RADPUL_EIO
        return code
    except Exception as e:
        mapped = exit_code_for(e)
        if mapped is None:
            raise
        erplog.error("%s\n", str(e))
        code = mapped
        return code
    finally:
        if code != 0:
            # black-box dump on ANY non-success exit (mapped error code
            # or an exception still in flight), before the run report
            # below closes out — the dump snapshots the open metrics
            # window via emergency_flush
            exc = sys.exc_info()[1]
            reason = (
                f"exit-code-{code}" if code is not None
                else "unhandled-exception"
            )
            flightrec.dump(reason, exc=exc)
        else:
            # clean exit: release the recorder so the empty faulthandler
            # sidecar doesn't litter the checkpoint directory
            flightrec.disarm()
        # the supervisor thread must not outlive the run it watches
        watchdog.disarm()
        # after the dump (which embeds the open-span stack), before the
        # run report (which links the trace artifacts)
        tracing.finish(code)
        steptime.finish(code)
        metrics.finish(
            code,
            context={
                "inputfile": args.inputfile,
                "templatebank": args.templatebank,
            },
        )


def _select_devices(args: DriverArgs, init_data=None) -> int:
    """Device selection (-D) / mesh sizing (--mesh), logged like the
    reference's pick (``cuda_utilities.c:96-237``,
    ``demod_binary_cuda.cu:176-230``).  Returns the mesh width to search
    with (1 = single-chip path).  A BOINC-assigned device in
    ``init_data.xml`` takes precedence over the command line
    (``cuda_utilities.c:44-85``)."""
    import jax

    if init_data is not None and init_data.gpu_device_num is not None:
        erplog.info(
            "Using BOINC-assigned device #%d (init_data.xml).\n",
            init_data.gpu_device_num,
        )
        args = replace(args, device=init_data.gpu_device_num)

    devices = jax.devices()
    erplog.debug("Analyzing available %s devices...\n", jax.default_backend())
    for i, d in enumerate(devices):
        erplog.debug("  device #%d: %s\n", i, str(d))

    if args.device is not None and (args.mesh_devices or 0) > 1:
        raise RadpulError(
            RADPUL_EVAL, "-D/--device and --mesh N>1 are mutually exclusive."
        )
    if args.device is not None:
        if not 0 <= args.device < len(devices):
            raise RadpulError(
                RADPUL_EVAL,
                f"No device matching the given device ID #{args.device} "
                f"found ({len(devices)} available)!",
            )
        dev = devices[args.device]
        jax.config.update("jax_default_device", dev)
        erplog.info(
            'Using %s device #%d "%s"\n',
            jax.default_backend(),
            args.device,
            str(dev),
        )
        return 1
    if args.mesh_devices is not None:
        if args.mesh_devices < 1 or args.mesh_devices > len(devices):
            raise RadpulError(
                RADPUL_EVAL,
                f"Requested a {args.mesh_devices}-device mesh but "
                f"{len(devices)} devices are available!",
            )
        return args.mesh_devices
    # auto: shard over every visible device (the reference's equivalent
    # backend dispatch is always wired in, demod_binary.c:450-487)
    erplog.info(
        "Using %d %s device(s).\n", len(devices), jax.default_backend()
    )
    return len(devices)


def _run_search(args: DriverArgs, adapter: BoincAdapter) -> int:
    """Process-level bring-up, then exactly one Session."""
    erplog.info("Starting data processing...\n")
    # re-arm the fault-injection schedule loudly (a malformed ERP_FAULT_SPEC
    # is a usage error -> RADPUL_EVAL via the ValueError mapping) and start
    # a fresh per-run retry budget for every resilience site
    if faultinject.configure():
        erplog.warn(
            "Fault injection armed: ERP_FAULT_SPEC=%s\n",
            os.environ.get(faultinject.ENV_SPEC, ""),
        )
    resilience.begin_run()
    # multi-host identity (parallel/distributed.py) BEFORE the first
    # backend query: the forced-CPU device count and jax.distributed both
    # must land before XLA freezes its platform view
    from ..parallel import distributed

    dist = distributed.initialize()
    if dist is not None and dist.shard_dir is None:
        raise RadpulError(
            RADPUL_EVAL,
            f"Multi-host run ({distributed.ENV_NUM_PROCESSES}="
            f"{dist.num_processes}) needs {distributed.ENV_SHARD_DIR} "
            f"pointing at a directory every host can reach.",
        )
    enable_compilation_cache()
    # BOINC slot-dir application info: device assignment + user/host
    # provenance (cuda_utilities.c:53-85, demod_binary.c:1591-1605)
    from .initdata import load_init_data

    init_data = load_init_data()
    if init_data is None:
        erplog.warn("User/host details unavailable...\n")
    # device pick / mesh sizing first, like the reference's backend init
    # (demod_binary.c:450-487 runs initialize_cuda before anything else)
    n_mesh = _select_devices(args, init_data)
    # graceful quit: SIGTERM/SIGINT set the adapter's quit flag so the batch
    # loop checkpoints and exits cleanly (erp_boinc_wrapper.cpp:143-152)
    adapter.install_signal_handlers()

    session = Session(args, adapter, init_data=init_data)
    return session.run(n_mesh=n_mesh, dist=dist)
