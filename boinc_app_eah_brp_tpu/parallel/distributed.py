"""Multi-host process identity and ``jax.distributed`` initialization.

The reference app scales across volunteer hosts only as independent
workunits that never communicate (SURVEY.md section 2.5); our pod target
(ROADMAP item 4) shards ONE workunit's template bank across hosts, which
needs a process-identity layer.  Two modes, both env-driven:

* **Coordinated** (``ERP_COORDINATOR`` set): wraps
  ``jax.distributed.initialize`` — the coordinator address, process id and
  process count come from ``ERP_COORDINATOR`` / ``ERP_PROCESS_ID`` /
  ``ERP_NUM_PROCESSES``.  ``jax.devices()`` then spans the pod;
  host-local meshes must come from the addressable devices
  (``mesh.make_mesh`` validates this).
* **Uncoordinated** (``ERP_NUM_PROCESSES`` > 1 without a coordinator):
  process identity comes purely from the environment and NO cross-process
  jax runtime is brought up — each process keeps its own single-process
  backend and all device collectives stay host-local (ICI-only inside a
  host).  Cross-host state flows exclusively through the shard-lease
  board on the shared filesystem (``parallel/elastic.py``), which is also
  what makes host loss survivable: there is no global collective to hang
  when a host dies.  This is the chip-free chaos-soak mode.

Chip-free multi-"host" emulation: ``ERP_LOCAL_DEVICES=K`` forces the CPU
platform with ``--xla_force_host_platform_device_count=K`` per process
(``force_cpu_devices``), so N
processes x K virtual devices model an N-host pod on one machine.

``initialize`` must run before the first jax backend query (XLA reads
the device-count flag exactly once); the driver calls it before device
selection.  No jax import happens unless a distributed config is active.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

ENV_COORDINATOR = "ERP_COORDINATOR"  # host:port of process 0's service
ENV_PROCESS_ID = "ERP_PROCESS_ID"
ENV_NUM_PROCESSES = "ERP_NUM_PROCESSES"
ENV_LOCAL_DEVICES = "ERP_LOCAL_DEVICES"  # chip-free: forced CPU devices
ENV_SHARD_DIR = "ERP_SHARD_DIR"  # shard-lease board root (elastic mode)


class DistributedConfigError(ValueError):
    """Malformed multi-host environment (bad id/count)."""


@dataclass(frozen=True)
class DistributedConfig:
    """Identity of this process within a multi-host search."""

    num_processes: int
    process_id: int
    coordinator: str | None = None
    local_devices: int | None = None
    shard_dir: str | None = None

    @property
    def host_id(self) -> str:
        """Stable logical host name used in leases/heartbeats/events."""
        return f"host{self.process_id}"

    @property
    def coordinated(self) -> bool:
        return self.coordinator is not None


def _env_int(name: str) -> int | None:
    raw = os.environ.get(name)
    if raw is None or raw.strip() == "":
        return None
    try:
        return int(raw)
    except ValueError:
        raise DistributedConfigError(
            f"{name}={raw!r} is not an integer."
        ) from None


def config_from_env() -> DistributedConfig | None:
    """The multi-host config this environment describes, or None for a
    plain single-process run (``ERP_NUM_PROCESSES`` unset or <= 1 and no
    coordinator)."""
    coordinator = os.environ.get(ENV_COORDINATOR) or None
    n_proc = _env_int(ENV_NUM_PROCESSES)
    proc_id = _env_int(ENV_PROCESS_ID)
    if coordinator is None and (n_proc is None or n_proc <= 1):
        return None
    if n_proc is None or n_proc < 1:
        raise DistributedConfigError(
            f"{ENV_COORDINATOR} is set but {ENV_NUM_PROCESSES} is not: a "
            f"coordinated run needs an explicit process count."
        )
    if proc_id is None:
        raise DistributedConfigError(
            f"{ENV_NUM_PROCESSES}={n_proc} but {ENV_PROCESS_ID} is unset."
        )
    if not 0 <= proc_id < n_proc:
        raise DistributedConfigError(
            f"{ENV_PROCESS_ID}={proc_id} out of range for "
            f"{ENV_NUM_PROCESSES}={n_proc}."
        )
    local = _env_int(ENV_LOCAL_DEVICES)
    if local is not None and local < 1:
        raise DistributedConfigError(f"{ENV_LOCAL_DEVICES} must be >= 1.")
    return DistributedConfig(
        num_processes=n_proc,
        process_id=proc_id,
        coordinator=coordinator,
        local_devices=local,
        shard_dir=os.environ.get(ENV_SHARD_DIR) or None,
    )


_active: DistributedConfig | None = None
_initialized = False


def force_cpu_devices(n_devices: int) -> None:
    """Force the virtual n-device CPU platform before any backend query
    (``jax`` may already be imported, so the live config is set)."""
    import jax

    if (jax.config.jax_platforms, jax.config.jax_num_cpu_devices) == (
        "cpu", n_devices,
    ):
        return  # already forced (a second call after backend start is legal)
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)


def initialize(cfg: DistributedConfig | None = None) -> DistributedConfig | None:
    """Arm this process's multi-host identity (idempotent).

    Coordinated mode additionally brings up ``jax.distributed``; both
    modes apply the chip-free forced-CPU device count when requested.
    Returns the active config (None = single-process)."""
    global _active, _initialized
    if _initialized:
        return _active
    if cfg is None:
        cfg = config_from_env()
    _initialized = True
    if cfg is None:
        return None
    from ..runtime import logging as erplog

    if cfg.local_devices is not None:
        force_cpu_devices(cfg.local_devices)
    if cfg.coordinated:
        import jax

        erplog.info(
            "Initializing jax.distributed: process %d/%d, coordinator %s\n",
            cfg.process_id, cfg.num_processes, cfg.coordinator,
        )
        jax.distributed.initialize(
            coordinator_address=cfg.coordinator,
            num_processes=cfg.num_processes,
            process_id=cfg.process_id,
        )
    else:
        erplog.info(
            "Multi-host search (uncoordinated): process %d/%d, "
            "cross-host merge via the shard board.\n",
            cfg.process_id, cfg.num_processes,
        )
    _active = cfg
    return _active


def context() -> DistributedConfig | None:
    """The active config, lazily initialized from the environment."""
    if not _initialized:
        return initialize()
    return _active


def reset() -> None:
    """Forget the active config (tests only — real runs initialize once)."""
    global _active, _initialized
    _active = None
    _initialized = False


def shard_ranges(n_templates: int, n_shards: int) -> list[tuple[int, int]]:
    """Contiguous balanced template ranges ``[(a0, b0), ...]`` covering
    ``[0, n_templates)``.  Sizes differ by at most one; with more shards
    than templates the tail shards are empty (``a == b``) and complete
    trivially.  Contiguity matters: the toplist tie-break is
    smallest-global-index-wins, and contiguous ascending blocks keep
    "earlier shard" == "earlier template" exactly like the in-host mesh
    sharding."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    base, extra = divmod(max(0, n_templates), n_shards)
    ranges = []
    a = 0
    for k in range(n_shards):
        b = a + base + (1 if k < extra else 0)
        ranges.append((a, b))
        a = b
    return ranges
