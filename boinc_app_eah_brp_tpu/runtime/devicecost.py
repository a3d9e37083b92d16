"""Device-cost observatory: the named-scope stage registry and the
scope-based HBM attribution schema.

Layer 8 of the observability stack (docs/observability.md).  Layer 7
attributes the HOST wall clock; the AOT ledger (``tools/cost_ledger.py``)
bounds DEVICE traffic — but until now its largest bucket was
2.5 GB/template of "compiler-generated" layout copies attributed to
nothing, because the optimized HLO only carries whatever source metadata
survives fusion.  This module closes that gap from the source side:
every pipeline stage wraps its ops in a ``jax.named_scope`` drawn from
the single registry below, so the scope name rides the ``op_name``
metadata of every derived HLO instruction — through vmap, jit and XLA
fusion — and ``tools/hlo_attrib.py`` can bucket the optimized module's
bytes by stage without a chip.

Design rules (same contract as ``metrics`` / ``tracing`` /
``flightrec``):

* **Zero numeric effect.**  ``stage_scope`` only pushes a name onto the
  JAX name stack; the jaxpr's operations, shapes and dtypes are
  untouched, so compiled executables are bit-identical modulo metadata
  and adding/removing scopes can never change results
  (``tests/test_devicecost.py`` proves no extra recompiles either).
* **No jax import at module import.**  The registry, the op_name
  parser and the artifact validators are plain Python so the chip-free
  tools (``cost_ledger``, ``metrics_report``) can import this module
  without dragging jax in; ``stage_scope`` imports jax lazily on first
  use inside already-jax-using code.

The scope names are dotted ``erp.<stage>`` so they are unambiguous
inside the slash-joined name stack (``jit(step)/vmap/erp.resample/...``)
and can never collide with jax-internal scope names.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

# schema of the attribution artifact tools/hlo_attrib.py emits
ATTRIB_SCHEMA = "erp-hlo-attrib/1"

SCOPE_PREFIX = "erp."

# The single stage registry: scope name (without prefix) -> the
# COST_LEDGER.json stage bucket its traffic lands in.  Order is pipeline
# order; tools render stages in this order.  Adding a stage here is the
# ONLY step needed for it to appear in hlo_attrib / cost_ledger output —
# the instrumentation sites just call stage_scope("<name>").
STAGES: dict[str, str] = {
    "unpack": "unpack",  # ops/unpack.py 4-bit nibble split
    "resample": "resample",  # ops/resample.py + ops/pallas_resample.py
    "fftprep": "resample",  # ops/pallas_resample.py resident finalize pass
    "fft": "fft+power",  # ops/fft.py cascades (fwd + inverse)
    "power": "fft+power",  # ops/spectrum.py |X|^2 epilogue
    "whiten": "whiten",  # ops/whiten.py scale/zap/edge device ops
    "median": "whiten",  # ops/median.py blocked-sort running median
    "harmonic": "harmonic-sum",  # ops/harmonic.py phase-major sum
    "sumspec": "harmonic-sum",  # ops/pallas_sumspec.py fused fold kernel
    "bank-slice": "bank-slice",  # models/search.py device bank slicing
    "merge": "merge",  # (M, T) max/argmax/where fold
    "allreduce": "merge",  # parallel/sharded_search.py ppermute butterfly
    "health": "health",  # models/search.py batch_health_vec
}

_SCOPE_RE = re.compile(r"erp\.([A-Za-z0-9_-]+)")


def scope_name(stage: str) -> str:
    """The full named-scope string for a registered stage."""
    if stage not in STAGES:
        raise KeyError(
            f"unregistered device-cost stage {stage!r}; add it to "
            "runtime/devicecost.py::STAGES"
        )
    return SCOPE_PREFIX + stage


def stage_scope(stage: str):
    """``jax.named_scope`` context manager for a registered stage.

    Use around the ops of one pipeline stage inside traced code; the
    scope name lands in the ``op_name`` metadata of every HLO
    instruction derived from ops traced under it.  Raises KeyError for
    names not in :data:`STAGES` — attribution silently losing a stage
    to a typo would defeat the registry."""
    name = scope_name(stage)  # validate before importing jax
    import jax

    return jax.named_scope(name)


def scoped(stage: str):
    """Decorator form of :func:`stage_scope` for functions that ARE one
    stage end to end (the pallas wrappers).  Stacks under ``jax.jit``:
    jit resolves static_argnames through ``__wrapped__``."""
    name = scope_name(stage)

    def deco(fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            import jax

            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def stage_of_op_name(op_name: str | None) -> str | None:
    """The registered stage of one HLO ``op_name`` metadata string, or
    None when no registered scope appears in it.

    The INNERMOST (last-occurring) scope wins: nested scopes like
    ``erp.power/.../erp.fft`` mean the op belongs to the inner stage.
    Unregistered ``erp.*`` names are ignored (stale artifacts from an
    older registry still parse)."""
    if not op_name:
        return None
    stage = None
    for m in _SCOPE_RE.finditer(op_name):
        if m.group(1) in STAGES:
            stage = m.group(1)
    return stage


def ledger_stage(stage: str) -> str:
    """COST_LEDGER.json bucket name for a registered stage."""
    return STAGES.get(stage, stage)


# ---------------------------------------------------------------------------
# roofline stage model (tools/step_report.py)


def stage_time_model(
    nsamples: int,
    n_unpadded: int,
    fund_hi: int,
    harm_hi: int,
    max_slope: float = 0.008,
    chip: str | None = None,
) -> list[dict]:
    """Roofline-estimated per-template device time per pipeline stage:
    ``[{stage, scope, t_ms, fraction, bound}, ...]`` in pipeline order.

    Each stage's time is ``max(t_mxu, t_hbm)`` from
    ``runtime/roofline.py``, also normalized to fractions of the whole
    step (``tools/step_report.py`` joins it against measured step
    times).  Imports jax
    transitively (roofline pulls ops.fft for the plan) — call from
    jax-using code only."""
    from .roofline import _CHIPS, chip_generation, pipeline_costs

    gen = chip or chip_generation()
    peak, bw = _CHIPS[gen]
    # roofline stage name -> registry scope carrying its traffic
    scope_of = {
        "resample_split": "resample",
        "rfft_packed+power": "fft",
        "harmonic_sum": "harmonic",
        "merge(M,T)": "merge",
    }
    costs = pipeline_costs(
        nsamples, n_unpadded, fund_hi, harm_hi, max_slope=max_slope
    )
    rows = []
    total = 0.0
    for c in costs:
        t = max(c.t_mxu(peak), c.t_hbm(bw))
        total += t
        rows.append(
            {
                "stage": c.name,
                "scope": scope_of.get(c.name, "merge"),
                "t_ms": t * 1e3,
                "bound": c.bound(peak, bw),
            }
        )
    for r in rows:
        r["fraction"] = (r["t_ms"] / 1e3 / total) if total > 0 else 0.0
    return rows


@dataclass
class ProfilerRecords:
    """Typed result of one xplane collection: the normalized device
    records plus, when anything went wrong, a human-readable warning
    saying WHAT was skipped (absent protos, unreadable file, parse
    failure) instead of a silent ``[]``.  Iterable/truthy/len-able like
    the bare list the old best-effort version returned."""

    records: list = field(default_factory=list)
    path: str | None = None
    warning: str | None = None

    def __iter__(self):
        return iter(self.records)

    def __len__(self) -> int:
        return len(self.records)

    def __bool__(self) -> bool:
        return bool(self.records)


_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_HLO_NAME_RE = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+)")


def hlo_op_scopes(module_text: str) -> dict[str, str]:
    """HLO instruction name -> its ``op_name`` metadata, for every
    instruction of a compiled module (``compiled.as_text()``) whose
    op_name holds a registered scope.  A TPU profile names each device
    event after the HLO instruction it ran (``%fusion.42 = ...``) and
    carries no op metadata (seen on a v5e, PR 21), so this map is what
    attributes those events to their stage (:func:`stage_records`)."""
    out: dict[str, str] = {}
    for line in module_text.splitlines():
        if " = " not in line:
            continue
        src = _OP_NAME_RE.search(line)
        if src is None or stage_of_op_name(src.group(1)) is None:
            continue
        out[_HLO_NAME_RE.match(line).group(1)] = src.group(1)
    return out


def decode_profile_planes(data) -> list[dict]:
    """Best-effort decode of a ``jax.profiler.ProfileData`` object into
    plain plane dicts ``[{name, lines: [{name, events: [{name, start_ns,
    duration_ns}]}]}]`` — the only shape :func:`parse_plane_dicts`
    consumes, so the pure parse is unit-testable on committed synthetic
    fixtures without a profiler run."""
    planes: list[dict] = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                events.append(
                    {
                        "name": getattr(ev, "name", "?"),
                        "start_ns": getattr(ev, "start_ns", None),
                        "duration_ns": getattr(ev, "duration_ns", 0),
                    }
                )
            lines.append(
                {"name": getattr(line, "name", ""), "events": events}
            )
        planes.append({"name": getattr(plane, "name", ""), "lines": lines})
    return planes


def is_device_plane(name: str) -> bool:
    return "device" in name.lower() or "TPU" in name


def parse_plane_dicts(planes: list[dict]) -> list[dict]:
    """Pure parse of decoded xplane plane dicts into normalized device
    records in ``tracing.add_device_records`` form.

    Device-plane selection: plane names containing ``device`` (any
    case) or ``TPU`` — host planes (``/host:CPU``) are skipped, which
    is why a chip-free collection is legitimately empty.  Timestamps
    are rebased so the earliest device event sits at 0; good enough to
    interleave device kernels with host spans on one Perfetto timeline,
    not for sub-µs cross-clock precision.  No jax, no IO — unit-tested
    on a committed synthetic fixture (``tests/golden``)."""
    records: list[dict] = []
    for plane in planes:
        pname = str(plane.get("name", ""))
        if not is_device_plane(pname):
            continue
        for line in plane.get("lines", []) or []:
            lane = f"device:{line.get('name') or pname}"
            for ev in line.get("events", []) or []:
                start_ns = ev.get("start_ns")
                if not isinstance(start_ns, (int, float)):
                    continue
                dur_ns = ev.get("duration_ns") or 0
                records.append(
                    {
                        "name": ev.get("name", "?"),
                        "tid": lane,
                        "ts_us": start_ns / 1e3,
                        "dur_us": dur_ns / 1e3,
                        "end_us": (start_ns + dur_ns) / 1e3,
                        "args": {"measured": True},
                    }
                )
    if not records:
        return []
    t0 = min(r["ts_us"] for r in records)
    for r in records:
        for k in ("ts_us", "end_us"):
            r[k] = round(r[k] - t0, 1)
    return records


def stage_records(
    records: list[dict],
    lane: str = "device:measured",
    op_scopes: dict[str, str] | None = None,
) -> list[dict]:
    """Fold raw profiler device records into per-STAGE measured records:
    events whose op name resolves through :func:`stage_of_op_name` are
    renamed to their ``erp.<stage>`` scope and moved onto ``lane``;
    unattributed events are dropped — the raw records still carry them.
    An event named after an HLO instruction (the TPU's) resolves through
    ``op_scopes`` (:func:`hlo_op_scopes`).  Pure record construction,
    no jax."""
    out = []
    for r in records:
        name = r.get("name") or ""
        op = name
        if op_scopes:
            op = op_scopes.get(_HLO_NAME_RE.match(name).group(1), name)
        stage = stage_of_op_name(op)
        if stage is None:
            continue
        out.append(
            {
                "name": SCOPE_PREFIX + stage,
                "tid": lane,
                "ts_us": r["ts_us"],
                "dur_us": r["dur_us"],
                "end_us": r["end_us"],
                "args": {"measured": True, "stage": stage,
                         "op": r.get("name", "?")},
            }
        )
    return out


def collect_profiler_device_records(logdir: str) -> ProfilerRecords:
    """Device events from a ``jax.profiler`` trace session (layer 6):
    locate the newest ``*.xplane.pb`` under ``logdir``, decode it via
    ``jax.profiler.ProfileData``, and run the pure
    :func:`parse_plane_dicts` over the decoded planes.

    Returns a :class:`ProfilerRecords`; every failure mode (ProfileData
    unavailable, no protos, unreadable file, decode error, no device
    events in the decoded planes) sets
    ``warning`` and logs it instead of silently returning ``[]`` —
    a missing profile should be diagnosable, not invisible."""
    import glob as _glob
    import os as _os

    from . import logging as _erplog

    def _warn(msg: str, path: str | None = None) -> ProfilerRecords:
        _erplog.warn("devicecost: %s\n", msg)
        return ProfilerRecords(path=path, warning=msg)

    try:
        from jax.profiler import ProfileData  # type: ignore
    except Exception as e:
        return _warn(f"jax.profiler.ProfileData unavailable ({e}); "
                     "cannot parse xplane protos")
    paths = sorted(
        _glob.glob(
            _os.path.join(logdir, "**", "*.xplane.pb"), recursive=True
        )
    )
    if not paths:
        return _warn(f"no *.xplane.pb under {logdir!r} "
                     "(profiler session produced nothing?)")
    path = paths[-1]
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError as e:
        return _warn(f"unreadable xplane proto {path!r}: {e}", path)
    try:
        data = ProfileData.from_serialized_xspace(raw)
        planes = decode_profile_planes(data)
    except Exception as e:
        return _warn(f"failed to decode xplane proto {path!r}: {e}", path)
    records = parse_plane_dicts(planes)
    if not records:
        names = [p["name"] for p in planes]
        what = (
            "its device planes hold no events"
            if any(is_device_plane(n) for n in names)
            else "it holds no device plane"
        )
        return _warn(f"xplane {path!r} decoded but {what} (planes: {names})",
                     path)
    return ProfilerRecords(records=records, path=path)


# ---------------------------------------------------------------------------
# artifact validation (shared by tools/metrics_report.py --check)


def _is_num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def validate_hlo_attrib(doc) -> list[str]:
    """Structural check of an ``erp-hlo-attrib/1`` artifact; returns a
    list of problems (empty = valid).  Hand-rolled: the container has no
    jsonschema."""
    errs: list[str] = []
    if not isinstance(doc, dict):
        return ["not a JSON object"]
    if doc.get("schema") != ATTRIB_SCHEMA:
        errs.append(
            f"schema is {doc.get('schema')!r}, expected {ATTRIB_SCHEMA!r}"
        )
    for key in ("total_bytes", "attributed_bytes", "attributed_fraction"):
        if not _is_num(doc.get(key)):
            errs.append(f"missing numeric {key}")
    if not _is_num(doc.get("batch")) or doc.get("batch", 0) <= 0:
        errs.append("missing positive batch")
    frac = doc.get("attributed_fraction")
    if _is_num(frac) and not (0.0 <= frac <= 1.0):
        errs.append(f"attributed_fraction {frac} outside [0, 1]")
    stages = doc.get("stages")
    if not isinstance(stages, dict):
        errs.append("missing stages object")
    else:
        for name, row in stages.items():
            if not isinstance(row, dict) or not _is_num(
                row.get("out_bytes")
            ):
                errs.append(f"stage {name}: missing numeric out_bytes")
    if not isinstance(doc.get("unattributed_top"), list):
        errs.append("missing unattributed_top list")
    return errs


def validate_cost_ledger(doc) -> list[str]:
    """Structural check of an ``erp-cost-ledger/1`` ledger document."""
    errs: list[str] = []
    if not isinstance(doc, dict):
        return ["not a JSON object"]
    if doc.get("schema") != "erp-cost-ledger/1":
        errs.append(
            f"schema is {doc.get('schema')!r}, expected 'erp-cost-ledger/1'"
        )
    rows = doc.get("rows")
    if not isinstance(rows, list):
        return errs + ["missing rows list"]
    for i, row in enumerate(rows):
        if not isinstance(row, dict):
            errs.append(f"row {i}: not an object")
            continue
        if not row.get("file"):
            errs.append(f"row {i}: missing file")
        for key in ("gb_per_template", "ideal_gb_per_template"):
            if not _is_num(row.get(key)):
                errs.append(f"row {i}: missing numeric {key}")
        stages = row.get("layout_gb_per_template")
        if not isinstance(stages, dict) or not all(
            _is_num(v) for v in stages.values()
        ):
            errs.append(
                f"row {i}: layout_gb_per_template must map stages to numbers"
            )
    return errs
