#!/usr/bin/env python3
"""Chip smoke: the BRP search driver end to end on one TPU, at the shipped
2^22-sample workunit width.

One process (a chip belongs to one process at a time), nothing from outside
the repo: the workunit, bank and zaplist are generated from ``--seed`` at the
geometry of the Arecibo test WU the reference benchmarks
(``bench_single.sh:28``: ``-A 0.08 -P 3.0 -f 400.0 -W``), with a binary
pulsar injected at one bank template.

Phases, in order (each prints its wall seconds):

* build      -- ``make -B -C native``: the native median, never the slow
                device fallback;
* device     -- the first JAX device must be a TPU;
* workunit   -- 2^22 4-bit samples at 65.476 us, 512 PALFA-range templates;
* main       -- ``python -m boinc_app_eah_brp_tpu`` in-process; the result
                file, the top candidate and the run report are checked;
* whiten     -- the same WU whitened once more in-process (the Pallas arm and
                the oracle reuse it);
* steady     -- the full bank through ``run_bank`` twice (the second run
                times the warm step): the production step, whose resampler
                is the resident Pallas chain on a TPU;
* oracle     -- the first 10 templates (null and injected among them) on the
                chip vs the host float64 oracle: recall >= the floor of
                ``PRECISION_BASELINE.json``;
* profile    -- one short profiler window: device records per erp.* stage;
* pallas     -- the full bank again with the fused sumspec and resident
                resample kernels compiled for the chip, and once on the
                degradation ladder's XLA rung (``allow_pallas=False``):
                candidates identical to the XLA rung's, no fallback;
* served     -- the WU twice through ``serving.FleetServer``: both results
                byte-identical to the driver's, zero recompiles the second
                time.

The numbers printed as ``info`` are information, not claims.  The last line
of stdout is ``{"ok": true, "device": {...}}``; any failure exits non-zero
without it.  ``--four-chips`` runs only the multi-chip phase instead.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

RESULT_DATE = "2008-11-12T00:00:00+00:00"  # byte-comparable result headers
N_ORACLE = 10  # templates of the oracle comparison: the head of the bank
INJECTED = 3  # bank index of the injected template (0 is the null template)


class SmokeError(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass(frozen=True)
class Shape:
    """A workunit class: data geometry, injected pulsar, bank ranges."""

    nsamples: int
    tsample_us: float
    f_signal: float  # Hz
    P_orb: float  # s
    tau: float  # light seconds
    psi0: float  # rad
    amp: float
    P_range: tuple[float, float]
    tau_max: float
    n_bank: int
    extra_args: tuple[str, ...] = ()


# the shipped WU's geometry and the shipped PALFA bank's ranges
# (P 660-2231 s, tau <= 0.335 lt-s; tools/_aot_common.py)
FULL = Shape(1 << 22, 65.476, 113.17, 1500.0, 0.2, 1.0, 1.0,
             (660.0, 2231.0), 0.335, 512)
# CPU-test size: the test fixtures' regime (t_obs ~2 s vs P_orb ~2 s)
TINY = Shape(4096, 500.0, 33.0, 2.2, 0.04, 1.2, 7.0, (1.5, 3.0), 0.1, 16,
             ("-B", "200"))


@dataclasses.dataclass
class Ctx:
    workdir: str
    shape: Shape
    seed: int = 0
    info: dict = dataclasses.field(default_factory=dict)
    # filled by the phases
    device: dict | None = None
    wu: str = ""
    bank: str = ""
    zap: str = ""
    bank_arrays: tuple | None = None
    geom: object = None
    derived: object = None
    cfg: object = None
    white: np.ndarray | None = None
    batch: int = 0
    main_out: str = ""
    step_cache: dict = dataclasses.field(default_factory=dict)


# --- helpers -----------------------------------------------------------------


def driver_argv(ctx: Ctx, out: str, cp: str, *extra: str) -> list[str]:
    return [
        "-i", ctx.wu, "-o", out, "-t", ctx.bank, "-c", cp, "-l", ctx.zap,
        "-A", "0.08", "-P", "3.0", "-f", "400.0", "-W",
        *ctx.shape.extra_args, *extra,
    ]


def read_report(metrics_file: str) -> dict:
    with open(metrics_file + ".report.json") as f:
        return json.load(f)


def counter(report: dict, name: str):
    row = report["metrics"]["counters"].get(name)
    return 0 if row is None else row["value"]


def candidate_lines(ctx: Ctx, M, T) -> list[str]:
    """Result-file candidate lines from a device (M, T) state, through the
    driver's own conversion (``runtime/session.py``)."""
    from boinc_app_eah_brp_tpu.io.results import format_candidate_line
    from boinc_app_eah_brp_tpu.oracle.stats import base_thresholds
    from boinc_app_eah_brp_tpu.oracle.toplist import finalize_candidates
    from boinc_app_eah_brp_tpu.runtime.session import _state_to_candidates

    P, tau, psi = ctx.bank_arrays
    cands = _state_to_candidates(
        np.asarray(M), np.asarray(T),
        P.astype(np.float32), tau.astype(np.float32), psi.astype(np.float32),
        base_thresholds(ctx.cfg.fA, ctx.derived.fft_size), ctx.geom,
    )
    return [
        format_candidate_line(c, ctx.derived.t_obs)
        for c in finalize_candidates(cands, ctx.derived.t_obs)
    ]


def file_candidate_lines(path: str) -> list[str]:
    from boinc_app_eah_brp_tpu.io.results import split_result_sections

    with open(path) as f:
        _, lines, done = split_result_sections(f.read())
    check(done, f"{path} does not end with %DONE%")
    return [ln if ln.endswith("\n") else ln + "\n" for ln in lines]


def run_bank(ctx: Ctx, stop: int | None = None, progress_cb=None,
             allow_pallas: bool = True):
    """The bank through the production dispatch loop; ``allow_pallas=False``
    runs the degradation ladder's XLA rung instead."""
    import jax

    from boinc_app_eah_brp_tpu.models.search import run_bank as _run_bank

    P, tau, psi = ctx.bank_arrays
    M, T = _run_bank(
        ctx.white, P, tau, psi, ctx.geom, batch_size=ctx.batch,
        stop_template=stop, progress_cb=progress_cb, step_cache=ctx.step_cache,
        allow_pallas=allow_pallas,
    )
    return jax.block_until_ready((M, T))


def lowered_step(ctx: Ctx):
    """The production step as ``run_bank`` dispatches it, lowered for the
    whole bank."""
    import jax.numpy as jnp

    from boinc_app_eah_brp_tpu.models.search import (
        bank_params_host,
        init_state,
        make_bank_step,
        prepare_ts,
        upload_bank,
    )

    P, tau, psi = ctx.bank_arrays
    bp = upload_bank(bank_params_host(P, tau, psi, ctx.geom.dt), ctx.batch)
    return make_bank_step(ctx.geom, ctx.batch).lower(
        prepare_ts(ctx.geom, ctx.white), *bp, jnp.int32(0),
        jnp.int32(len(P)), *init_state(ctx.geom),
    )


def in_memory_metrics():
    from boinc_app_eah_brp_tpu.runtime import metrics

    metrics.configure(force=True)
    return metrics


# --- phases ------------------------------------------------------------------


def phase_build(ctx: Ctx) -> None:
    import bench

    check(bench.ensure_native(rebuild=True), "native median unavailable")


def phase_device(ctx: Ctx, require_tpu: bool = True) -> None:
    import jax

    devs = jax.devices()
    ctx.device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    say(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    if require_tpu:
        check(devs[0].platform == "tpu",
              f"no TPU: JAX's first device is {devs[0].platform}")


def make_workunit(ctx: Ctx) -> None:
    """The WU (pulsar injected at bank[INJECTED]), the bank (null template
    first) and a zaplist, all from ``ctx.seed``."""
    from fixtures import synthetic_timeseries

    from boinc_app_eah_brp_tpu.io import write_workunit
    from boinc_app_eah_brp_tpu.io.templates import TemplateBank, write_template_bank

    s = ctx.shape
    rng = np.random.default_rng(ctx.seed)
    n = s.n_bank
    P = rng.uniform(*s.P_range, n)
    tau = rng.uniform(0.0, s.tau_max, n)
    psi = rng.uniform(0.0, 2 * np.pi, n)
    P[0], tau[0], psi[0] = 1000.0, 0.0, 0.0  # the null template
    P[INJECTED], tau[INJECTED], psi[INJECTED] = s.P_orb, s.tau, s.psi0
    ctx.bank_arrays = (P, tau, psi)
    ctx.bank = os.path.join(ctx.workdir, "bank")
    write_template_bank(ctx.bank, TemplateBank(P, tau, psi))

    ts = synthetic_timeseries(
        s.nsamples, tsample_us=s.tsample_us, f_signal=s.f_signal,
        P_orb=s.P_orb, tau=s.tau, psi0=s.psi0, amp=s.amp, seed=ctx.seed,
    )
    ctx.wu = os.path.join(ctx.workdir, "wu.bin4")
    write_workunit(ctx.wu, ts, tsample_us=s.tsample_us, scale=1.0, dm=55.5)
    ctx.zap = os.path.join(ctx.workdir, "zap")
    with open(ctx.zap, "w") as f:
        f.write("59.9 60.1\n119.9 120.1\n")


def phase_main(ctx: Ctx) -> None:
    """The CLI entry, in-process, then its result file and run report."""
    from boinc_app_eah_brp_tpu.runtime import cli

    out = os.path.join(ctx.workdir, "out.cand")
    mfile = os.path.join(ctx.workdir, "metrics.jsonl")
    rc = cli.main(driver_argv(
        ctx, out, os.path.join(ctx.workdir, "cp.bin"),
        "--mesh", "1", "--metrics-file", mfile,
    ))
    check(rc == 0, f"driver exited {rc}")
    lines = file_candidate_lines(out)
    check(lines, "no candidates")
    rows = [ln.split() for ln in lines]
    check(all(len(r) == 7 for r in rows), "candidate lines are not 7 columns")
    top = max(rows, key=lambda r: float(r[4]))
    f, P_b, tau = float(top[0]), float(top[1]), float(top[2])
    s = ctx.shape
    say(f"top candidate: f={f} P_b={P_b} tau={tau} power={top[4]} "
        f"n_harm={top[6]}")
    check(abs(P_b - s.P_orb) < 1e-6 * s.P_orb and abs(tau - s.tau) < 1e-6,
          f"top candidate at (P_b, tau)=({P_b}, {tau}), injected "
          f"({s.P_orb}, {s.tau})")
    bin_hz = 1.0 / (s.nsamples * s.tsample_us * 1e-6 * 3.0)
    check(min(abs(f - s.f_signal), abs(f - 2 * s.f_signal)) < 4 * bin_hz,
          f"top candidate at {f} Hz, injected {s.f_signal} Hz")

    report = read_report(mfile)
    check(report["ok"], f"run report exit status {report['exit_status']}")
    check(counter(report, "checkpoint.count") >= 1, "no checkpoint written")
    for name in ("resilience.pallas_fallback", "resilience.batch_halved"):
        check(counter(report, name) == 0, f"{name} = {counter(report, name)}")
    # on a TPU the shipped geometry fits the resident chain: every template
    # goes through it; the tiny CPU bank keeps the XLA resampler
    n_res = counter(report, "search.templates_resident")
    on_tpu = ctx.device is not None and ctx.device["platform"] == "tpu"
    want = counter(report, "search.templates") if on_tpu else 0
    check(n_res == want, f"search.templates_resident = {n_res}, not {want}")
    phases = report["metrics"]["phases"]
    ctx.info.update(
        main_compile_s=counter(report, "jax.compile_time_s"),
        main_whitening_s=phases["whitening"]["wall_s"],
        main_template_loop_s=phases["template loop"]["wall_s"],
        main_rescore_s=phases.get("oracle rescore", {}).get("wall_s"),
        main_wall_s=report["wall_s"],
    )
    ctx.batch = int(report["metrics"]["gauges"]["autobatch.batch_size"]["value"])
    ctx.main_out = out


def phase_whiten(ctx: Ctx) -> None:
    """Geometry exactly as the Session derives it, and the whitened host
    series, once."""
    from boinc_app_eah_brp_tpu.io import read_workunit, read_zaplist
    from boinc_app_eah_brp_tpu.models.search import (
        SearchGeometry,
        lut_step_for_bank,
        lut_tiles_for_bank,
        max_slope_for_bank,
    )
    from boinc_app_eah_brp_tpu.ops.whiten import whiten_and_zap
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig

    wu = read_workunit(ctx.wu)
    window = (
        int(ctx.shape.extra_args[ctx.shape.extra_args.index("-B") + 1])
        if "-B" in ctx.shape.extra_args else 1000
    )
    ctx.cfg = SearchConfig(f0=400.0, padding=3.0, fA=0.08, window=window,
                           white=True)
    ctx.derived = DerivedParams.derive(
        wu.nsamples, float(wu.header["tsample"]), ctx.cfg
    )
    P, tau, psi = ctx.bank_arrays
    ctx.geom = SearchGeometry.from_derived(
        ctx.derived,
        max_slope=max_slope_for_bank(P, tau),
        lut_step=lut_step_for_bank(P, ctx.derived.dt),
        lut_tiles=lut_tiles_for_bank(P, psi, ctx.derived.n_unpadded,
                                     ctx.derived.dt),
        exact_mean=False,
    )
    t0 = time.perf_counter()
    ctx.white = np.asarray(
        whiten_and_zap(wu.samples, ctx.derived, ctx.cfg, read_zaplist(ctx.zap)),
        dtype=np.float32,
    )
    ctx.info["whitening_s"] = time.perf_counter() - t0


def phase_steady(ctx: Ctx) -> None:
    run_bank(ctx)  # compile (or load from the persistent cache) + one pass
    t0 = time.perf_counter()
    M, T = run_bank(ctx)
    wall = time.perf_counter() - t0
    ctx.info["steady_templates_per_s"] = ctx.shape.n_bank / wall
    check(candidate_lines(ctx, M, T),
          "the production step emitted no candidates")


def phase_oracle(ctx: Ctx) -> None:
    """Chip vs host float64 oracle on the head of the bank, scored with the
    validator's matching (``runtime/precision.py``)."""
    from boinc_app_eah_brp_tpu.models.search import state_to_natural
    from boinc_app_eah_brp_tpu.oracle.resample import ResampleParams
    from boinc_app_eah_brp_tpu.oracle.stats import base_thresholds
    from boinc_app_eah_brp_tpu.runtime import precision as pr

    g, d = ctx.geom, ctx.derived
    P, tau, psi = (a[:N_ORACLE] for a in ctx.bank_arrays)
    white64 = ctx.white.astype(np.float64)
    M64 = np.zeros((5, g.fund_hi), np.float64)
    T64 = np.zeros((5, g.fund_hi), np.int32)
    for t in range(N_ORACLE):
        rp = ResampleParams.from_template(P[t], tau[t], psi[t], d.dt,
                                          d.nsamples, d.n_unpadded)
        res, _ = pr.resample_f64(white64, rp)
        sums = pr.harmonic_maxima(pr.power_spectrum_f64(res, d.nsamples),
                                  g.window_2, g.fund_hi, g.harm_hi)
        better = sums > M64
        M64, T64 = np.where(better, sums, M64), np.where(better, t, T64)
    thr = base_thresholds(ctx.cfg.fA, d.fft_size)
    rows64 = pr.toplist_rows(M64, T64, P, tau, psi, thr, g.window_2, d.t_obs)

    M, T = run_bank(ctx, stop=N_ORACLE)
    rows = pr.toplist_rows(state_to_natural(M, g), state_to_natural(T, g),
                           P, tau, psi, thr, g.window_2, d.t_obs)
    scores = pr.candidate_scores(rows64, rows, d.t_obs)
    with open(os.path.join(REPO, "PRECISION_BASELINE.json")) as f:
        floor = json.load(f)["recall_min"]
    say(f"oracle: {scores}")
    check(scores["oracle_n"] > 0, "the oracle emitted no candidates")
    check(scores["recall_at_tol"] >= floor,
          f"recall {scores['recall_at_tol']} below the floor {floor}")
    ctx.info["oracle_recall"] = scores["recall_at_tol"]


def phase_pallas(ctx: Ctx, interpret: bool = False) -> None:
    """The full bank with the fused sumspec fold and the resident
    resample->fftprep kernels, compiled for the device (interpret mode off),
    against the degradation ladder's XLA rung."""
    import jax

    from boinc_app_eah_brp_tpu.models.search import (
        use_pallas_resident,
        use_pallas_sumspec,
    )

    gates = {"ERP_PALLAS_SUMSPEC": "1", "ERP_PALLAS_RESIDENT": "1",
             "ERP_PALLAS_INTERPRET": "1" if interpret else "0"}
    saved = {k: os.environ.get(k) for k in gates}
    jax.clear_caches()
    os.environ.update(gates)
    metrics = in_memory_metrics()
    try:
        check(use_pallas_sumspec(ctx.geom) and use_pallas_resident(ctx.geom),
              "the Pallas gates refuse this geometry")
        check(interpret or "tpu_custom_call" in lowered_step(ctx).as_text(),
              "no tpu_custom_call in the lowered step")
        run_bank(ctx)  # compile + one pass
        t0 = time.perf_counter()
        M, T = run_bank(ctx)
        ctx.info["pallas_templates_per_s"] = (
            ctx.shape.n_bank / (time.perf_counter() - t0)
        )
        snap = metrics.snapshot()["counters"]
        fallback = snap.get("resilience.pallas_fallback", {}).get("value", 0)
        check(fallback == 0, f"Pallas fell back to XLA {fallback} times")
    finally:
        metrics.finish(0)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        jax.clear_caches()
    M0, T0 = (np.asarray(a) for a in run_bank(ctx, allow_pallas=False))
    xla_lines = candidate_lines(ctx, M0, T0)
    lines = candidate_lines(ctx, M, T)
    M, T = np.asarray(M), np.asarray(T)
    ctx.info["pallas_state_bit_identical"] = bool(
        np.array_equal(M0, M) and np.array_equal(T0, T)
    )
    ctx.info["pallas_state_diff"] = {
        "M_entries": int(np.sum(M0 != M)),
        "T_entries": int(np.sum(T0 != T)),
        "M_max_rel": float(np.max(np.abs(M - M0) / np.maximum(np.abs(M0), 1e-30))),
        "entries": int(M0.size),
    }
    if lines != xla_lines:
        diff = sum(a != b for a, b in zip(lines, xla_lines))
        raise SmokeError(
            f"Pallas candidates differ from the XLA rung's: {len(lines)} vs "
            f"{len(xla_lines)} lines, {diff} differing"
        )


def phase_served(ctx: Ctx) -> None:
    from boinc_app_eah_brp_tpu.runtime import cli
    from boinc_app_eah_brp_tpu.serving import FleetServer

    with open(ctx.main_out, "rb") as f:
        ref = f.read()
    results = []
    with FleetServer(name="smoke") as server:
        for i in range(2):
            args = cli.parse_args(driver_argv(
                ctx, os.path.join(ctx.workdir, f"served{i}.cand"),
                os.path.join(ctx.workdir, f"served{i}.cp"),
            ))
            check(not isinstance(args, int), "driver arguments refused")
            results.append(server.process(args, corr_id=f"smoke-{i}"))
    for i, r in enumerate(results):
        check(r.ok, f"served WU {i} failed: {r.error}")
        with open(r.outputfile, "rb") as f:
            check(f.read() == ref, f"served WU {i} differs from the driver's")
    check(results[1].recompiles == 0,
          f"second served WU recompiled {results[1].recompiles} times")
    ctx.info["served_wall_s"] = [r.wall_s for r in results]


def phase_profile(ctx: Ctx) -> None:
    """One profiler window over the head of the bank; the TPU names its
    events after HLO instructions, so the compiled step's text maps them
    to their erp.* stage."""
    from boinc_app_eah_brp_tpu.runtime import devicecost, steptime

    scopes = devicecost.hlo_op_scopes(lowered_step(ctx).compile().as_text())
    logdir = os.path.join(ctx.workdir, "profile")
    with steptime.capture_profile(logdir, op_scopes=scopes) as cap:
        run_bank(ctx, stop=N_ORACLE)
    shutil.rmtree(logdir, ignore_errors=True)  # too large to bring back
    counts: dict[str, int] = {}
    for r in cap.stage_records:
        counts[r["name"]] = counts.get(r["name"], 0) + 1
    ctx.info["profile_device_records"] = len(cap.records)
    ctx.info["profile_stage_records"] = counts
    ctx.info["profile_stage_ms"] = cap.stage_ms
    if cap.warning:
        ctx.info["profile_warning"] = cap.warning


def phase_four_chips(ctx: Ctx, n_dev: int = 4) -> None:
    """The driver with ``--mesh n_dev`` vs the single-device ``run_bank`` on
    device 0 in this process: (M, T) bit-identical, candidates identical,
    and every device of the mesh holding the series, bank and state.  Both
    run the default step, whose per-batch body the mesh shares with one
    chip: on a TPU the resident Pallas chain, on every shard and on device
    0 alike, and the ``--mesh`` run's report counts every template
    resident."""
    import jax

    from boinc_app_eah_brp_tpu.models.search import use_pallas_resident
    from boinc_app_eah_brp_tpu.parallel import make_mesh, run_bank_sharded
    from boinc_app_eah_brp_tpu.runtime import cli

    check(len(jax.devices()) >= n_dev, f"{len(jax.devices())} devices < {n_dev}")
    out = os.path.join(ctx.workdir, "mesh.cand")
    mfile = os.path.join(ctx.workdir, "mesh-metrics.jsonl")
    rc = cli.main(driver_argv(
        ctx, out, os.path.join(ctx.workdir, "mesh.cp"),
        "--mesh", str(n_dev), "--no-rescore", "--metrics-file", mfile,
    ))
    check(rc == 0, f"driver --mesh {n_dev} exited {rc}")
    phase_whiten(ctx)
    from boinc_app_eah_brp_tpu.runtime.autobatch import choose_batch

    report = read_report(mfile)
    n_t = counter(report, "search.templates")
    n_res = counter(report, "search.templates_resident")
    want = n_t if use_pallas_resident(ctx.geom) else 0
    check(n_t > 0 and n_res == want,
          f"driver --mesh {n_dev}: search.templates_resident = {n_res}, not "
          f"{want} of {n_t}")
    check(counter(report, "resilience.pallas_fallback") == 0,
          "the mesh fell back to the XLA rung")
    ctx.info["mesh_templates_resident"] = n_res

    ctx.batch = choose_batch(ctx.geom.nsamples)
    M1, T1 = run_bank(ctx)
    M1, T1 = np.asarray(M1), np.asarray(T1)

    seen = {}

    def probe(done, total, M, T):
        if seen:
            return True
        seen["state_devices"] = len(M.sharding.device_set)
        seen["bytes_in_use"] = [
            (d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in jax.devices()[:n_dev]
        ]
        return True

    P, tau, psi = ctx.bank_arrays
    M4, T4 = run_bank_sharded(
        ctx.white, P, tau, psi, ctx.geom, make_mesh(n_dev),
        per_device_batch=min(ctx.batch, -(-len(P) // n_dev)),  # the driver's
        progress_cb=probe,
    )
    M4, T4 = np.asarray(M4), np.asarray(T4)
    check(np.array_equal(M4, M1) and np.array_equal(T4, T1),
          f"sharded (M, T) differ from device 0's: "
          f"{int(np.sum(M4 != M1))} M and {int(np.sum(T4 != T1))} T entries")
    check(file_candidate_lines(out) == candidate_lines(ctx, M1, T1),
          f"driver --mesh {n_dev} candidates differ from device 0's")
    check(seen.get("state_devices") == n_dev,
          f"(M, T) live on {seen.get('state_devices')} devices, not {n_dev}")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n_dev]]
    ctx.info.update(mesh_bytes_in_use=seen["bytes_in_use"],
                    mesh_peak_bytes=peaks)
    if jax.devices()[0].memory_stats() is None:
        return  # the CPU backend reports no device memory
    floor = ctx.white.nbytes + M1.nbytes + T1.nbytes
    check(all(b >= floor for b in seen["bytes_in_use"]),
          f"a device holds less than the series + state ({floor} B): "
          f"{seen['bytes_in_use']}")
    check(min(peaks) >= 0.5 * max(peaks), f"uneven device peaks {peaks}")


# --- driver ------------------------------------------------------------------


def tidy(workdir: str) -> None:
    """Drop what is too large to bring back from the chip: the WU and the
    checkpoints."""
    for name in os.listdir(workdir):
        path = os.path.join(workdir, name)
        if os.path.isfile(path) and os.path.getsize(path) > 1 << 20:
            os.remove(path)


def run_phases(ctx: Ctx, phases) -> float:
    t_all = time.perf_counter()
    for name, fn in phases:
        t0 = time.perf_counter()
        fn(ctx)
        say(f"phase {name}: {time.perf_counter() - t0:.3f} s")
    return time.perf_counter() - t_all


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", default=os.path.join(REPO, "chiprun_out",
                                                      "smoke"))
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh phase")
    a = ap.parse_args(argv)
    os.makedirs(a.workdir, exist_ok=True)
    os.environ["ERP_RESULT_DATE"] = RESULT_DATE
    ctx = Ctx(workdir=a.workdir, shape=FULL, seed=a.seed)
    if a.four_chips:
        phases = [("build", phase_build), ("device", phase_device),
                  ("workunit", make_workunit), ("four-chips", phase_four_chips)]
    else:
        phases = [
            ("build", phase_build), ("device", phase_device),
            ("workunit", make_workunit), ("main", phase_main),
            ("whiten", phase_whiten), ("steady", phase_steady),
            ("oracle", phase_oracle), ("profile", phase_profile),
            ("pallas", phase_pallas), ("served", phase_served),
        ]
    try:
        wall = run_phases(ctx, phases)
    except (SmokeError, SystemExit, Exception) as e:  # noqa: BLE001
        print(f"chip_smoke: FAIL: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    finally:
        tidy(a.workdir)
    import jax

    dev = jax.devices()[0]
    ctx.info["peak_hbm_bytes"] = (dev.memory_stats() or {}).get(
        "peak_bytes_in_use")
    ctx.info["smoke_wall_s"] = wall
    say("info (not claims): " + json.dumps(ctx.info, default=str))
    print(json.dumps({"ok": True, "device": ctx.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
