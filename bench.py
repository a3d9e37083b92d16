"""Benchmark: orbital templates/sec on the reference's own protocol.

Reproduces ``debian/extra/einstein_bench/bench_single.sh:28`` — the shipped
2^22-sample Arecibo test workunit with the 6,662-template bank under
``-A 0.08 -P 3.0 -f 400.0 -W`` (whitening + zaplist) — and times the batched
TPU search step in steady state. Baseline is the reference's only citable
throughput number: ~2 templates/s implied by the Debian progress-cadence
comment (``debian/rules:162-163``; BASELINE.md).

Prints exactly one JSON line:
    {"metric": ..., "value": N, "unit": "templates/sec", "vs_baseline": N,
     "device": {"platform": ..., "kind": ..., "count": N}, ...}

One process, on the chip.  Without a TPU it exits non-zero, unless
``JAX_PLATFORMS=cpu`` asks for the CPU backend explicitly: that run's
numbers are labeled as CPU-backend numbers and carry no device metrics.

Env knobs: BENCH_BATCH (default: the driver's autobatch choice),
BENCH_TEMPLATES (timed templates, default 256), BENCH_SYNTH=1 (force
synthetic WU).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

TESTWU = "/root/reference/debian/extra/einstein_bench/testwu"
WU = os.path.join(TESTWU, "p2030.20151015.G187.41-00.88.N.b2s0g0.00000_1099.bin4")
BANK = os.path.join(TESTWU, "stochastic_full.bank")
ZAP = os.path.join(TESTWU, "p2030.20151015.G187.41-00.88.N.b2s0g0.00000.zap")

BASELINE_TEMPLATES_PER_SEC = 2.0  # debian/rules:162-163 implied CPU rate

METRIC = (
    "orbital templates/sec/chip (2^22-sample WU, -A 0.08 -P 3.0 -f 400.0 -W)"
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_problem():
    from boinc_app_eah_brp_tpu.io.templates import read_template_bank
    from boinc_app_eah_brp_tpu.io.workunit import pack_4bit, read_workunit
    from boinc_app_eah_brp_tpu.io.zaplist import read_zaplist
    from boinc_app_eah_brp_tpu.oracle.pipeline import DerivedParams, SearchConfig

    cfg = SearchConfig(f0=400.0, padding=3.0, fA=0.08, window=1000, white=True)
    use_synth = os.environ.get("BENCH_SYNTH") == "1" or not os.path.exists(WU)
    if use_synth:
        log("bench: reference test WU unavailable, using synthetic 2^22 workunit")
        rng = np.random.default_rng(0)
        n = 1 << 22
        samples = np.clip(rng.normal(4.0, 1.5, n).round(), 0, 15).astype(np.float32)
        tsample_us = 65.476
        nb = 6662
        P = np.concatenate([[1000.0], rng.uniform(3000.0, 50000.0, nb - 1)])
        tau = np.concatenate([[0.0], rng.uniform(0.0, 3.0, nb - 1)])
        psi = np.concatenate([[0.0], rng.uniform(0.0, 2 * np.pi, nb - 1)])
        zap_ranges = np.array([[60.0, 60.2], [119.9, 120.1]], dtype=np.float64)
        # same 4-bit packed form the real WU ships (samples are nibbles)
        packed = (
            np.frombuffer(pack_4bit(samples, 1.0), dtype=np.uint8),
            1.0,
        )
    else:
        wu = read_workunit(WU)
        samples = wu.samples
        tsample_us = float(wu.header["tsample"])
        n = wu.nsamples
        bank = read_template_bank(BANK)
        P, tau, psi = bank.P, bank.tau, bank.psi0
        zap_ranges = read_zaplist(ZAP)
        packed = (wu.raw, float(wu.header["scale"])) if wu.raw is not None else None

    derived = DerivedParams.derive(n, tsample_us, cfg)
    return samples, (P, tau, psi), zap_ranges, cfg, derived, packed


def ensure_native(repo: str | None = None, log=log, rebuild: bool = False) -> bool:
    """Build the native median (``make -C native``) and refuse to run
    without it: whitening would otherwise silently take the ~47 s/pass
    device median (``ops/whiten.py``).  ``rebuild`` forces ``make -B`` even
    when a library is present, so one copied in with the tree is never
    trusted.  ``ERP_ALLOW_DEVICE_MEDIAN=1`` explicitly accepts the device
    median.  Returns True when the native median is available, False
    when the override accepted the fallback."""
    from boinc_app_eah_brp_tpu.ops.native_median import native_available

    allow = os.environ.get("ERP_ALLOW_DEVICE_MEDIAN", "").strip() == "1"
    if os.environ.get("ERP_MEDIAN", "").strip() == "device":
        # an explicit device-median request still degrades the bench the
        # same way a missing library does — require the same opt-in so a
        # stray exported A/B knob can't burn a scarce chip window
        if allow:
            log("bench: WARNING - ERP_MEDIAN=device (~47 s/pass on chip; "
                "ERP_ALLOW_DEVICE_MEDIAN=1)")
            return False
        raise SystemExit(
            "bench: ERP_MEDIAN=device would run the ~47 s/pass device "
            "median. Unset it or add ERP_ALLOW_DEVICE_MEDIAN=1."
        )
    if not rebuild and native_available():
        return True
    repo = repo or os.path.dirname(os.path.abspath(__file__))
    cmd = ["make", *(["-B"] if rebuild else []), "-C", os.path.join(repo, "native")]
    log(f"bench: building the native median: {' '.join(cmd)}")
    try:
        r = subprocess.run(
            cmd,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            timeout=600,
        )
        if r.returncode != 0:
            log(f"bench: native build failed:\n{r.stdout.decode(errors='replace')[-2000:]}")
    except (OSError, subprocess.TimeoutExpired) as e:
        log(f"bench: native build failed: {e}")
    if native_available():  # failed loads are never cached; re-probe works
        return True
    if allow:
        log(
            "bench: WARNING - proceeding with the device median "
            "(~47 s/pass on chip; ERP_ALLOW_DEVICE_MEDIAN=1)"
        )
        return False
    raise SystemExit(
        "bench: native median unavailable and the build failed - refusing "
        "to run with the silent ~47 s/pass device-median fallback. "
        "Build native/ or set ERP_ALLOW_DEVICE_MEDIAN=1."
    )


def run_bench() -> int:
    import jax

    from boinc_app_eah_brp_tpu.runtime import logging as erplog
    from boinc_app_eah_brp_tpu.runtime import metrics

    # stdout is this program's machine-read channel (one JSON line);
    # the worker logger's DEBUG lines must not land there
    erplog.route_debug_to_stderr()
    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    if dev.platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        log(f"bench: no TPU (found {device}); JAX_PLATFORMS=cpu runs the "
            "CPU backend on purpose")
        return 1
    on_chip = dev.platform == "tpu"
    ensure_native()  # refuse the silent device-median fallback (r04 #9)

    # in-memory metrics (force=True: no stream file unless ERP_METRICS_FILE
    # is also set) so the payload carries a run report — recompiles, phase
    # walls, autobatch decision — alongside the throughput number
    metrics.configure(force=True)

    # host span timeline (runtime/tracing.py): armed only when
    # $ERP_TRACE_FILE is set; the payload then carries the artifact path
    # plus the trace-derived stall breakdown (tools/trace_report.py)
    from boinc_app_eah_brp_tpu.runtime import tracing

    trace_armed = tracing.configure()
    if trace_armed:
        metrics.note_host_trace(os.environ.get(tracing.TRACE_FILE_ENV, ""))

    # warm-start: persistent compilation cache on by default, like the
    # reference's mandatory FFTW wisdom (create_wisdomf_eah_brp.sh)
    from boinc_app_eah_brp_tpu.runtime.driver import (
        compilation_cache_dir,
        enable_compilation_cache,
    )

    cache = compilation_cache_dir()
    cache_warm = bool(cache) and os.path.isdir(cache) and bool(os.listdir(cache))
    enable_compilation_cache()
    log(f"bench: compilation cache at {cache} warm={cache_warm}")

    from boinc_app_eah_brp_tpu.models.search import (
        SearchGeometry,
        bank_params_host,
        init_state,
        make_bank_step,
        upload_bank,
    )
    from boinc_app_eah_brp_tpu.ops.whiten import whiten_and_zap

    backend = jax.default_backend()
    log(f"bench: device {device}")

    samples, (P, tau, psi), zap_ranges, cfg, derived, packed = load_problem()
    log(
        f"bench: nsamples={derived.nsamples} fft_size={derived.fft_size} "
        f"fund_hi={derived.fundamental_idx_hi} harm_hi={derived.harmonic_idx_hi} "
        f"bank={len(P)}"
    )

    t0 = time.perf_counter()
    # device-resident parity halves on TPU (the driver's production path),
    # fed from the packed 4-bit payload (device nibble split, ~8x less
    # H2D); host array on CPU/GPU — prepare_ts below handles both
    with tracing.span("whitening"):
        samples = whiten_and_zap(
            samples, derived, cfg, zap_ranges, return_device_split=True,
            packed_payload=packed[0] if packed else None,
            packed_scale=packed[1] if packed else 1.0,
        )
    whitening_s = time.perf_counter() - t0
    metrics.record_phase("whitening", whitening_s)
    log(f"bench: whitening {whitening_s:.2f}s (once per WU, untimed)")

    from boinc_app_eah_brp_tpu.models.search import (
        lut_step_for_bank,
        max_slope_for_bank,
    )

    geom = SearchGeometry.from_derived(
        derived,
        max_slope=max_slope_for_bank(P, tau),
        lut_step=lut_step_for_bank(P, derived.dt),
    )
    if os.environ.get("BENCH_BATCH"):
        batch = int(os.environ["BENCH_BATCH"])
    else:
        # measured-sweep / memory-model batch (runtime/autobatch.py) —
        # the recorded bench must use the driver's actual choice
        from boinc_app_eah_brp_tpu.runtime.autobatch import choose_batch

        batch = choose_batch(geom.nsamples, log=lambda m: log("bench: " + m.rstrip()))
    batch = min(batch, len(P))
    n_timed = min(int(os.environ.get("BENCH_TEMPLATES", "256")), len(P))
    n_timed = max(batch, (n_timed // batch) * batch)  # whole batches, >= 1

    import jax.numpy as jnp

    from boinc_app_eah_brp_tpu.models.search import prepare_ts

    # the production bank-resident feed (models/search.py::run_bank):
    # params derived vectorized + uploaded once; each step slices its
    # batch on device from a scalar index
    step = make_bank_step(geom, batch)
    ts_dev = samples if isinstance(samples, tuple) else prepare_ts(geom, samples)
    M, T = init_state(geom)

    t0 = time.perf_counter()
    with tracing.span("feed-setup"):
        params = bank_params_host(P, tau, psi, geom.dt)
        dev_bank = upload_bank(params, batch)
        jax.block_until_ready(dev_bank[0])
    feed_setup_s = time.perf_counter() - t0
    metrics.record_phase("feed setup", feed_setup_s)
    n_total = jnp.int32(len(P))
    log(f"bench: bank feed setup (derive {len(P)} params + upload) "
        f"{feed_setup_s:.3f}s, once per WU")

    # warmup: compile + one steady-state batch
    t0 = time.perf_counter()
    with tracing.span("compile-first-batch"):
        M, T = step(ts_dev, *dev_bank, jnp.int32(0), n_total, M, T)
        jax.block_until_ready(M)
    compile_s = time.perf_counter() - t0
    metrics.record_phase("compile+first batch", compile_s)
    log(f"bench: compile+first batch {compile_s:.2f}s (cache_warm={cache_warm})")

    # timed async loop — the production schedule: dispatch runs ahead
    # (JAX async dispatch), one drain at the end.  Wall here is device
    # compute; any host feed work overlaps it.
    n_batches = n_timed // batch
    done = batch
    t0 = time.perf_counter()
    with tracing.span("dispatch", n_templates=n_timed):
        while done < batch + n_timed:
            start = done % (len(P) - batch + 1)
            M, T = step(ts_dev, *dev_bank, jnp.int32(start), n_total, M, T)
            done += batch
    with tracing.span("drain"):
        jax.block_until_ready(M)
    elapsed = time.perf_counter() - t0
    metrics.record_phase("timed async loop", elapsed)

    # forced-sync loop — identical steps, but drained after every
    # dispatch (lookahead=1 semantics).  Per-batch difference vs the
    # async loop is exactly the host-side feed/dispatch overhead the
    # async schedule hides; this is the tracked metric behind the
    # "overhead-bound" diagnosis (BENCH_r05, ISSUE 1).
    Ms, Ts = init_state(geom)
    done = 0
    t0s = time.perf_counter()
    with tracing.span("forced-sync-loop", n_templates=n_timed):
        while done < n_timed:
            start = done % (len(P) - batch + 1)
            Ms, Ts = step(ts_dev, *dev_bank, jnp.int32(start), n_total, Ms, Ts)
            jax.block_until_ready(Ms)
            done += batch
    sync_elapsed = time.perf_counter() - t0s
    metrics.record_phase("timed sync loop", sync_elapsed)

    async_ms = elapsed / n_batches * 1e3
    sync_ms = sync_elapsed / n_batches * 1e3
    feed_split = {
        "async_wall_per_batch_ms": round(async_ms, 3),
        "forced_sync_wall_per_batch_ms": round(sync_ms, 3),
        "overhead_per_batch_ms": round(sync_ms - async_ms, 3),
        "feed_setup_s": round(feed_setup_s, 3),
    }
    log(
        f"bench: feed split per batch: async {async_ms:.1f} ms, "
        f"forced-sync {sync_ms:.1f} ms, overhead "
        f"{sync_ms - async_ms:.1f} ms"
    )

    rate = n_timed / elapsed
    log(f"bench: {n_timed} templates in {elapsed:.2f}s -> {rate:.2f} templates/s")
    full_wu_min = len(P) / rate / 60.0
    log(f"bench: full {len(P)}-template WU projected {full_wu_min:.1f} min")
    # second north-star metric (BASELINE.md): a completed WU emits <=100
    # candidates (demod_binary.c:1630-1671), so candidates/hr follows from
    # the projected WU wall (steady-state search; whitening amortized)
    candidates_per_hr = 100.0 / (full_wu_min / 60.0)
    log(f"bench: projected candidates/hr = {candidates_per_hr:.0f}")

    # MFU / roofline accounting (VERDICT r03 #2; the reference's GFLOPS
    # model analogue, cuda_utilities.c:163-182)
    from boinc_app_eah_brp_tpu.runtime.roofline import roofline_report

    roof = roofline_report(
        geom.nsamples,
        geom.n_unpadded,
        geom.fund_hi,
        geom.harm_hi,
        max_slope=geom.max_slope,
        measured_templates_per_sec=rate if on_chip else None,
    )
    log(
        f"bench: roofline chip={roof['chip']} attainable="
        f"{roof['attainable_templates_per_sec']} t/s mfu={roof.get('mfu')} "
        f"hbm_util={roof.get('hbm_utilization')} bound={roof.get('bound')}"
    )

    metric = METRIC if on_chip else METRIC + " [CPU backend, not a device number]"
    payload = {
        "metric": metric,
        "value": round(rate, 3),
        "unit": "templates/sec",
        "vs_baseline": round(rate / BASELINE_TEMPLATES_PER_SEC, 3),
        "backend": backend,
        "device": device,
        "batch": batch,
        "candidates_per_hr": round(candidates_per_hr, 1),
        "whitening_s": round(whitening_s, 2),
        "compile_first_batch_s": round(compile_s, 2),
        # host-feed vs device-compute split (ISSUE 1 satellite): how much
        # wall each batch pays when the host serializes against the device
        "feed_split": feed_split,
        "cache_warm": cache_warm,
        "mfu": roof.get("mfu"),
        "hbm_utilization": roof.get("hbm_utilization"),
        "bound": roof.get("bound"),
        "attainable_templates_per_sec": roof["attainable_templates_per_sec"],
    }
    if not on_chip:
        # chip peaks say nothing about a CPU run
        for k in ("mfu", "hbm_utilization", "bound"):
            payload.pop(k)
    # close the tracing window first and reduce the trace to its stall
    # breakdown — the payload then shows where the bench wall went
    # (dispatch vs drain vs host feed) next to the throughput number
    trace_summary = tracing.finish(0) if trace_armed else None
    if trace_summary and trace_summary.get("trace_file"):
        payload["trace_file"] = trace_summary["trace_file"]
        try:
            sys.path.insert(
                0,
                os.path.join(
                    os.path.dirname(os.path.abspath(__file__)), "tools"
                ),
            )
            import trace_report

            payload["trace_stalls"] = trace_report.stall_table(
                trace_report.load_trace(trace_summary["trace_file"])
            )
        except Exception as e:  # the bench number outranks its telemetry
            log(f"bench: trace stall table unavailable: {e}")
    # close the metrics window and embed the run report: COMPACT view on
    # stdout (phase walls, counters — recompiles in particular), the full
    # report (histograms, device peaks) only in the artifact
    report = metrics.finish(0, context={"program": "bench", "batch": batch})
    if report is not None:
        payload["run_report"] = metrics.compact_report(report)
    # the FULL payload (nested roofline table + projection) goes to the
    # chain's artifact; the stdout line stays COMPACT — the round
    # driver's capture window truncates ~2 kB lines, which is why
    # BENCH_r04's record shows "parsed": null
    full = dict(payload, roofline=roof)
    if report is not None:
        full["run_report"] = report
    copy = os.environ.get("ERP_BENCH_JSON_COPY")
    if copy and on_chip:
        try:
            with open(copy, "w") as f:
                f.write(json.dumps(full) + "\n")
        except OSError as e:
            log(f"bench: could not write {copy}: {e}")
    print(json.dumps(payload))
    return 0


if __name__ == "__main__":
    sys.exit(run_bench())
