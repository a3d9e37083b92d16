# Developer/CI entry points.  Everything runs on the CPU backend; no
# accelerator required.

PYTHON ?= python

.PHONY: test smoke bench-history chaos chaos-hosts chaos-hang serving-chaos fabric-soak fabric-soak-server fleet-bench fleet-report fleet-timeline step-report precision-audit trace-report

# tier-1 suite (the gate every PR must keep green) + the benchmark-artifact
# schema gate (--strict fails on malformed round artifacts) + the clean multi-host
# elastic gate (2 forced-4-device CPU driver processes over one shard
# board; the host-KILL half lives in `make chaos-hosts`) + the hang-soak
# gate (chaos-hang below: wedges must become supervised restarts) + the
# adversarial volunteer-fabric gate (fabric-soak-server below: zero
# false grants under every adversary model, references computed by the
# resident serving tier) + the serving-tier gate (fleet-bench below:
# WUs/hour/chip floor, ZERO recompiles after warmup, server results
# byte-identical to the per-WU driver path) + the fleet-rollup SLO gate
# (fleet-report below: re-checks the soak's cached erp-fleet-report/1
# against the committed FLEET_BASELINE.json bounds) + the measured-time
# gate (step-report below: fresh measured step latencies reconciled
# against the cost model and held under the committed
# STEPTIME_BASELINE.json ceilings) + the serving-durability gate
# (serving-chaos below: SIGKILL the server mid-queue with journal-write
# EIO and a wedged dispatch thread; every accepted WU must still be
# granted byte-identical with zero recompiles after the warm resume) +
# the precision gate (precision-audit below: stage-wise f32-vs-f64 error
# attribution + candidate recall held under the committed
# PRECISION_BASELINE.json floors/ceilings, tap proved observation-only).
# fleet-bench runs before bench_history so the strict gate sees a fresh
# scoreboard (including the measured step-latency row step-report and
# fleet-bench both feed, and the precision row precision-audit feeds).
test:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider
	$(MAKE) fleet-bench
	$(MAKE) step-report
	$(MAKE) precision-audit
	$(PYTHON) tools/bench_history.py --strict
	env JAX_PLATFORMS=cpu $(PYTHON) tools/smoke.py --hosts 2
	$(MAKE) chaos-hang
	$(MAKE) serving-chaos
	$(MAKE) fleet-timeline
	$(MAKE) fabric-soak-server
	$(MAKE) fleet-report

# fast observability smoke: tiny end-to-end run with the health watchdog
# at max cadence + metrics + flight recorder, then schema-check every
# artifact it leaves (tools/smoke.py)
smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) tools/smoke.py

# kill/resume chaos soak: SIGKILL/SIGTERM schedules + injected
# checkpoint-write EIO faults + a corrupted-generation fallback, final
# result byte-compared against an uninterrupted reference run
# (tools/chaos_soak.py; the pytest `chaos` marker wraps the same thing)
chaos:
	env JAX_PLATFORMS=cpu $(PYTHON) tools/chaos_soak.py --quick

# host-loss chaos soak: 4 emulated hosts (forced 2-device CPU platform
# per process, shard leases on a shared board dir), one SIGKILLed right
# after a mid-shard commit; survivors must adopt its template range
# (>= 1 resilience.rebalance in a run report) and the merge winner's
# result must be byte-identical to a single-process reference
# (tools/chaos_soak.py --hosts; the pytest `chaos` marker wraps it too)
chaos-hosts:
	env JAX_PLATFORMS=cpu $(PYTHON) tools/chaos_soak.py --hosts 4 --kill-host 1

# hang chaos soak: planted wedges (dispatch stall, lease-heartbeat IO,
# elastic merge) must become bounded-time supervised restarts — watchdog
# rc 99, resume from the last committed checkpoint, final toplist
# byte-identical — and a template that wedges on every visit must be
# quarantined after K incidents instead of crash-looping
# (tools/chaos_soak.py --hang; the pytest `chaos` marker wraps it too)
chaos-hang:
	env JAX_PLATFORMS=cpu $(PYTHON) tools/chaos_soak.py --hang --templates 24 --timeout 150

# serving durability chaos soak (tools/serving_chaos.py): SIGKILL a
# durable FleetServer subprocess mid-queue while journal_write EIO
# faults hit the WU journal's WAL, restart it under the rc-99
# supervision loop with a planted serving_dispatch wedge (watchdog
# deadline -> supervised restart -> journal replay), and require every
# submitted WU granted byte-identical to per-WU driver references with
# ZERO recompiles after the warm resume; the bounded-queue shed check
# (explicit retry-after, /healthz 503 while shedding) rides along
serving-chaos:
	env JAX_PLATFORMS=cpu $(PYTHON) tools/serving_chaos.py --quick

# adversarial volunteer-fabric soak: 64 concurrent volunteer streams
# (honest majority + every adversary model in fabric/hosts.py — bitflip,
# reorder, stale-epoch, echo, stall, forged quarantine gaps — plus
# injected result_report corruption and transient validator crashes)
# against the quorum scheduler; ZERO false grants, zero starvation,
# granted toplists byte-identical to single-process driver references,
# bounded re-issue overhead, every signed erp-quorum/1 verdict passes
# --check (tools/fabric_soak.py; --streams 256 for the acceptance soak)
fabric-soak:
	env JAX_PLATFORMS=cpu $(PYTHON) tools/fabric_soak.py

# the same soak with ERP_FABRIC_BACKEND=server: the honest references
# are computed by the IN-PROCESS fleet serving tier (serving/server.py,
# one resident Scheduler, correlation ids through each Session's scoped
# ObsContext) instead of per-payload driver subprocesses — the fabric
# and the serving tier gate each other in one run
fabric-soak-server:
	env JAX_PLATFORMS=cpu ERP_FABRIC_BACKEND=server $(PYTHON) tools/fabric_soak.py

# serving-tier bench/gate (tools/fleet_bench.py): stream same-geometry
# WUs through one resident FleetServer (warmed via the Scheduler.warm
# path aot_prewarm --warm exposes), require every result byte-identical
# to the one-process-per-WU driver and ZERO recompiles after warmup,
# then enforce the committed FLEET_SERVING_BASELINE.json floors; the
# scoreboard is cached for bench_history --strict
fleet-bench:
	env JAX_PLATFORMS=cpu $(PYTHON) tools/fleet_bench.py --verify --check

# fleet-observatory gate (tools/fleet_timeline.py): run a 2-host
# host-loss chaos soak with per-host erp-trace/1 streams kept, then
# assemble every stream + the lease board + SLO heartbeats into ONE
# merged Chrome trace and the erp-fleet-timeline/1 sidecar; --check
# validates both, requires >= 95% trace coverage on every surviving
# host, and requires the host-lost -> takeover -> adoption flow chain
# with a measured adoption latency (docs/observability.md layer 11)
fleet-timeline:
	mkdir -p .erp_cache/fleet_timeline_ci
	find .erp_cache/fleet_timeline_ci -mindepth 1 -maxdepth 1 \
		! -name xla-cache -exec rm -rf {} +
	env JAX_PLATFORMS=cpu $(PYTHON) tools/chaos_soak.py --hosts 2 --kill-host 1 \
		--workdir $(CURDIR)/.erp_cache/fleet_timeline_ci --keep
	$(PYTHON) tools/fleet_timeline.py .erp_cache/fleet_timeline_ci \
		--check --min-coverage 0.95 --require-adoption
	$(PYTHON) tools/metrics_report.py --check \
		.erp_cache/fleet_timeline_ci/fleet-timeline.json

# fleet-rollup SLO gate: validates the erp-fleet-report/1 the fabric
# soak cached (grant/validation-latency percentiles, re-issue overhead,
# per-adversary detections, signed-verdict provenance) and enforces the
# committed FLEET_BASELINE.json bounds (tools/fleet_report.py --check;
# see docs/observability.md layer 9)
fleet-report:
	$(PYTHON) tools/fleet_report.py --check .erp_cache/fleet_report_ci.json \
		--baseline FLEET_BASELINE.json

# measured-time reconciliation gate (tools/step_report.py, chip-free):
# run the CI fixture with the runtime/steptime.py bracket armed, join
# the measured per-window step times against the roofline stage model
# into erp-step-report/1, hold the run
# under the STEPTIME_BASELINE.json ceilings (same-backend only), then
# schema-check the cached artifact with the common validator
step-report:
	env JAX_PLATFORMS=cpu $(PYTHON) tools/step_report.py \
		--baseline STEPTIME_BASELINE.json \
		--json .erp_cache/step_report_ci.json
	$(PYTHON) tools/metrics_report.py --check .erp_cache/step_report_ci.json

# precision observatory gate (tools/precision_audit.py, chip-free): run
# the production jitted pipeline and the f64 oracle on one workunit
# slice, attribute cumulative vs introduced relative error to each
# registered stage boundary (runtime/precision.py), score candidate
# recall/rank-stability/Jaccard against the oracle toplist, shadow-audit
# the bf16 lane, prove the tap observation-only (byte-identical merge
# state, zero recompiles), hold the f32 lane under the committed
# PRECISION_BASELINE.json floors/ceilings, then schema-check the cached
# artifact with the common validator
precision-audit:
	env JAX_PLATFORMS=cpu $(PYTHON) tools/precision_audit.py \
		--baseline PRECISION_BASELINE.json \
		--json .erp_cache/precision_audit_ci.json
	$(PYTHON) tools/metrics_report.py --check .erp_cache/precision_audit_ci.json
	$(PYTHON) tools/metrics_report.py --check PRECISION_BASELINE.json

# performance trajectory across the round artifacts (tools/bench_history.py)
bench-history:
	$(PYTHON) tools/bench_history.py

# stall attribution from a host span trace: TRACE=path/to/run.trace.jsonl
# (or its .chrome.json export); see docs/observability.md layer 7
trace-report:
	$(PYTHON) tools/trace_report.py $(TRACE)
