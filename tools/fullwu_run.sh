#!/bin/bash
# Full-workunit run artifact: the complete 6,662-template search on the
# shipped Arecibo WU through the native wrapper (bench_single.sh protocol),
# with a mid-run SIGTERM + checkpoint resume, and a fresh uninterrupted run
# to prove the resumed result file is identical.
#
# Usage: tools/fullwu_run.sh <outdir> [interrupt_after_seconds]
# Env: ERP_FULLWU_PLATFORM (cpu|default; default inherits, i.e. TPU when up)
set -u
OUT=${1:?usage: fullwu_run.sh <outdir> [interrupt_s]}
INT_S=${2:-600}
REPO=$(cd "$(dirname "$0")/.." && pwd)
TESTWU=/root/reference/debian/extra/einstein_bench/testwu
WU=$TESTWU/p2030.20151015.G187.41-00.88.N.b2s0g0.00000_1099.bin4
BANK=$TESTWU/stochastic_full.bank
ZAP=$TESTWU/p2030.20151015.G187.41-00.88.N.b2s0g0.00000.zap
WRAPPER=$REPO/native/build/erp_wrapper

mkdir -p "$OUT"
cd "$OUT"
export PYTHONPATH="${PYTHONPATH:-}:$REPO"
if [ "${ERP_FULLWU_PLATFORM:-}" = "cpu" ]; then export JAX_PLATFORMS=cpu; fi

run_wrapper() { # $1=out $2=cp $3=log   (call in a subshell: it execs)
  # exec: the calling (sub)shell BECOMES the wrapper, so a backgrounded
  # `run_wrapper ... &` yields the WRAPPER's pid in $! and `kill -TERM`
  # reaches erp_wrapper's graceful 3-signal handler.  (The original
  # formulation signalled only the bash subshell: the wrapper and its
  # worker survived as orphans racing the resume run — an invalid gate.)
  exec "$WRAPPER" -i "$WU" -o "$1" -c "$2" \
    -t "$BANK" -l "$ZAP" -A 0.08 -P 3.0 -f 400.0 -W -z \
    >> "$3" 2>&1
}

echo "=== interrupted run: SIGTERM after ${INT_S}s ===" | tee -a timing.log
S0=$(date +%s)
run_wrapper run1.cand cp1.cpt run1.log &
WPID=$!
sleep "$INT_S"
if kill -0 "$WPID" 2>/dev/null; then
  echo "sending SIGTERM to wrapper $WPID at $(( $(date +%s) - S0 ))s" \
    | tee -a timing.log
  kill -TERM "$WPID"
fi
wait "$WPID"; RC1=$?
echo "interrupted run rc=$RC1 after $(( $(date +%s) - S0 ))s" | tee -a timing.log
ls -la cp1.cpt >> timing.log 2>&1
# the gate is void if anything from the interrupted run is still alive
if kill -0 "$WPID" 2>/dev/null; then
  echo "ERROR: wrapper survived SIGTERM+wait" | tee -a timing.log
fi

echo "=== resume to completion ===" | tee -a timing.log
S1=$(date +%s)
( run_wrapper run1.cand cp1.cpt run1.log )
RC2=$?
echo "resume rc=$RC2 after $(( $(date +%s) - S1 ))s" | tee -a timing.log

echo "=== fresh uninterrupted run ===" | tee -a timing.log
S2=$(date +%s)
( run_wrapper run2.cand cp2.cpt run2.log )
RC3=$?
echo "fresh rc=$RC3 after $(( $(date +%s) - S2 ))s" | tee -a timing.log

grep -v '^%' run1.cand > run1.payload
grep -v '^%' run2.cand > run2.payload
if cmp -s run1.payload run2.payload; then
  echo "RESULT: resumed candidate payload IDENTICAL to uninterrupted run" \
    | tee -a timing.log
  DIFF_OK=True  # interpolated into the Python literal below
else
  echo "RESULT: payload DIFFERS" | tee -a timing.log
  DIFF_OK=False
fi
TOTAL1=$(( S2 - S0 ))
JSON_OUT=${ERP_FULLWU_JSON:-$OUT/fullwu.json}
python3 - <<EOF
import hashlib, json, subprocess, sys

def sha(p):
    try:
        return hashlib.sha256(open(p, "rb").read()).hexdigest()
    except OSError:
        return None

def emitted(p):
    try:
        return sum(1 for l in open(p) if l.strip() and not l.startswith("%"))
    except OSError:
        return None

backend = "unknown"
try:
    # the driver logs "Using N <backend> device(s)." at startup
    probe = subprocess.run(
        ["grep", "-aoE", "Using [0-9]+ [a-z]+ device", "run1.log"],
        capture_output=True, text=True)
    if probe.stdout:
        backend = probe.stdout.splitlines()[-1].split()[2]
except Exception:
    pass
def sigterm_handled():
    # the worker logs "Caught signal N" when the wrapper forwards the
    # graceful quit (runtime/boinc.py install_signal_handlers) — evidence
    # the signal actually traversed wrapper -> worker, not just the shell
    try:
        return any("Caught signal" in l for l in open("run1.log", errors="replace"))
    except OSError:
        return False

payload = {
  "what": "full 6662-template WU via native wrapper, SIGTERM at ${INT_S}s + resume, vs fresh run",
  "interrupted_rc": $RC1, "resume_rc": $RC2, "fresh_rc": $RC3,
  "sigterm_reached_worker": sigterm_handled(),
  "resume_payload_identical": $DIFF_OK,
  "interrupted_plus_resume_wall_s": $TOTAL1,
  "fresh_wall_s": $(( $(date +%s) - S2 )),
  "platform": "${JAX_PLATFORMS:-default}",
  "jax_backend_logged": backend,
  "resumed_cand_sha256": sha("run1.cand"),
  "fresh_cand_sha256": sha("run2.cand"),
  "resumed_payload_sha256": sha("run1.payload"),
  "fresh_payload_sha256": sha("run2.payload"),
  "emitted_candidates": emitted("run2.cand"),
}
text = json.dumps(payload, indent=1)
print(text)
with open("${JSON_OUT}", "w") as f:
    f.write(text + "\n")
EOF
echo "artifact: ${JSON_OUT}" | tee -a timing.log
