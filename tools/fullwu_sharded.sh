#!/bin/bash
# Full-bank SHARDED golden run (VERDICT r04 item 5): the complete
# 6,662-template WU through parallel/run_bank_sharded on the 8-device
# virtual CPU mesh, end to end through the driver (whiten + search +
# rescore + result write), then diff the candidate payload byte-for-byte
# against the single-device golden payload
# (8d3eb761..., FULLWU_r04_cpu.json).  Multi-chip correctness as an
# end-to-end artifact instead of a tiny-shape dryrun — the reference
# analogue is BOINC cross-host validation (SURVEY #4.4).
#
# Usage: tools/fullwu_sharded.sh <outdir> [n_devices]
#
# Single-core hosts: the in-process CPU communicator aborts a collective
# when rendezvous arrival skew exceeds 40 s, and the 8 virtual devices'
# local steps SERIALIZE through the shared intra-op pool — arrival skew
# is ~(n_dev-1) x per-device step time.  Keep per-device batches small
# (ERP_BATCH=4 worked; 16 aborted reproducibly) and do not run anything
# else on the box.  Real multi-chip meshes route collectives in hardware
# and have no such constraint.
set -u
OUT=${1:?usage: fullwu_sharded.sh <outdir> [n_devices]}
NDEV=${2:-8}
REPO=$(cd "$(dirname "$0")/.." && pwd)
TESTWU=/root/reference/debian/extra/einstein_bench/testwu
WU=$TESTWU/p2030.20151015.G187.41-00.88.N.b2s0g0.00000_1099.bin4
BANK=$TESTWU/stochastic_full.bank
ZAP=$TESTWU/p2030.20151015.G187.41-00.88.N.b2s0g0.00000.zap
GOLDEN_SHA=8d3eb761450ce908c3084f6a9f53078451fad227fd648b6f60a296727d20b5e5

mkdir -p "$OUT"
cd "$OUT"
export PYTHONPATH="${PYTHONPATH:-}:$REPO"
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=$NDEV ${XLA_FLAGS:-}"

S0=$(date +%s)
python -m boinc_app_eah_brp_tpu \
  -i "$WU" -o shard.cand -c shard.cpt \
  -t "$BANK" -l "$ZAP" -A 0.08 -P 3.0 -f 400.0 -W -z \
  --mesh "$NDEV" > run.log 2>&1
RC=$?
WALL=$(( $(date +%s) - S0 ))
echo "sharded run rc=$RC wall=${WALL}s" | tee timing.log

grep -v '^%' shard.cand > shard.payload 2>/dev/null
JSON_OUT=${ERP_MULTIFULLWU_JSON:-$OUT/multichip_fullwu.json}
python3 - <<EOF
import hashlib, json

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

payload_sha = sha("shard.payload")
payload = {
  "what": ("full 6662-template WU sharded over a ${NDEV}-device virtual CPU "
           "mesh (parallel/run_bank_sharded via the driver --mesh path), "
           "payload diffed against the single-device golden run"),
  "n_devices": ${NDEV},
  "rc": ${RC},
  "wall_s": ${WALL},
  "emitted_candidates": emitted("shard.cand"),
  "payload_sha256": payload_sha,
  "golden_payload_sha256": "${GOLDEN_SHA}",
  "payload_identical_to_single_device": payload_sha == "${GOLDEN_SHA}",
}
text = json.dumps(payload, indent=1)
print(text)
with open("${JSON_OUT}", "w") as f:
    f.write(text + "\n")
EOF
echo "artifact: ${JSON_OUT}" | tee -a timing.log
