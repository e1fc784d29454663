#!/usr/bin/env bash
# One-shot on-chip capture orchestrator.
#
# Runs every capture stage in priority order, each in its own process
# (a chip belongs to one process at a time) under its own hard timeout,
# appending raw results to benchmarks/results/capture_<date>.jsonl so a
# failed stage still leaves the earlier stages' artifacts. Run it on a
# machine that holds the chip (through the chip tool; README "Tests and
# benchmarks"): a stage that finds no TPU fails with its own exit code.
#
# Every stage's JSON records now carry per-stage peak HBM
# (peak_hbm_bytes / hbm_bytes_in_use from the runtime's memory_stats —
# benchlib.device_memory_record, ISSUE 9), so the bench trajectory
# tracks footprint alongside throughput; summarize_captures.py surfaces
# both, and a stats-less backend reports an explicit null, not a
# missing column.
#
#   bash benchmarks/capture_all.sh
set -u
cd "$(dirname "$0")/.."

STAMP=$(date -u +%Y-%m-%dT%H%MZ)
OUT=benchmarks/results/capture_${STAMP}.jsonl
mkdir -p benchmarks/results

run_stage() {  # run_stage <name> <timeout> <cmd...>
  local name=$1 tmo=$2; shift 2
  echo "--- stage: ${name}" >&2
  local start=$(date +%s)
  local out
  out=$(timeout "${tmo}" "$@" 2>/dev/null)
  local rc=$?
  local secs=$(( $(date +%s) - start ))
  # keep only JSON lines; tag each with the stage
  while IFS= read -r line; do
    case "${line}" in
      '{'*) printf '{"stage": "%s", "rc": %d, "secs": %d, "data": %s}\n' \
                   "${name}" "${rc}" "${secs}" "${line}" >> "${OUT}" ;;
    esac
  done <<< "${out}"
  if [ ${rc} -ne 0 ] && [ -z "${out}" ]; then
    printf '{"stage": "%s", "rc": %d, "secs": %d, "data": null}\n' \
           "${name}" "${rc}" "${secs}" >> "${OUT}"
  fi
  return ${rc}
}

echo "capturing to ${OUT}" >&2

# Priority order: the decisions blocked on each artifact, most important
# first.
run_stage bench 900 python bench.py
run_stage profile 600 python benchmarks/capture_profile.py
run_stage pallas_ab 900 python benchmarks/bench_pallas_encode.py
BENCH_CONTEXTS=1024 run_stage pallas_ab_c1024 900 \
  python benchmarks/bench_pallas_encode.py
# ragged packed-wire fusion A/B (ISSUEs 10 + 12): packed train, train-
# BACKWARD (value_and_grad step time + grad-program AOT temp bytes, the
# custom-VJP recompute's residual axis) and predict step time AND
# per-arm peak HBM, across THREE arms: unfused (unpack-then-dense),
# fused (the SHIPPED default: fusion + custom-VJP twin train), and
# fused_kernel (+ RAGGED_TRAIN_KERNEL, the Pallas train pair). The
# fusion speedups confirm the default flip vs unpack; the kernel
# verdict (ragged_train_kernel_speedup) compares the pair against the
# fused twin it would replace — first at the java14m headline fill,
# then the fused path's best case (high max_contexts, low fill, where
# the dense planes are mostly padding). scripts/flip_verdict.py
# settles the >=2% flips from these records after the round.
# Per-arm timeout pinned so all THREE arms fit inside the 1300 s stage
# budget (the default 780 s/arm would let one stalled arm eat the
# stage).
BENCH_PALLAS_ARM_TIMEOUT=390 run_stage pallas_ragged 1300 \
  python benchmarks/bench_pallas_ragged.py
BENCH_CONTEXTS=1024 BENCH_FILL=0.1 BENCH_PALLAS_ARM_TIMEOUT=390 \
  run_stage pallas_ragged_c1024 1300 \
  python benchmarks/bench_pallas_ragged.py
# serving engine A/B (ISSUE 4): naive per-request predict vs the
# micro-batching engine — on-chip latency p50/p99 + throughput; the
# traced arm (ISSUE 8) keeps its span log durable so the per-phase
# attribution survives the round
TRACE_DIR=benchmarks/results/serving_trace_${STAMP}
run_stage serving 900 python benchmarks/bench_serving.py \
  --trace-dir "${TRACE_DIR}"
# phase x bucket x tier p50/p95/p99 off the span log (jax-free, cheap)
if [ -f "${TRACE_DIR}/spans.jsonl" ]; then
  run_stage serving_latency 120 python scripts/latency_report.py \
    --spans "${TRACE_DIR}/spans.jsonl" --json
fi
# serving mesh (ISSUE 13): fixed offered load against 1/2/4 replicas —
# sustained admitted throughput, p99-under-load, shed rate, per-replica
# device fill, dispatch share, and the zero-postwarm-compile check over
# the mixed predict + submit_neighbors stream
run_stage mesh 900 python benchmarks/bench_mesh.py
# memoization tier (ISSUE 16): Zipf-replayed duplicate-heavy traffic
# through memo off / exact / exact+semantic — hit rate, cache-served
# vs live p99, shed rate, device-seconds-per-1k-requests, and the
# zero-postwarm-compile check with the cache in front of the fleet
run_stage mesh_memo 900 python benchmarks/bench_mesh.py --zipf-alpha 1.1
# elastic fleet (ISSUE 18): stepped offered load (low -> high -> low)
# against one process replica with the SLO/queue-driven autoscaler
# live — scale-up latency (decision + worker cold start), scale-down
# drain latency, and transition-vs-steady p99
run_stage mesh_stepped 900 python benchmarks/bench_mesh.py --stepped-load
# mesh chaos soak (ISSUE 14): paced load + periodic kill_worker/
# drop_heartbeat faults against socket-mode workers — zero lost
# admitted requests, zero post-warmup parent compiles, bounded p99
# while the supervisor keeps restoring capacity
run_stage mesh_soak 600 python scripts/mesh_soak.py --mode socket
# embedding index (ISSUE 5): exact vs IVF throughput/recall curves +
# the naive numpy host-loop baseline
run_stage index 900 python benchmarks/bench_index.py --arms base
# quantized tier (ISSUE 19): f16 vs int8 vs PQ — QPS, recall@10,
# device bytes/vector, zero post-warmup compiles — plus the
# live-insert throughput arm
run_stage index_quant 900 python benchmarks/bench_index.py --arms quant
# training goodput plane (ISSUE 17): steady-state MFU, goodput
# fraction, and badput shares of the real hot loop — the healthy
# baseline a later goodput regression flips against
run_stage goodput 900 python benchmarks/bench_goodput.py
# scenario traffic plane (ISSUE 20): mixed Java+C# recorded profile
# replayed against a live mesh — per-scenario x per-language
# exact-match/F1, memo hit-rate, shed, p99, per-scenario SLO budget
# burn, the retrieval-vs-softmax A/B verdict, and the zero-postwarm-
# compile check across the mixed-scenario steady state
run_stage scenarios 900 python benchmarks/accuracy_at_scale.py \
  --scenarios --workdir /tmp/acc_scenarios

# settle the queued >=2% flip verdicts from everything this round (and
# prior rounds) captured — durable rows in results/flip_verdicts.json.
# Non-fatal: a partial round still records PENDING with provenance.
python scripts/flip_verdict.py --write || true

echo "capture complete: ${OUT}" >&2
