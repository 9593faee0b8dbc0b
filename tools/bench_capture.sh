#!/bin/bash
# One capture of every bench row, in decision-relevance order: train + score
# benches, the op-level step profile, the BN bisect, the remaining bench
# modes, the serving/cold-start/restart/memory tools and chip_smoke.py.
# Meant for a machine with the chip attached (through the chip tool: each
# bench.py row is its own process, and the chip belongs to one process at a
# time, so rows run strictly one after another). bench.py refuses to report
# a number without an accelerator; the script stops up front in that case.
#
# Usage: tools/bench_capture.sh [tag]      (default tag: local)
set -u
cd "$(dirname "$0")/.."
TAG="${1:-local}"

# offline evidence first (CPU, no accelerator needed): HLO-diff + FLOP/byte
# notes for every perf-sensitive segment at this SHA land in
# docs/perf_evidence/
echo "[bench_capture] generating offline perf evidence (CPU)" >&2
JAX_PLATFORMS=cpu timeout 900 python tools/perf_evidence.py >&2 || \
  echo "[bench_capture] perf_evidence FAILED (continuing)" >&2

KIND=$(python -c "
import jax
d = jax.devices()[0]
assert d.platform != 'cpu', 'no accelerator'
print(d.platform, d.device_kind)
") || { echo "[bench_capture] no accelerator; nothing to capture" >&2; exit 3; }
echo "[bench_capture] device: $KIND" >&2

run_one() {  # run_one <suffix> [extra ENV=VAL ...]
  local SUFFIX="$1"; shift
  local OUT="BENCH_${TAG}_${SUFFIX}.json"
  # per-run telemetry (docs/observability.md): each bench row runs with a
  # fresh MXTPU_TELEMETRY_DIR whose JSONL gets archived next to the
  # BENCH artifact — step timings / jit-cache / collective counters at the
  # exact SHA+config of every number we publish
  local TDIR
  TDIR=$(mktemp -d "telemetry_${TAG}_${SUFFIX}.XXXX")
  echo "[bench_capture] running $SUFFIX -> $OUT" >&2
  env "$@" MXTPU_TELEMETRY_DIR="$TDIR" \
    timeout 1800 python bench.py > "$OUT" 2> "BENCH_${TAG}_${SUFFIX}.log"
  local RC=$?
  # archive whatever telemetry the run flushed (concatenated across
  # pids/ranks; empty runs leave no artifact)
  if ls "$TDIR"/*.jsonl >/dev/null 2>&1; then
    cat "$TDIR"/*.jsonl > "BENCH_${TAG}_${SUFFIX}_telemetry.jsonl"
  fi
  rm -rf "$TDIR"
  echo "[bench_capture] $SUFFIX rc=$RC $(cat "$OUT" 2>/dev/null | head -c 300)" >&2
}

# decision-relevant first: the post-BN/maxpool-fix train number
run_one train           MXTPU_BENCH_MODE=train
run_one score           MXTPU_BENCH_MODE=score

# hot-path promotion A/B (docs/sharded_training.md): op-by-op gluon loop
# vs the fused ShardedTrainer whole-step executable on a dispatch-bound
# MLP. The fused row times BOTH impls in-process (speedup, per-step
# dispatch delta, donation aliased_fraction, data-wait/compute split);
# the opbyop row pins the op-by-op number on its own trajectory
run_one train_sharded_opbyop MXTPU_BENCH_MODE=train_sharded \
                             MXTPU_BENCH_SHARDED_IMPL=opbyop \
                             MXTPU_BENCH_BATCH=256
run_one train_sharded_fused  MXTPU_BENCH_MODE=train_sharded \
                             MXTPU_BENCH_SHARDED_IMPL=fused \
                             MXTPU_BENCH_BATCH=256

# input-pipeline A/B (docs/data_pipeline.md): sync next() vs the
# DevicePrefetcher double buffer over a deliberately stalled iterator —
# data_wait_fraction both arms, loss-trajectory equality self-check
run_one input           MXTPU_BENCH_MODE=train_input \
                        MXTPU_BENCH_BATCH=256

echo "[bench_capture] step profile" >&2
rm -rf step_trace
PYTHONPATH=".:${PYTHONPATH:-}" timeout 1200 python tools/step_profile.py 256 \
  > "PROFILE_${TAG}.json" 2> "PROFILE_${TAG}.log"
echo "[bench_capture] profile rc=$?" >&2

echo "[bench_capture] bn bisect" >&2
PYTHONPATH=".:${PYTHONPATH:-}" timeout 1500 python tools/bn_bisect.py \
  > "BISECT_${TAG}.json" 2> "BISECT_${TAG}.log"
echo "[bench_capture] bisect rc=$?" >&2

run_one train_nhwc      MXTPU_BENCH_MODE=train MXTPU_BENCH_LAYOUT=NHWC
run_one score_nhwc      MXTPU_BENCH_MODE=score MXTPU_BENCH_LAYOUT=NHWC

# conv-epilogue + space-to-depth stem A-B (the round-6 fusion work): off /
# fused / stem / combined, all NHWC train — one window answers the whole
# comparison without further code changes
run_one train_nhwc_epioff      MXTPU_BENCH_MODE=train MXTPU_BENCH_LAYOUT=NHWC \
                               MXTPU_PALLAS_CONV_EPILOGUE=0
run_one train_nhwc_epifuse     MXTPU_BENCH_MODE=train MXTPU_BENCH_LAYOUT=NHWC \
                               MXTPU_PALLAS_CONV_EPILOGUE=1
run_one train_nhwc_s2d         MXTPU_BENCH_MODE=train MXTPU_BENCH_LAYOUT=NHWC \
                               MXTPU_PALLAS_CONV_EPILOGUE=0 MXTPU_S2D_STEM=1
run_one train_nhwc_epifuse_s2d MXTPU_BENCH_MODE=train MXTPU_BENCH_LAYOUT=NHWC \
                               MXTPU_PALLAS_CONV_EPILOGUE=1 MXTPU_S2D_STEM=1
run_one score_resnet152 MXTPU_BENCH_MODE=score MXTPU_BENCH_NET=resnet152
run_one score_inception MXTPU_BENCH_MODE=score MXTPU_BENCH_NET=inception_v3
run_one train_inception MXTPU_BENCH_MODE=train MXTPU_BENCH_NET=inception_v3 MXTPU_BENCH_BATCH=128
run_one train_alexnet   MXTPU_BENCH_MODE=train MXTPU_BENCH_NET=alexnet MXTPU_BENCH_BATCH=256
run_one score_int8      MXTPU_BENCH_MODE=score_int8
echo "[bench_capture] int8 probe" >&2
PYTHONPATH=".:${PYTHONPATH:-}" timeout 900 python tools/int8_probe.py \
  > "INT8_PROBE_${TAG}.jsonl" 2> "INT8_PROBE_${TAG}.log"
echo "[bench_capture] int8 probe rc=$?" >&2
run_one bert            MXTPU_BENCH_MODE=bert
run_one lstm            MXTPU_BENCH_MODE=lstm
run_one lstm_scan       MXTPU_BENCH_MODE=lstm MXTPU_PALLAS_LSTM=0

# serving: dynamic-batching inference over resnet18 (docs/serving.md) —
# closed-loop speedup vs sequential, open-loop latency, batch occupancy,
# and the zero-recompile-after-warmup proof, with the full telemetry JSONL
# (queue depth / occupancy / jit events) archived next to the artifact
echo "[bench_capture] serve bench (resnet18)" >&2
SERVE_TDIR=$(mktemp -d "telemetry_${TAG}_serve.XXXX")
env MXTPU_TELEMETRY_DIR="$SERVE_TDIR" PYTHONPATH=".:${PYTHONPATH:-}" \
  timeout 1500 python tools/serve_bench.py --net resnet18 \
  --clients 32 --requests 12 --open-rate 100 \
  > "BENCH_${TAG}_serve_resnet18.json" 2> "BENCH_${TAG}_serve_resnet18.log"
echo "[bench_capture] serve bench rc=$?" >&2
if ls "$SERVE_TDIR"/*.jsonl >/dev/null 2>&1; then
  cat "$SERVE_TDIR"/*.jsonl > "BENCH_${TAG}_serve_resnet18_telemetry.jsonl"
fi
rm -rf "$SERVE_TDIR"

# serving generation: the decode row (docs/serving.md §Generation) —
# continuous batching + paged KV cache over a tiny decoder-only LM:
# tokens/sec, inter-token p99, KV-page peak occupancy, and the
# zero-jit-compile-after-warm proof, with the scheduler's telemetry
# (kv gauges, decode counters, intertoken histogram) archived
echo "[bench_capture] serve bench (decode)" >&2
DEC_TDIR=$(mktemp -d "telemetry_${TAG}_decode.XXXX")
env MXTPU_TELEMETRY_DIR="$DEC_TDIR" PYTHONPATH=".:${PYTHONPATH:-}" \
  timeout 900 python tools/serve_bench.py --generate \
  --clients 16 --requests 8 \
  > "BENCH_${TAG}_decode.json" 2> "BENCH_${TAG}_decode.log"
echo "[bench_capture] serve decode rc=$?" >&2
if ls "$DEC_TDIR"/*.jsonl >/dev/null 2>&1; then
  cat "$DEC_TDIR"/*.jsonl > "BENCH_${TAG}_decode_telemetry.jsonl"
fi
rm -rf "$DEC_TDIR"

# serving resilience: the failover row (docs/serving.md chaos playbook) —
# SIGKILL one replica of a 2-replica pool mid-run; the evidence is
# error-rate 0 with every request resolving 200/429/503/504, loss-window
# throughput > 0, and the recovery-time-to-healthy, with the pool's
# telemetry (healthy gauge, failover/restart counters, eject events)
# archived next to the artifact
echo "[bench_capture] serve bench (failover)" >&2
FAIL_TDIR=$(mktemp -d "telemetry_${TAG}_failover.XXXX")
env MXTPU_TELEMETRY_DIR="$FAIL_TDIR" PYTHONPATH=".:${PYTHONPATH:-}" \
  timeout 900 python tools/serve_bench.py --failover --replicas 2 \
  > "BENCH_${TAG}_failover.json" 2> "BENCH_${TAG}_failover.log"
echo "[bench_capture] serve failover rc=$?" >&2
if ls "$FAIL_TDIR"/*.jsonl >/dev/null 2>&1; then
  cat "$FAIL_TDIR"/*.jsonl > "BENCH_${TAG}_failover_telemetry.jsonl"
fi
rm -rf "$FAIL_TDIR"

# serving elasticity: the autoscale row (docs/serving.md §Autoscaling
# surge playbook) — open-loop surge over a 1-replica pool with the
# autoscaler armed; the evidence is the measured scale-up latency (surge
# start -> grown pool serving), the p99-verdict recovery time, the idle
# scale-down, zero 500s, and the decision counters/events archived in
# the telemetry JSONL next to the artifact
echo "[bench_capture] serve bench (autoscale)" >&2
ASC_TDIR=$(mktemp -d "telemetry_${TAG}_autoscale.XXXX")
env MXTPU_TELEMETRY_DIR="$ASC_TDIR" PYTHONPATH=".:${PYTHONPATH:-}" \
  timeout 900 python tools/serve_bench.py --autoscale \
  > "BENCH_${TAG}_autoscale.json" 2> "BENCH_${TAG}_autoscale.log"
echo "[bench_capture] serve autoscale rc=$?" >&2
if ls "$ASC_TDIR"/*.jsonl >/dev/null 2>&1; then
  cat "$ASC_TDIR"/*.jsonl > "BENCH_${TAG}_autoscale_telemetry.jsonl"
fi
rm -rf "$ASC_TDIR"

# cold start: serving replica time-to-ready, cold vs persistent-warm
# compile cache (docs/compile_cache.md) — run 1 populates an empty
# MXTPU_COMPILE_CACHE dir, run 2's fresh replica must reach ready with
# ZERO jit_compile events (rc=4 if it compiled anything) and measurably
# lower time-to-ready; the workers' telemetry JSONL is archived beside
# the row
echo "[bench_capture] cold start (resnet18, compile cache)" >&2
COLD_TDIR=$(mktemp -d "telemetry_${TAG}_coldstart.XXXX")
env PYTHONPATH=".:${PYTHONPATH:-}" TMPDIR="$COLD_TDIR" \
  timeout 1500 python tools/coldstart_bench.py --net resnet18 \
  > "BENCH_${TAG}_coldstart.json" 2> "BENCH_${TAG}_coldstart.log"
echo "[bench_capture] cold start rc=$?" >&2
if ls "$COLD_TDIR"/coldstart_bench_*/telemetry_*/*.jsonl >/dev/null 2>&1; then
  cat "$COLD_TDIR"/coldstart_bench_*/telemetry_*/*.jsonl \
    > "BENCH_${TAG}_coldstart_telemetry.jsonl"
fi
rm -rf "$COLD_TDIR"

# fused-restart cold start: TRAINING time-to-step-1, cold vs warm
# persistent cache (docs/sharded_training.md) — the quarantine-lift
# proof: a restarted promoted-trainer life must reach step 1 with ZERO
# jit_compile events (rc=4 otherwise), riding the warmup manifest its
# cold life wrote
echo "[bench_capture] train restart (fused sharded step, compile cache)" >&2
TRB_TDIR=$(mktemp -d "telemetry_${TAG}_train_restart.XXXX")
env PYTHONPATH=".:${PYTHONPATH:-}" TMPDIR="$TRB_TDIR" \
  timeout 900 python tools/train_restart_bench.py \
  > "BENCH_${TAG}_train_restart.json" 2> "BENCH_${TAG}_train_restart.log"
echo "[bench_capture] train restart rc=$?" >&2
if ls "$TRB_TDIR"/train_restart_bench_*/telemetry_*/*.jsonl >/dev/null 2>&1; then
  cat "$TRB_TDIR"/train_restart_bench_*/telemetry_*/*.jsonl \
    > "BENCH_${TAG}_train_restart_telemetry.jsonl"
fi
rm -rf "$TRB_TDIR"

# preemption row: sync-vs-async checkpoint stall A/B + measured
# steps-lost contrast (docs/fault_tolerance.md §Preemption) — the async
# writer's per-save trainer stall must stay an order of magnitude under
# the synchronous serialize+fsync, and a graceful preemption must lose
# zero steps where a hard kill loses up to a save period
echo "[bench_capture] train preempt (checkpoint stall A/B)" >&2
env PYTHONPATH=".:${PYTHONPATH:-}" \
  timeout 900 python tools/train_restart_bench.py --mode preempt \
  > "BENCH_${TAG}_preempt.json" 2> "BENCH_${TAG}_preempt.log"
echo "[bench_capture] train preempt rc=$?" >&2

# memory row: the serving memory budget's evidence (docs/observability.md
# §Memory) — per-bucket memory_analysis footprint, over-budget load
# rejected / within-budget accepted / warn-mode canary, and the donation
# verifier confirming the fused trainer step aliases its donated buffers
echo "[bench_capture] serve memory budget" >&2
env PYTHONPATH=".:${PYTHONPATH:-}" \
  timeout 900 python tools/memory_bench.py \
  > "BENCH_${TAG}_memory.json" 2> "BENCH_${TAG}_memory.log"
echo "[bench_capture] serve memory rc=$?" >&2

# trace row: render the archived telemetry JSONL (serve_bench samples
# every request at --trace-sample 1.0, so the serve rows' JSONL carries
# the full span stream) into perfetto-loadable merged traces next to the
# raw JSONL — `--trace <id>` on the slowest_request id from the serve
# JSON zooms to the worst request (docs/observability.md §Tracing)
echo "[bench_capture] trace merge" >&2
for ROW in serve_resnet18 failover; do
  JSONL="BENCH_${TAG}_${ROW}_telemetry.jsonl"
  if [ -s "$JSONL" ]; then
    PYTHONPATH=".:${PYTHONPATH:-}" timeout 300 python tools/trace_merge.py \
      "$JSONL" -o "BENCH_${TAG}_${ROW}_trace.json" \
      2>> "BENCH_${TAG}_${ROW}.log" \
      && echo "[bench_capture] trace row: BENCH_${TAG}_${ROW}_trace.json" >&2
  fi
done

echo "[bench_capture] running chip_smoke.py" >&2
timeout 1800 python chip_smoke.py > "CHIP_SMOKE_${TAG}.jsonl" \
  2> "CHIP_SMOKE_${TAG}.log"
echo "[bench_capture] smoke rc=$?" >&2

# refresh the committed bench trajectory (docs/bench_trajectory.md +
# BENCH_TRAJECTORY.json) so this capture's rows land in the reviewer table
echo "[bench_capture] bench history" >&2
PYTHONPATH=".:${PYTHONPATH:-}" timeout 120 python tools/bench_history.py \
  2>> /dev/stderr || echo "[bench_capture] bench history failed" >&2

# regression gate over the refreshed trajectory, WARN-ONLY here (a capture
# must land even when it regressed — the table in the log is the signal;
# CI/reviewers run `python -m tools.bench_history --check` blocking)
PYTHONPATH=".:${PYTHONPATH:-}" timeout 120 python tools/bench_history.py \
  --check 2>> /dev/stderr \
  || echo "[bench_capture] WARNING: bench_history --check flagged a >15% headline regression (see table above)" >&2
echo "[bench_capture] done" >&2
