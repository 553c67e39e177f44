#!/bin/bash
# One-shot on-chip perf sweep. Every command below is its own process,
# run one after another, so the chip is never held by two at once; bench.py
# and chip_smoke.py refuse to report a device rate when jax finds no TPU.
# Logs everything to tools/perf_sweep.log for later tuning decisions.
#   bash tools/perf_sweep.sh [quick]
set -u
cd "$(dirname "$0")/.."
LOG=tools/perf_sweep.log
: > "$LOG"

# One run log for the WHOLE sweep: pin the run-file path so every
# obs_event below AND every child bench.py lands in the same JSONL file
# (each python startup would otherwise open its own run-<pid> file and
# `obs_report` with no args would summarize only the last fragment).
# See docs/observability.md.
if [ -n "${PADDLE_TPU_OBS_DIR:-}" ]; then
  export PADDLE_TPU_OBS_RUN_FILE="${PADDLE_TPU_OBS_DIR}/run-sweep-$(date -u +%Y%m%dT%H%M%S)-p$$.jsonl"
fi

obs_event() {
  # mirror one sweep timing into the structured run log (same JSONL
  # schema as Executor/bench events; see docs/observability.md) — only
  # when the operator exported PADDLE_TPU_OBS_DIR. obs_report --emit
  # loads the obs package standalone, so this costs a stdlib-only
  # python startup, not a jax import.
  [ -n "${PADDLE_TPU_OBS_DIR:-}" ] || return 0
  python tools/obs_report.py --emit bench.sweep.cmd "$@" >/dev/null 2>&1 \
    || true
}

run() {
  echo "=== $* ===" | tee -a "$LOG"
  local t0 t1 rc
  t0=$(date +%s.%N)
  timeout "${T:-600}" "$@" >> "$LOG" 2>&1
  rc=$?
  t1=$(date +%s.%N)
  echo "rc=$rc" | tee -a "$LOG"
  obs_event "cmd=$*" "rc=$rc" "dur_s=$(awk "BEGIN{printf \"%.3f\", $t1-$t0}")"
}

# 0. lint gate (opt-in: LINT=1): the static-check step (compileall +
# pyflakes when installed + program_lint over a fresh mnist export,
# docs/analysis.md) before burning chip time on a broken tree.
if [ "${LINT:-0}" = 1 ]; then
  echo "== lint ==" | tee -a "$LOG"
  # direct invocation, not run(): run()'s rc is function-local and it
  # never aborts (benches may fail individually) — the lint GATE must
  # actually gate, so a broken tree doesn't get chip time
  if bash tools/lint.sh >> "$LOG" 2>&1; then
    echo "lint OK" | tee -a "$LOG"
    obs_event "cmd=lint" "rc=0"
  else
    echo "LINT FAILED — aborting sweep" | tee -a "$LOG"
    obs_event "cmd=lint" "rc=1"
    exit 1
  fi
fi

# 0b. the chip answers and the main path runs on it, or nothing below
# is worth the time
echo "== chip_smoke ==" | tee -a "$LOG"
if ! python chip_smoke.py >> "$LOG" 2>&1; then
  echo "chip_smoke.py failed — aborting sweep" | tee -a "$LOG"
  exit 1
fi

# 1. headline bench
run python bench.py

if [ "${1:-}" = quick ]; then exit 0; fi

# 2. layout / batch sensitivity for ResNet
run env BENCH_LAYOUT=NCHW python bench.py
run env BENCH_BATCH=512 python bench.py
run env BENCH_BATCH=2048 python bench.py

# 3. flash-attention block sweep at bench shapes (fwd+bwd)
run python tools/tune_flash.py --seq 256 --batch 64 --heads 8 --dim 64
run python tools/tune_flash.py --seq 1024 --batch 16 --heads 8 --dim 64 \
    --causal

# 4. transformer seq-length scaling
run env BENCH_SEQ=512 BENCH_TBATCH=32 python bench.py

# 5. GPipe bubble curve (needs >= 2 chips: pp shards the decoder stack).
#    Bubble fraction = (S-1)/(M+S-1); this measures where real overlap
#    diverges from the formula. Skipped on a one-chip machine.
# count REAL accelerator devices only, never virtual CPU ones
NDEV=$(timeout 60 python -c "
import jax
d = jax.devices()
print(len(d) if d and d[0].platform != 'cpu' else 0)" 2>/dev/null || echo 1)
if [ "${NDEV:-1}" -ge 2 ]; then
  for M in 2 4 8 16; do
    run python benchmark/fluid_benchmark.py --model transformer \
        --device TPU --use_fake_data --iterations 20 --pp 2 --n_micro "$M"
  done
fi

# 6. K-step bundling sweep (opt-in: BUNDLE=1, or BUNDLE=K for one K):
#    pipelined hot-loop steps/sec at several scan lengths via the bundle
#    bench phase — the small-model host-bound case where dispatch
#    amortization shows (docs/perf.md). Runs regardless of platform:
#    the bundling win is host-side.
if [ "${BUNDLE:-0}" != 0 ]; then
  if [ "${BUNDLE}" = 1 ]; then KS="1 4 8 16"; else KS="$BUNDLE"; fi
  for K in $KS; do
    run env BENCH_BUNDLE_STEPS="$K" python bench.py --phase bundle
  done
fi

# 6b. pipeline-overlap A/B (opt-in: OVERLAP=1): double-buffered feeds
#     on/off (steps/sec + per-step input wait + host-stall totals) and
#     checkpoint-cadence off/sync/async (per-interval step-boundary
#     stall: sync pays file IO + commit inline, async only the buffer
#     snapshot) through the overlap bench phase. Host-side wins, so it
#     runs regardless of platform — records are stamped platform-honest
#     like every bench.metric (docs/perf.md#overlap).
if [ "${OVERLAP:-0}" != 0 ]; then
  run python bench.py --phase overlap
fi

# 7. persistent compile-cache sweep (opt-in: CACHE_SWEEP=1): a cold run
#    into a FRESH cache dir, then a SECOND PROCESS over the same dir.
#    The second run's log must show zero executor.compile spans for the
#    cached keys (executor.compile.persistent_hit events instead) — the
#    restart-warmup contract (docs/perf.md). The obs_event rc records
#    both runs' wall clock in the sweep run log for the delta.
if [ "${CACHE_SWEEP:-0}" = 1 ]; then
  # a fixed, sweep-owned directory inside the resolved cache (the path is
  # part of what the second process must find again), emptied for the
  # cold run
  CDIR="${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}/cache_sweep"
  rm -rf "$CDIR"
  run env JAX_COMPILATION_CACHE_DIR="$CDIR" python bench.py --phase bundle
  run env JAX_COMPILATION_CACHE_DIR="$CDIR" python bench.py --phase bundle
  rm -rf "$CDIR"
fi

# 8. optimizer-pass A/B (opt-in: OPT=1): the bundle bench phase run with
#    PADDLE_TPU_OPT=off then =default — same shapes, same platform, so
#    the two bench.metric records in the sweep run log give the
#    off-vs-default steps/s delta the pass pipeline buys (passes.*
#    spans/counters in the same log attribute it per pass; docs/passes.md).
if [ "${OPT:-0}" = 1 ]; then
  run env PADDLE_TPU_OPT=off python bench.py --phase bundle
  run env PADDLE_TPU_OPT=default python bench.py --phase bundle
fi

# 8b. pod-scale GSPMD phase (opt-in: GSPMD=1): the annotated Program at
#     dp=N over every visible device vs single-device, through plain
#     Executor.run (no strategy wrapper) — fit_a_line (host-bound
#     honesty metric) + mnist_mlp (batch-bound scale-out metric), each
#     record stamped with mesh shape + platform + host_cores
#     (docs/parallel.md).
if [ "${GSPMD:-0}" = 1 ]; then
  run python bench.py --phase gspmd
fi

# 8c. sharded-embedding phase (opt-in: EMBED=1): the huge-vocab CTR
#     workload — dense-replicated vs sharded-sparse deepfm tables at
#     BENCH_EMBED_VOCAB (default 1e6) rows on the 'model' mesh; emits
#     steps/sec per leg, the *_rows_touched counter metric, and each
#     leg's compiled-step temp footprint (docs/embedding.md).
if [ "${EMBED:-0}" = 1 ]; then
  run python bench.py --phase embedding
fi

# 8c2. streaming-ids phase (opt-in: STREAM=1): the online-training
#      loop — drifting id stream -> VocabTable admission/eviction ->
#      sharded-sparse online training -> DeltaPublisher row pushes into
#      a live replica; emits steps/sec, freshness lag (*_lag_s,
#      lower-is-better), push latency (*_push_ms), and rows
#      admitted/evicted (docs/embedding.md#streaming). Host-side
#      machinery, so it runs regardless of platform.
if [ "${STREAM:-0}" = 1 ]; then
  run python bench.py --phase streaming
fi

# 8c3. tiered-embedding-storage phase (opt-in: TIER=1): zipf drift over
#      an id universe 8x the HBM row budget — TieredVocabTable (host
#      arena spill/restore) vs plain zeroing VocabTable over the same
#      stream; emits tiered + untiered steps/sec, the warm hit rate
#      (*_hit_rate), restore p50/p99 (*_ms,
#      lower-is-better), and asserts zero steady-state compiles
#      (docs/embedding.md#tiers). Host-side machinery plus two
#      fixed-signature dispatches, so it runs regardless of platform.
if [ "${TIER:-0}" = 1 ]; then
  run python bench.py --phase tiered
fi

# 8c4. pallas kernel A/B (opt-in: KERNELS=1): the paged decode-attention
#      kernel vs the gather+attention XLA lowering through the DecodeEngine
#      — tokens/sec per leg, per-chip MFU from the analytic per-token
#      flop count (TPU only; None on CPU where the kernel runs
#      INTERPRETED and the comparison is parity, not speed), trace-time
#      kernel dispatch count, and zero steady-state compiles per leg
#      (docs/perf.md#kernel-layer). The interpret field stamps which
#      regime the record measured.
if [ "${KERNELS:-0}" = 1 ]; then
  run python bench.py --phase kernels
fi

# 8c5. int8 delta-push A/B (opt-in: QUANT=1): the DeltaPublisher wire
#      fp32 vs int8 over the SAME touched-row stream — bytes per push
#      per leg (streaming_*_delta_push_bytes, lower is better; int8
#      must land <= 0.55x fp32), publish p50 ms,
#      and the row round-trip error vs the documented max|row|/254
#      bound (docs/perf.md#quantized-inference). Host-side codec, so it
#      runs regardless of platform.
if [ "${QUANT:-0}" = 1 ]; then
  run python bench.py --phase quant
fi

# 8d. elastic smoke (opt-in: ELASTIC=1): the fast elastic drill tier —
#     sharded checkpoints through the Trainer, atomic commit + torn-write
#     fallback, reshard-on-restore topology change, heartbeat staleness
#     (docs/robustness.md#elastic). CPU-pinned: the drills exercise
#     host-side commit/restore machinery, not chip throughput.
if [ "${ELASTIC:-0}" = 1 ]; then
  run env JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
      -m 'elastic and not slow' tests/test_elastic.py
fi

# 9. serving engine vs sequential Predictor (opt-in: SERVE=1). Closed
#    loop at the acceptance concurrency, then an open-loop arrival test;
#    --check-compiles fails the command if steady state compiled, which
#    the obs_event rc then records in the sweep run log.
if [ "${SERVE:-0}" = 1 ]; then
  run python tools/serve_bench.py --model mnist --concurrency 8 \
      --requests 512 --check-compiles
  run python tools/serve_bench.py --model mnist --mode open --qps 200 \
      --duration 3 --check-compiles
fi

# 9b. AOT cold-replica warmup (opt-in: AOT=1): process A warms the
#     serving signature set and exports it as a step-artifact AOT blob;
#     a COLD process B imports the blob before its own warmup — time to
#     first response with ZERO online compiles (serve.aot.* records;
#     --check-compiles fails the leg if the cold replica compiled).
if [ "${AOT:-0}" = 1 ]; then
  run python tools/serve_bench.py --workload aot-cold --check-compiles
fi

# 10. continuous-batching decode vs whole-batch lockstep beam decode
#     (opt-in: DECODE=1): the open-loop mixed-length stream at equal
#     batch capacity; --check-speedup enforces the >=1.5x tokens/sec
#     acceptance bar and --check-compiles the closed-signature-set
#     contract (decode.* bench.metric records, docs/serving.md).
if [ "${DECODE:-0}" = 1 ]; then
  run python tools/serve_bench.py --workload decode --requests 96 \
      --check-compiles --check-speedup 1.5
fi

# 10a. paged decode memory (opt-in: PAGED=1): dense-slot vs paged
#      engine at EQUAL state-buffer bytes on a short-request stream —
#      --check-speedup here enforces the >=2x peak-concurrent-streams
#      capacity ratio; prefix-cache hit rate + zero steady compiles
#      ride along (decode.paged.* bench.metric records).
if [ "${PAGED:-0}" = 1 ]; then
  run python tools/serve_bench.py --workload decode-paged \
      --check-compiles --check-speedup 2.0
fi

# 10aa. pod-scale serving (opt-in: POD=1): sharded-replica scoring
#      across 2 worker processes (row-sharded table restored from a
#      sharded checkpoint, never dense) with a mid-run SIGKILL host
#      loss — reports host-loss detect + recovery time
#      (serve.pod.recovery_s, lower is better),
#      rows/sec before/after, dropped futures (must be 0), and
#      post-recovery steady compiles (--check-compiles enforces 0;
#      docs/serving.md#pod). Host-side failover machinery: CPU-safe.
if [ "${POD:-0}" = 1 ]; then
  run python tools/serve_bench.py --workload pod-sharded --check-compiles
fi

# 10ab. rpc pod wire (opt-in: RPC=1): the same pod router driven over
#      the length-prefixed TCP transport vs the file mailbox — reports
#      per-wire p50/p99/throughput plus streamed-decode TTFT
#      (serve.wire.* records); --check-speedup enforces rpc at-or-
#      better p50 vs the file wire. The decode-failover leg SIGKILLs
#      the stream-owning host mid-generation and enforces a token-
#      exact resume on the survivor (serve.decode_failover.resume_s /
#      _replayed_tokens, lower is better; exits
#      nonzero on any drop/reorder). Host-side wire machinery:
#      CPU-safe (docs/serving.md#pod-transport).
if [ "${RPC:-0}" = 1 ]; then
  run python tools/serve_bench.py --workload pod-rpc --check-speedup 1.0
  run python tools/serve_bench.py --workload decode-failover
fi

# 10ac. SLO gate (opt-in: SLO=1): the rpc pod workload + the decode-
#      failover drill graded against the checked-in percentile budgets
#      (tools/slo_budgets.json, obs.slo schema): serve_bench --slo
#      evaluates TTFT p50/p99 (client AND server-side), per-token p99,
#      recovery time, and dropped==0 from the run's own histograms/
#      events, prints one verdict line per budget, and exits nonzero
#      naming every violated percentile (docs/observability.md#slo-budgets).
#      The budgets are honest shared-CPU ceilings, so a failure here is
#      structural — a stall or a lost stream — not box noise. Host-side
#      machinery: CPU-safe.
if [ "${SLO:-0}" = 1 ]; then
  run python tools/serve_bench.py --workload pod-rpc \
      --slo tools/slo_budgets.json
  run python tools/serve_bench.py --workload decode-failover \
      --slo tools/slo_budgets.json
fi

# 10b. speculative decoding (opt-in: SPEC=1): greedy target-only vs
#      draft-then-verify on the predictable-continuation decoder;
#      reports measured accept-rate and enforces a tokens/sec win
#      (modest bar — the CI box is noisy; decode.spec.* records).
if [ "${SPEC:-0}" = 1 ]; then
  run python tools/serve_bench.py --workload decode-spec \
      --check-compiles --check-speedup 1.02
fi

echo "sweep complete; see $LOG" | tee -a "$LOG"
