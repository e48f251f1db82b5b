#!/bin/bash
# One-shot hardware revalidation on a machine that holds a chip.
#
# ORDERING CONTRACT (VERDICT r4 weak #3): the first thing a window buys is
# the canonical bench of shipped defaults — round 2's only window was
# ~40 min and four rounds produced null driver artifacts while this script
# spent its first ~20 min on kernel tests.  Steps, highest-value first:
#   1. bench.py on shipped defaults (SLOT=128, auto-geometry) — headline
#   2. products-shape A/B (matmul vs auto-binned vs +reorder)
#   3. fp32-exact + GAT + overcommit benches
#   4. TPU-gated kernel tests
#   5. out-of-core streaming A/B, serving bench, fault drill (SIGTERM ->
#      resume parity; seeded chaos twin on the streamed path)
#   6. group-count / constant / sparse-preset sweeps
# Each step is timeout-guarded so a wedged compile can't eat the window.
# Usage:  bash tools/hw_revalidate.sh [start-step]  (from repo root)
set -u
cd "$(dirname "$0")/.."
LOG=/tmp/hw_revalidate.log
START=${1:-0}
case "$START" in
    [0-6]) ;;
    *) echo "usage: $0 [start-step 0-6]" >&2; exit 2 ;;
esac
: > "$LOG"

note() { echo "== $*" | tee -a "$LOG"; }

if [ "$START" -le 0 ]; then
note "0. static analysis gate (roclint + collective budget audit) — no"
note "   TPU minutes spent: catches host syncs / budget drift before the"
note "   window burns on a program we would reject anyway"
timeout 120 python tools/roclint.py 2>&1 | tail -2 | tee -a "$LOG" \
    || { note "roclint findings; fix or waive before burning the window"; \
         exit 1; }
timeout 600 python tools/roclint.py --audit --no-lint 2>&1 | tail -2 \
    | tee -a "$LOG" || { note "budget audit red; investigate first"; exit 1; }
fi

if [ "$START" -le 1 ]; then
note "1. bench shipped defaults (THE headline; expect binned, ~0.63 s/epoch)"
timeout 1800 python bench.py 2>&1 | tail -3 | tee -a "$LOG"
fi

if [ "$START" -le 2 ]; then
note "2. products-shape single-chip A/B (the north-star graph:"
note "   matmul vs binned-auto-geometry vs +RCM-reorder)"
# ROC_BENCH_SHAPE=products now presets nodes/degree/layers by itself
PROD="env ROC_BENCH_SHAPE=products ROC_BENCH_EPOCHS=5"
# SAME-PROCESS A/B (round-5 anomaly fix, docs/PERF.md): both legs in one
# invocation, per-epoch samples in the artifact — separate invocations
# are how the 8.5x forced-vs-auto artifact happened.  With the refit
# cost model auto now resolves to a sparse binned preset here, so the
# legs are the real matmul-vs-binned comparison.
$PROD ROC_BENCH_AB=matmul,auto timeout 6000 python bench.py 2>&1 \
    | tail -2 | tee -a "$LOG"
# with the RCM locality pass (auto keeps the order only on a measured
# padded-row gain): choose_geometry should then pick a binned geometry
$PROD ROC_BENCH_BACKEND=auto ROC_BENCH_REORDER=auto timeout 3000 \
    python bench.py 2>&1 | tail -2 | tee -a "$LOG"
# hierarchical-locality variant (inter edges ring-adjacent, the structure
# real co-purchase graphs have): A/B the reorder win where it can exist —
# the uniform-inter runs above are the locality worst case
for rr in 0 auto; do
    $PROD ROC_BENCH_BACKEND=auto ROC_BENCH_INTER=ring ROC_BENCH_REORDER=$rr \
        timeout 3000 python bench.py 2>&1 | tail -2 | tee -a "$LOG"
done
fi

if [ "$START" -le 3 ]; then
note "3a. fp32-exact epoch on the binned kernels (target: <= 1.0 s)"
ROC_BENCH_PRECISION=exact ROC_BENCH_BACKEND=binned ROC_BENCH_EPOCHS=5 \
    timeout 1800 python bench.py 2>&1 | tail -2 | tee -a "$LOG"

note "3b. GAT shape sweep, plan-backend attention (target: within ~2x of"
note "    GCN at the canonical shape; record each leg's roofline_frac in"
note "    docs/PERF.md — the sweep shows where the attention path falls"
note "    off the roofline as width/depth grow)"
for gat_shape in 602-64-41 602-128-41 602-64-64-41; do
    note "   ROC_BENCH_LAYERS=$gat_shape"
    ROC_BENCH_MODEL=gat ROC_BENCH_LAYERS=$gat_shape ROC_BENCH_HEADS=4 \
        ROC_BENCH_EPOCHS=5 timeout 1800 python bench.py 2>&1 \
        | tail -2 | tee -a "$LOG"
done

note "3c. overcommit: 4 parts on the 1 bench chip (multi-part paths:"
note "    halo all_to_all, per-part plans, psum)"
timeout 900 python -m roc_tpu -dataset reddit-small -layers 602-128-41 \
    -e 10 -parts 4 -v 2>&1 | tail -2 | tee -a "$LOG"
timeout 900 python -m roc_tpu -dataset reddit-small -layers 602-128-41 \
    -e 10 -parts 4 -no-halo -v 2>&1 | tail -2 | tee -a "$LOG"
timeout 900 python -m roc_tpu -dataset reddit-small -layers 602-64-41 \
    -e 10 -parts 4 -model gat -heads 2 -aggr-backend matmul -v 2>&1 \
    | tail -2 | tee -a "$LOG"

note "3d. balancer dryrun: 4-part overcommit with the online cost-model"
note "    load balancer (probe -> fit -> reshard under frozen shapes;"
note "    expect 'balance@' lines, reshard only if pred gain >= 5%)"
timeout 900 python -m roc_tpu -dataset reddit-small -layers 602-128-41 \
    -e 8 -parts 4 -balance-every 2 -v 2>&1 | tail -4 | tee -a "$LOG"

note "3e. memory-plan dryrun (roc_tpu/memory): DP under a deliberately"
note "    tight budget — expect a 'mem-plan[auto/dp]' line with >=1 remat"
note "    layer, and the bench artifact's memory block comparing predicted"
note "    vs measured (memory_stats) peak HBM"
ROC_BENCH_MEM=1 ROC_MEM_PLAN=auto ROC_MEM_BUDGET=4g ROC_BENCH_EPOCHS=5 \
    timeout 1800 python bench.py 2>&1 | tail -2 | tee -a "$LOG"
timeout 900 python -m roc_tpu -dataset reddit-small -layers 602-128-41 \
    -e 10 -parts 4 -mem-plan auto -mem-budget 2g -v 2>&1 \
    | tail -3 | tee -a "$LOG"

note "3f. bf16-storage A/B at the canonical Reddit GCN shape: paired legs"
note "    (fp32 storage, then ROC_BF16_STORAGE=1) — compare epoch time"
note "    (expect the bf16 leg faster where the run is staging/halo"
note "    byte-bound; artifact 'dtype' field distinguishes the pair) and"
note "    final loss (parity gate: |bf16 - fp32| within 1e-2)"
ROC_BENCH_EPOCHS=5 timeout 1800 python bench.py 2>&1 \
    | tail -2 | tee -a "$LOG"
ROC_BF16_STORAGE=1 ROC_BENCH_EPOCHS=5 timeout 1800 python bench.py 2>&1 \
    | tail -2 | tee -a "$LOG"
# sharded loss A/B (halo wire rides bf16; -v prints per-epoch loss)
timeout 900 python -m roc_tpu -dataset reddit-small -layers 602-128-41 \
    -e 10 -parts 4 -v 2>&1 | tail -2 | tee -a "$LOG"
timeout 900 python -m roc_tpu -dataset reddit-small -layers 602-128-41 \
    -e 10 -parts 4 -bf16-storage -v 2>&1 | tail -2 | tee -a "$LOG"

note "3g. obs-trace capture: the shipped-defaults bench under ROC_OBS=1 —"
note "    hands back the first HOST-side span trace from real hardware"
note "    (trace.json loads in Perfetto next to an xprof trace) plus the"
note "    watchdog verdict against the budget-seeded EWMA; artifacts under"
note "    /tmp/roc_obs_hw"
ROC_OBS=1 ROC_OBS_DIR=/tmp/roc_obs_hw ROC_BENCH_EPOCHS=5 \
    timeout 1800 python bench.py 2>&1 | tail -2 | tee -a "$LOG"
timeout 120 python -m roc_tpu.obs report -dir /tmp/roc_obs_hw 2>&1 \
    | tee -a "$LOG"
timeout 120 python -m roc_tpu.obs calibration -dir /tmp/roc_obs_hw 2>&1 \
    | tee -a "$LOG"

note "3h. per-kernel microbench on the chip: times every Pallas variant"
note "    (two-pass p1/p2, flat, fused, matmul) in isolation"
note "    across the geometry presets and COMMITS the measured table into"
note "    tools/kernel_budgets.json — the balance cost model and"
note "    choose_geometry warm-start from it (interpret=false tables only;"
note "    the CPU table in the repo is schema ballast, never trusted)."
note "    Review + commit the kernel_budgets.json diff after the window."
KB_DEVICE=1 KB_REPS=5 timeout 1800 \
    python tools/kernel_bench.py --update 2>&1 | tail -20 | tee -a "$LOG"

note "    ... then the geometry AUTOTUNER (roc_tpu/tune): successive-"
note "    halving sweep of the kernel-config lattice at the device shapes,"
note "    winners persisted content-keyed into tuned.json beside the plan"
note "    cache (choose_geometry consults them before its analytic model"
note "    on the very next run), the refit stage re-solving the binned"
note "    cost terms' rates and mm_chunk_s from the trial records into"
note "    the kernel_budgets measured table, and the calibration report"
note "    grading every trial's predict/measure pair.  One command:"
timeout 3600 python -m roc_tpu.tune --device --shapes device \
    --refit --update 2>&1 | tail -25 | tee -a "$LOG"
fi

if [ "$START" -le 4 ]; then
note "4. TPU-gated kernel tests (incl. H=41, fallback kernel, avg, flat)"
PYTHONPATH=$PWD timeout 1200 python tests/test_tpu_hw.py \
    2>&1 | tail -3 | tee -a "$LOG"

note "4b. flat-vs-slot-padded A/B at Reddit scale (same shape, flat=0/1;"
note "    model predicts ~37% fewer grid steps — record the measured ratio"
note "    in docs/PERF.md and re-fit the flat DMA constant from it)"
for flat in 0 1; do
    timeout 900 python tools/sweep_binned.py 512 4096 128 512 4096 \
        2097152 $flat 2>&1 | tail -1 | tee -a "$LOG"
done
fi

if [ "$START" -le 5 ]; then
note "5. out-of-core streaming A/B at the canonical shape: paired legs"
note "   (in-core SPMD, then ROC_BENCH_STREAM=1 rotating 4 shards through"
note "   2 device slots).  Record both epoch times and the streamed leg's"
note "   stream.stream_overlap_frac (the artifact's measured transfer/"
note "   compute overlap) in docs/PERF.md round 11 — the cost model"
note "   predicts near-full overlap when per-shard compute exceeds the"
note "   staging-DMA time of one slot's table bytes"
ROC_BENCH_EPOCHS=5 timeout 1800 python bench.py 2>&1 \
    | tail -2 | tee -a "$LOG"
ROC_BENCH_STREAM=1 ROC_STREAM_SLOTS=2 ROC_BENCH_EPOCHS=5 \
    timeout 1800 python bench.py 2>&1 | tail -2 | tee -a "$LOG"
note "   round-20 tier legs: bf16-streamed (wire bytes must land near"
note "   0.5x the fp32 streamed leg's stream.bytes_per_epoch — the"
note "   kernel_budgets stream row's <= 0.55x claim, measured), then the"
note "   NVMe spill tier (same slots; record stream.stream_spill_stall_frac"
note "   — the cost model predicts near-zero when spill reads hide under"
note "   the ring like host reads do).  Artifacts stamp stream_dtype/"
note "   stream_spill, so the paired legs stay distinguishable."
ROC_BENCH_STREAM=1 ROC_STREAM_SLOTS=2 ROC_BF16_STORAGE=1 \
    ROC_BENCH_EPOCHS=5 timeout 1800 python bench.py 2>&1 \
    | tail -2 | tee -a "$LOG"
SPILL_DIR=$(mktemp -d /tmp/roc_spill.XXXXXX)
ROC_BENCH_STREAM=1 ROC_STREAM_SLOTS=2 ROC_STREAM_SPILL="$SPILL_DIR" \
    ROC_BENCH_EPOCHS=5 timeout 1800 python bench.py 2>&1 \
    | tail -2 | tee -a "$LOG"
rm -rf "$SPILL_DIR"
# driver-path smoke on real hardware: >2x-budget rotation + live obs
timeout 900 python -m roc_tpu -dataset reddit-small -layers 602-128-41 \
    -e 10 -parts 4 -stream -stream-slots 2 -v 2>&1 | tail -3 | tee -a "$LOG"

note "5b. serving latency/throughput on the chip (roc_tpu/serve): warm-"
note "    cache cold start (plan_builds must be 0), then open-loop p50/p99"
note "    at stepped offered QPS — record the knee (where p99 detaches"
note "    from p50) and the cold start in docs/PERF.md's serving table,"
note "    and compare measured p50 against the roofline forward-time"
note "    prediction (the serve-p50 ledger pair in the calibration report)"
timeout 1200 env ROC_SERVE_BENCH_DATASET=reddit-small \
    ROC_SERVE_BENCH_REQUESTS=500 ROC_SERVE_BENCH_QPS=50 \
    python tools/serve_bench.py 2>&1 | tail -2 | tee -a "$LOG"
for qps in 100 200 400; do
    note "   offered qps=$qps"
    timeout 1200 env ROC_SERVE_BENCH_DATASET=reddit-small \
        ROC_SERVE_BENCH_REQUESTS=500 ROC_SERVE_BENCH_QPS=$qps \
        python tools/serve_bench.py 2>&1 | tail -1 | tee -a "$LOG"
done
# riding-along capture on the canonical bench shape (serve block in the
# bench artifact; excluded from vs_baseline / the canonical persist)
ROC_BENCH_SERVE=1 ROC_BENCH_EPOCHS=5 timeout 1800 python bench.py 2>&1 \
    | tail -2 | tee -a "$LOG"

note "5c. fault drill on the chip (roc_tpu/fault): three legs."
note "    (i) SIGTERM mid-run — the trainer must finish the epoch, write"
note "    the checkpoint, and exit cleanly; (ii) -resume from that"
note "    checkpoint completes and the final loss matches the"
note "    uninterrupted reference leg; (iii) a seeded chaos leg (retried"
note "    ring fetch + lux read, one injected NaN step) on the streamed"
note "    path must finish with a finite loss within 1e-3 of its own"
note "    fault-free twin.  Chaos legs NEVER feed perf baselines — their"
note "    epoch times include injected sleeps and retries."
CKPT=/tmp/roc_fault_drill.npz
DRILL="python -m roc_tpu -dataset reddit-small -layers 602-64-41 -e 12 -v"
rm -f "$CKPT"
timeout 900 $DRILL -ckpt "$CKPT" -ckpt-every 2 > /tmp/roc_drill_a.log 2>&1 &
DRILL_PID=$!
sleep 45; kill -TERM "$DRILL_PID" 2>/dev/null
wait "$DRILL_PID"
tail -2 /tmp/roc_drill_a.log | tee -a "$LOG"
grep -q "exiting cleanly" /tmp/roc_drill_a.log \
    || note "   drill note: no clean-exit line (run may have finished first)"
[ -f "$CKPT" ] || note "   drill RED: SIGTERM leg left no checkpoint"
timeout 900 $DRILL -ckpt "$CKPT" -resume 2>&1 | tail -2 | tee -a "$LOG"
timeout 900 $DRILL 2>&1 | tail -2 | tee -a "$LOG"   # uninterrupted reference
# chaos twin pair on the streamed path (same seed; compare final losses)
STREAMED="python -m roc_tpu -dataset reddit-small -layers 602-64-41 \
    -e 10 -parts 2 -stream -stream-slots 2 -v"
timeout 900 $STREAMED 2>&1 | tail -2 | tee -a "$LOG"
ROC_FAULT="seed=5,ring.fetch=2,lux.read=1,step.nan=1" timeout 900 \
    $STREAMED 2>&1 | tail -3 | tee -a "$LOG"

note "5d. on-device delta drill (roc_tpu/serve/delta): mixed add/retire"
note "    churn on the real chip — the serve selftest's delta leg pins"
note "    zero retraces + zero plan rebuilds + journal restart-replay"
note "    parity, then the fault selftest's delta stage runs the kill-"
note "    window matrix (lost-before-WAL vs replayed-after-WAL, torn"
note "    tail truncated).  The bench's delta block records apply"
note "    p50/p99 fault-free; chaos legs NEVER feed perf baselines."
timeout 900 python -m roc_tpu.serve --selftest 2>&1 | tail -3 | tee -a "$LOG"
timeout 600 python -m roc_tpu.fault --selftest 2>&1 | tail -2 | tee -a "$LOG"
timeout 1200 env ROC_SERVE_BENCH_DATASET=reddit-small \
    ROC_SERVE_BENCH_REQUESTS=200 ROC_SERVE_BENCH_QPS=50 \
    ROC_SERVE_BENCH_DELTAS=100 \
    python tools/serve_bench.py 2>&1 | tail -1 | tee -a "$LOG"

note "5e. on-device fleet drill (roc_tpu/fleet): 3 replicas behind the"
note "    router on the real chip — WAL-shipped segment replication in"
note "    seq lockstep (bitwise parity vs a single-engine oracle), a"
note "    seeded replica kill + snapshot catch-up mid-stream, typed"
note "    backpressure counted.  Then the bench's --fleet sweep records"
note "    router p50/p99 + shed rate + replication lag p99 fault-free"
note "    (the fleet block of BENCH_SERVE.json)."
timeout 900 python -m roc_tpu.fleet --selftest 2>&1 | tail -4 | tee -a "$LOG"
timeout 1800 env ROC_SERVE_BENCH_DATASET=reddit-small \
    ROC_SERVE_BENCH_REQUESTS=200 ROC_SERVE_BENCH_QPS=50 \
    ROC_SERVE_BENCH_DELTAS=100 \
    python tools/serve_bench.py --fleet 3 2>&1 | tail -1 | tee -a "$LOG"
fi

if [ "$START" -le 6 ]; then
note "6. group-count sweep (fewer groups -> less phase-1 rounding)"
for grt in 2097152 4194304 8388608; do
    note "   ROC_BINNED_GROUP_ROWS=$grt"
    ROC_BINNED_GROUP_ROWS=$grt ROC_BENCH_BACKEND=binned \
        timeout 1800 python bench.py 2>&1 | tail -2 | tee -a "$LOG"
done

note "6b. constant sweep round 2"
timeout 5400 python tools/sweep_binned.py 2>&1 | tee -a "$LOG"

note "6c. sparse-preset sweep at products shape (re-fit choose_geometry's"
note "    cost model constants from whatever this measures)"
SWEEP_SHAPE=products SWEEP_N=2449029 SWEEP_E=125000000 SWEEP_TIMEOUT_S=1800 \
    timeout 6000 python tools/sweep_binned.py 2>&1 | tee -a "$LOG"
fi

note "done — record winners in docs/PERF.md + BASELINE.md, update"
note "ROC_BINNED_GROUP_ROWS default / native BN_* constants if changed"
