#!/bin/bash
# Preflight gate: run the tier-1 lane (ROADMAP.md §Tier-1 verify) and
# refuse to let a snapshot/commit proceed on red.
#
# Usage:
#   bash tools/preflight.sh            # run lane, report DOTS_PASSED, exit rc
#   bash tools/preflight.sh --commit "msg"   # lane, then git commit -am only
#                                            # if the lane is green
#
# The DOTS_PASSED count is the lane's progress-dot tally — compare it
# against the last recorded baseline (CHANGES.md) to catch silently
# deselected tests, which a bare exit code cannot.
set -u
cd "$(dirname "$0")/.."
LOG=/tmp/_t1.log

set -o pipefail

# Stage 0: static analysis (roc_tpu/analysis/) — AST lint over the tree,
# then the collective budget audit (lowering only; CPU suffices).  Red
# here means a host sync / tracer hazard crept in, or a config's compiled
# communication drifted from budgets.json (regenerate DELIBERATE drifts
# with tools/roclint.py --update-budgets and review the manifest diff).
echo "== roclint =="
python tools/roclint.py || {
    echo "preflight: roclint findings — refusing to snapshot" >&2; exit 1; }
echo "== budget audit =="
timeout -k 10 600 python tools/roclint.py --audit --no-lint || {
    echo "preflight: collective budget audit RED" >&2; exit 1; }
# Lock-discipline gate: the whole-tree concurrency analyzer must report
# zero findings (after reasoned waivers) and zero drift against the
# committed threads.json lock-order baseline (exit 3 on either).
# Regenerate DELIBERATE discipline changes with --update-threads and
# review the diff; the analyzer's own seeded-mutation matrix (inversion,
# dropped guard, waitless condvar, ...) must keep biting.
echo "== lock discipline =="
timeout -k 10 120 python tools/roclint.py --threads --no-lint || {
    echo "preflight: lock discipline RED (threads findings or baseline drift)" >&2; exit 3; }
echo "== threads selftest =="
timeout -k 10 120 python -m roc_tpu.analysis.threads --selftest || {
    echo "preflight: threads analyzer selftest RED" >&2; exit 1; }
# Kernel step budgets: predicted binned grid-step counts at the canonical
# shapes must match tools/kernel_budgets.json exactly, and the flat
# schedule must hold its >=25% step reduction over the shipped default.
# Regenerate deliberate drifts with tools/check_kernel_budgets.py --update.
echo "== kernel step budgets =="
timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python tools/check_kernel_budgets.py || {
    echo "preflight: kernel step budgets RED" >&2; exit 1; }
# Bench-artifact schema: BENCH_SERVE.json is written outside the test
# suite; a field rename in tools/serve_bench.py would otherwise surface
# months later.
echo "== bench artifact schema =="
timeout -k 10 60 python tools/perf_ledger.py --check || {
    echo "preflight: bench artifact schema RED" >&2; exit 1; }

# Obs gate: the observability layer holds its own contracts — tracer
# span nesting + Chrome-trace schema validity, watchdog fires on an
# injected 3x slow epoch / stays quiet on noise, and the span overhead
# bound (stdlib-only, so this costs ~100 ms).
echo "== obs selftest =="
timeout -k 10 120 env JAX_PLATFORMS=cpu python -m roc_tpu.obs selftest || {
    echo "preflight: obs selftest RED" >&2; exit 1; }

# Calibration gate: the prediction/measurement ledger must actually pair
# on a tiny CPU run — >= 5 cost models joined by content key, each inside
# its sanity band.  This is the wiring proof for the flight recorder: a
# renamed field or a broken content key shows up here, not on the chip.
echo "== calibration selftest =="
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python -m roc_tpu.obs calibration --selftest || {
    echo "preflight: calibration selftest RED" >&2; exit 1; }

# Autotune gate: the geometry autotuner's closed CPU world must hold —
# seeded-surrogate sweep byte-identical across two runs, tuned.json
# schema valid, choose_geometry provably consumes the tuned entry (and
# falls back off-key), refit recovers the generating constants within
# 5%, and every trial pairs in the calibration ledger.
echo "== autotune selftest =="
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python -m roc_tpu.tune --selftest || {
    echo "preflight: autotune selftest RED" >&2; exit 1; }

# Memory-plan determinism gate: the same config must produce a
# byte-identical plan JSON (the plan participates in the step cache key —
# nondeterminism here means phantom retraces and unreproducible OOM
# triage).  Pure analytic path (no jax arrays), so this costs ~a second.
echo "== memory-plan determinism =="
PLAN_A=$(mktemp) PLAN_B=$(mktemp)
timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m roc_tpu.memory --mode auto --budget 6g > "$PLAN_A" && \
timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m roc_tpu.memory --mode auto --budget 6g > "$PLAN_B" && \
cmp -s "$PLAN_A" "$PLAN_B" || {
    echo "preflight: memory plan JSON not deterministic" >&2
    diff "$PLAN_A" "$PLAN_B" >&2; rm -f "$PLAN_A" "$PLAN_B"; exit 1; }
rm -f "$PLAN_A" "$PLAN_B"

# Streamed smoke: the out-of-core executor must still train end-to-end
# (tiny graph, 2 shards through 2 slots).  This is the cheapest proof that
# slot rotation, the prefetch ring, and the host-side gradient scatter all
# still compose — unit tests cover the pieces, this covers the wiring.
echo "== streamed smoke =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m roc_tpu \
    -dataset roc-audit -layers 8-16-4 -e 2 -parts 2 \
    -stream -stream-slots 2 -eval-every 100 >/dev/null || {
    echo "preflight: streamed smoke RED" >&2; exit 1; }

# Serve smoke: cold start from a warm plan cache (zero plan rebuilds,
# asserted), ~100 mixed-batch-size queries on the tiny CPU dataset with
# served-vs-eval parity <= 32 ULPs and zero retraces after warmup — the
# serving contracts, end-to-end in one process (roc_tpu/serve/__main__).
# Includes the delta leg: journaled add/retire churn patched with zero
# retraces / zero plan rebuilds, then a restart that replays the delta
# journal to bitwise-identical served logits.
echo "== serve smoke =="
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python -m roc_tpu.serve --selftest >/dev/null || {
    echo "preflight: serve smoke RED" >&2; exit 1; }
# Serving bench artifact: tools/serve_bench.py must emit a BENCH_SERVE
# payload that passes the perf-ledger schema gate (tmp root — the real
# BENCH_SERVE.json is only written by an actual bench invocation).
echo "== serve bench selftest =="
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python tools/serve_bench.py --selftest || {
    echo "preflight: serve bench selftest RED" >&2; exit 1; }

# Fleet drill: 3 replicas behind the router under a 1000-event mixed
# query+delta stream — WAL-shipped segment replication keeps every
# member in seq lockstep (bitwise parity vs a single-engine oracle,
# zero retraces / zero plan rebuilds), a seeded hard kill of one
# follower mid-stream loses nothing (local WAL replay + snapshot
# catch-up while the survivors keep answering), and backpressure is
# typed + counted (roc_tpu/fleet/__main__).
echo "== fleet drill =="
timeout -k 10 570 env JAX_PLATFORMS=cpu \
    python -m roc_tpu.fleet --selftest >/dev/null || {
    echo "preflight: fleet drill RED" >&2; exit 1; }

# Fault-harness gate: the chaos machinery itself must be provably live —
# seeded spec determinism, retry recovery/exhaustion/kill-switch, the
# fsync-rename durability helper, the jitted non-finite skip, a seeded
# NaN-injection mini-train + serve-queue shed smoke, and the delta-
# journal kill-window matrix (lost-before-WAL vs replayed-after-WAL).
# Without this, "the faults didn't fire" and "the faults fired and were
# survived" are indistinguishable from a green run.
echo "== fault selftest =="
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python -m roc_tpu.fault --selftest >/dev/null || {
    echo "preflight: fault selftest RED" >&2; exit 1; }

rm -f "$LOG"
# ROC_T1_TIMEOUT: the full tier-1 lane needs ~1030 s on a 1-core box
# (PR 18 note) — the old hard-coded 870 s stopwatch lied.  Env knob so
# slow boxes can widen it without editing the gate.
timeout -k 10 "${ROC_T1_TIMEOUT:-1500}" env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}
dots=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" | tr -cd . | wc -c)
echo "DOTS_PASSED=$dots"

if [ "$rc" -ne 0 ]; then
    echo "preflight: tier-1 lane RED (rc=$rc) — refusing to snapshot" >&2
    exit "$rc"
fi
echo "preflight: tier-1 lane green"

if [ "${1:-}" = "--commit" ]; then
    shift
    git commit -am "${1:?--commit needs a message}"
fi
