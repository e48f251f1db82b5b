#!/usr/bin/env python
"""Kernel step-budget gate (tools/kernel_budgets.json).

The binned schedules' predicted grid-step counts are pure host arithmetic
(binned._plan_steps over _cell_stats), so a schedule regression — pad
creep, chunk-count blowup, a packer change that silently doubles phase-1
steps — is checkable offline, exactly like the collective-budget audit.
This tool recomputes the canonical table (Reddit-scale + products-scale
synthetic shapes, shipped geometries) and diffs it EXACTLY against the
committed JSON; any drift fails preflight until the table is regenerated
with --update and the diff is reviewed.

It also pins the flat-schedule acceptance claim: at the Reddit shape the
flat schedule must keep total predicted steps <= 0.75x the shipped
SLOT=128 geometry (the >= 25% reduction of record, docs/PERF.md).

The table carries a dtype axis: every geometry row records its staging
dtype and predicted staging-DMA bytes (binned.staging_bytes_for — padded
rows x 2 passes x H x itemsize), and the bf16-unit flat geometry must move
<= 0.6x the bytes of its fp32 flat twin at the Reddit shape.  The ratio is
not a clean 0.5 because the 16-row bf16 unit pads every touched cell to
twice the rows of the 8-row fp32 unit (measured ~0.52 on the uniform
synthetic shapes); 0.6 leaves headroom without letting the claim decay.

Stream rows (round 20): the Reddit-scale shape carries a ``stream``
entry — predicted streamed wire bytes/epoch for the out-of-core
executor at the GCN-of-record layers, priced both ways by
stream.segments.predicted_epoch_bytes on the real partition + frozen
halo width K.  check_stream_claim gates the round's acceptance claim:
the bf16 tier (2-byte slot activations + compact uint16 edge wire where
the frozen table space fits 16 bits) must move <= 0.55x the fp32
streamed baseline's bytes/epoch.  The ratio is not a clean 0.5 because
indegree/mask wire and the int32-vs-uint16 edge split are dtype-mixed;
0.55 holds only while BOTH cuts (bf16 floats and the compact edge wire)
stay live.

    python tools/check_kernel_budgets.py            # diff, exit 1 on drift
    python tools/check_kernel_budgets.py --update   # regenerate the table
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BUDGETS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "kernel_budgets.json")

# (name, num_rows/table_rows, num_edges, rng seed).  Uniform synthetic
# stand-ins sized to run the O(E) statistics in seconds; the REAL graphs'
# numbers live in docs/PERF.md and are hardware-window material.
SHAPES = [
    ("reddit_scaled", 32768, 4_194_304, 0),
    ("products_scaled", 262_144, 2_097_152, 1),
]

# Max allowed flat/default total-step ratio at the Reddit-scale shape
# (the tentpole acceptance criterion: >= 25% reduction).
FLAT_MAX_RATIO = 0.75

# Max allowed flat_bf16/flat staging-bytes ratio at the Reddit-scale shape
# (the bf16-storage acceptance criterion: ~2x fewer staging bytes; the
# 16-row unit's extra cell padding keeps it above a clean 0.5).
BF16_MAX_RATIO = 0.6

# Max allowed bf16-streamed / fp32-streamed predicted bytes-per-epoch
# ratio at the Reddit-scale shape (round-20 acceptance: the bf16 slot
# tier plus the compact uint16 edge wire must nearly halve the streamed
# bill; the dtype-independent indegree/mask wire keeps it above 0.5).
STREAM_BF16_MAX_RATIO = 0.55

# Streamed-row pricing configuration: the GCN of record (Reddit's
# 602-256-41 stack) rotated through 8 parts — the shape docs/PERF.md
# round 20 reports.
STREAM_PARTS = 8
STREAM_LAYERS = [602, 256, 41]


def _geometries():
    import roc_tpu.ops.pallas.binned as B
    return [
        ("default", B._default_geom()),
        ("wide", B.GEOM_WIDE),
        ("sparse_wide", B.GEOM_SPARSE_WIDE),
        ("flat", B.GEOM_FLAT),
        ("flat_sparse", B.GEOM_FLAT_SPARSE),
        ("flat_bf16", B.GEOM_FLAT_BF16),
        ("flat_sparse_bf16", B.GEOM_FLAT_SPARSE_BF16),
    ]


def compute_table():
    import numpy as np
    import roc_tpu.ops.pallas.binned as B
    table = {}
    for name, n, e, seed in SHAPES:
        rng = np.random.default_rng(seed)
        src = rng.integers(0, n, size=e).astype(np.int64)
        dst = rng.integers(0, n, size=e).astype(np.int64)
        entry = {"num_rows": n, "num_edges": e, "seed": seed,
                 "geometries": {}}
        for gname, geom in _geometries():
            cb, cn, cnt = B._cell_stats(src, dst, geom.sb, geom.rb)
            padded, s1, s2 = B._plan_steps(cb, cn, cnt, geom, n, n, e)
            entry["geometries"][gname] = {
                "padded_rows": int(padded),
                "steps_phase1": int(s1),
                "steps_phase2": int(s2),
                "steps_total": int(s1 + s2),
                "staging_dtype": str(B.staging_dtype(geom, False).__name__),
                "staging_bytes": int(B.staging_bytes_for(src, dst, geom)),
            }
        if name == "reddit_scaled":
            # the stream row needs a real partition + halo maps (O(E)
            # with a per-part unique) — priced once, at the shape the
            # acceptance claim is stated at
            entry["stream"] = _stream_entry(src, dst, n, e)
        table[name] = entry
    return table


def _stream_entry(src, dst, n, e):
    """Streamed-epoch wire row (round 20, stream/segments.py).  Prices
    predicted streamed bytes/epoch for the out-of-core executor at the
    GCN of record, both dtype tiers, on the REAL partition geometry:
    partition_graph's padded S/E and _stream_maps' frozen halo width K
    — the same numbers the executor's ledger predicts from.  The bf16
    leg applies the executor's own compact-edge eligibility rule
    (uint16 esrc when S + P*K fits 16 bits, uint16 edst when S does)."""
    from roc_tpu.graph.csr import from_edges
    from roc_tpu.graph.partition import partition_graph
    from roc_tpu.models import build_gcn
    from roc_tpu.stream.executor import _stream_maps
    from roc_tpu.stream.segments import predicted_epoch_bytes, split_segments

    part = partition_graph(from_edges(n, src, dst), STREAM_PARTS)
    K, _, _ = _stream_maps(part.meta, part.edge_src)
    segs = split_segments(build_gcn(STREAM_LAYERS, 0.0))
    P, S, E = STREAM_PARTS, part.shard_nodes, part.shard_edges
    fp32 = predicted_epoch_bytes(segs, P, S, E, K, STREAM_LAYERS[-1])
    esrc_sz = 2 if S + P * K <= 1 << 16 else 4
    edst_sz = 2 if S <= 1 << 16 else 4
    bf16 = predicted_epoch_bytes(segs, P, S, E, K, STREAM_LAYERS[-1],
                                 act_itemsize=2, esrc_itemsize=esrc_sz,
                                 edst_itemsize=edst_sz)
    return {
        "parts": STREAM_PARTS, "layers": list(STREAM_LAYERS),
        "shard_nodes": int(S), "shard_edges": int(E), "halo_k": int(K),
        "epoch_bytes_fp32": int(fp32),
        "epoch_bytes_bf16": int(bf16),
        "esrc_itemsize_bf16": esrc_sz,
        "edst_itemsize_bf16": edst_sz,
    }


def check_stream_claim(table):
    """Round-20 acceptance gate: the bf16 streamed tier must keep
    predicted streamed bytes/epoch <= STREAM_BF16_MAX_RATIO x the fp32
    streamed baseline at the Reddit shape, and the compact uint16 edge
    wire must stay eligible there — losing eligibility (frozen table
    space outgrowing 16 bits) silently hands the edge arrays their full
    int32 width back and the ratio decays toward 0.58."""
    problems = []
    r = table["reddit_scaled"]["stream"]
    b16, b32 = r["epoch_bytes_bf16"], r["epoch_bytes_fp32"]
    if b16 > STREAM_BF16_MAX_RATIO * b32:
        problems.append(
            f"stream bf16 claim: predicted streamed {b16} bytes/epoch > "
            f"{STREAM_BF16_MAX_RATIO}x fp32 streamed {b32} at "
            f"reddit_scaled — ratio {b16 / b32:.3f}")
    if r["esrc_itemsize_bf16"] != 2 or r["edst_itemsize_bf16"] != 2:
        problems.append(
            "stream bf16 claim: compact uint16 edge wire no longer "
            "eligible at reddit_scaled — the bf16 tier is paying int32 "
            "edge bytes")
    return problems


def check_flat_claim(table):
    g = table["reddit_scaled"]["geometries"]
    flat, dflt = g["flat"]["steps_total"], g["default"]["steps_total"]
    problems = []
    if flat > FLAT_MAX_RATIO * dflt:
        problems.append(f"flat schedule regression: {flat} steps vs default "
                        f"{dflt} at reddit_scaled — ratio "
                        f"{flat / dflt:.3f} > {FLAT_MAX_RATIO}")
    b16, b32 = g["flat_bf16"]["staging_bytes"], g["flat"]["staging_bytes"]
    if b16 > BF16_MAX_RATIO * b32:
        problems.append(f"bf16 staging regression: flat_bf16 moves {b16} "
                        f"staging bytes vs flat {b32} at reddit_scaled — "
                        f"ratio {b16 / b32:.3f} > {BF16_MAX_RATIO}")
    return problems


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    update = "--update" in argv
    table = compute_table()
    problems = check_flat_claim(table) + check_stream_claim(table)
    if update:
        if problems:
            for p in problems:
                print(f"KERNEL BUDGET VIOLATION: {p}")
            return 1
        # Regenerating the predicted table must not discard the measured
        # one (tools/kernel_bench.py's subtree — device timings are not
        # recomputable offline).
        if os.path.exists(BUDGETS_PATH):
            try:
                with open(BUDGETS_PATH, encoding="utf-8") as f:
                    prev = json.load(f)
                if "measured" in prev:
                    table["measured"] = prev["measured"]
            except ValueError:
                # roclint: allow(silent-swallow) — rewrite below replaces it wholesale
                pass
        with open(BUDGETS_PATH, "w", encoding="utf-8") as f:
            json.dump(table, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# kernel_budgets: wrote {BUDGETS_PATH}")
        return 0
    if not os.path.exists(BUDGETS_PATH):
        print(f"KERNEL BUDGET VIOLATION: {BUDGETS_PATH} missing — run "
              f"with --update and commit it")
        return 1
    with open(BUDGETS_PATH, encoding="utf-8") as f:
        committed = json.load(f)
    # The measured subtree is kernel_bench's, not this tool's: timings
    # drift run to run by design and never gate the schedule diff.
    committed.pop("measured", None)
    if committed != table:
        for name in sorted(set(committed) | set(table)):
            a, b = committed.get(name), table.get(name)
            if a != b:
                problems.append(f"{name}: committed {a} != computed {b}")
    for p in problems:
        print(f"KERNEL BUDGET VIOLATION: {p}")
    n = len(problems)
    print(f"# kernel_budgets: {n} violation(s)", file=sys.stderr)
    return 1 if n else 0


if __name__ == "__main__":
    sys.exit(main())
