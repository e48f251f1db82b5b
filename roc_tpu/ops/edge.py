"""Edge-tensor ops: per-edge scores, edge softmax, attention aggregation.

The reference declares edge tensors as first-class (create_edge_tensor,
gnn.cc:534-589; EDGE_TENSOR input paths in linear.cc:73-77,
activation.cc:48-52, dropout.cc:42-46) but ships no op that produces one —
the capability is latent (SURVEY.md §2.1).  Here edge tensors are realized
the TPU way: an edge tensor is an [E, ...] array aligned with the CSR's
dst-sorted edge order, sharded over the mesh's 'parts' axis by the same
edge partition that shards edge_src/edge_dst (roc_tpu/graph/partition.py).

These ops are what GAT-style models need: endpoint scores, a per-destination
softmax over in-edges, and attention-weighted aggregation.  All are pure
XLA (sorted segment reductions); pad edges are inert because the partitioner
routes them to pad destination rows (partition.py edge padding invariants).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from roc_tpu.obs import scopes

# Normalizer guard for every softmax division (live rows have z >= 1 by
# the max shift; the guard only touches edgeless/pad rows, whose quotient
# is 0 either way).  The VALUE is load-bearing twice over:
#   * >= ~1e-20, because XLA flushes subnormals to zero (a 1e-38 guard
#     vanishes and edgeless rows hit 0/0 NaN);
#   * >= ~1e-15, because the AUTODIFF transpose of a/b squares the
#     denominator: 1/(1e-20)^2 = 1e40 overflows fp32 to inf and
#     0 * inf = NaN silently poisons every parameter gradient (found at
#     products shape via the chunked-GAT backward; the hand-derived
#     custom-vjp backwards only ever divide by the first power, but the
#     autodiff'd sites — chunked GAT, edge_softmax, ring/edge attention —
#     go through d(a/b)/db = -a*ct/b^2).
_Z_GUARD = 1e-15


def edge_softmax(scores, edge_dst, num_nodes: int):
    """Per-destination softmax over in-edges.

    scores: [E, ...] (any trailing dims, e.g. one column per attention
    head); edge_dst: [E] sorted ascending.  Returns alpha with
    sum over {e : dst(e)=v} alpha[e] == 1 for every v with in-edges.
    """
    m = jax.ops.segment_max(scores, edge_dst, num_segments=num_nodes,
                            indices_are_sorted=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)          # edgeless destinations
    e = jnp.exp(scores - jnp.take(m, edge_dst, axis=0))
    s = jax.ops.segment_sum(e, edge_dst, num_segments=num_nodes,
                            indices_are_sorted=True)
    # _Z_GUARD (rationale at its definition above): survives the XLA
    # subnormal flush AND the autodiff division transpose's square; live
    # destinations have s >= 1 by the max shift.
    return e / jnp.maximum(jnp.take(s, edge_dst, axis=0), _Z_GUARD)


# GAT switches to the edge-chunked scan above the same gathered-intermediate
# budget as aggregate._chunked_segment_sum (2^28 elems = 1 GiB fp32 — at
# Reddit scale the dense [E, K, F] alone is ~24 GB, over a v5e's HBM).
# Shared constants so the two memory policies cannot drift.
from roc_tpu.ops.aggregate import (          # noqa: E402
    _CHUNK_TARGET_ELEMS as _GAT_CHUNK_TARGET_ELEMS,
    _CHUNK_THRESHOLD_ELEMS as _GAT_CHUNK_THRESHOLD_ELEMS)

_GAT_CHUNK_MIN = 1024     # floor on edge-chunk length (tests shrink it)


def attention_keep(key, rate: float, heads: int, num_edges: int):
    """The attention-dropout keep mask, ``[K, E]`` bool (True = kept), of
    one gat op for one step: Bernoulli(1 - rate) per edge and head
    (Velickovic et al. section 3.3: dropout on the normalised attention
    coefficients).  EVERY attention path draws its mask through this one
    function from the same key, so the dense, chunked and plan paths drop
    the same coefficients, and the plan path's hand-derived backward
    regenerates the mask here instead of saving ``[K, E]`` again."""
    return jax.random.bernoulli(key, 1.0 - rate, shape=(heads, num_edges))


def _drop_args(drop):
    """(key, rate) of a ``drop`` argument, (None, 0.0) when it drops
    nothing (None: evaluation; no key; rate 0): what the custom VJPs take,
    the rate static."""
    key, rate = drop if drop is not None else (None, 0.0)
    return (None, 0.0) if key is None or not rate else (key, float(rate))


def _keep_scale(drop, heads: int, num_edges: int, dtype):
    """``keep / (1 - p)`` as a ``[K, E]`` multiplier, or None when the call
    drops nothing."""
    key, rate = _drop_args(drop)
    if key is None:
        return None
    keep = attention_keep(key, rate, heads, num_edges)
    return jnp.where(keep, jnp.asarray(1.0 / (1.0 - rate), dtype),
                     jnp.asarray(0.0, dtype))


def gat_attend(h, table, edge_src, edge_dst, num_nodes: int,
               a_src, a_dst, slope: float, drop=None):
    """Multi-head graph attention aggregation (GAT).

    h:       [N_local, K, F] W-projected features of the *destination* rows.
    table:   [T, K, F] source feature table (== h on one device; local rows
             ++ halo rows, or the all-gathered tensor, under SPMD).
    a_src/a_dst: [K, F] attention vectors (the two halves of the GAT `a`).
    Per edge: s_e = LeakyReLU(a_dst.h[dst_e] + a_src.table[src_e]);
    alpha = edge_softmax(s); out[v] = sum_e alpha_e * table[src_e].
    ``drop`` = (key, rate) in training: the normalised coefficients are
    dropped per edge and head (:func:`attention_keep`), scaled by
    1 / (1 - rate), and NOT renormalised; None in evaluation.
    Returns [N_local, K, F].
    """
    E, (K, F) = edge_src.shape[0], h.shape[1:]
    if E * K * F > _GAT_CHUNK_THRESHOLD_ELEMS:
        return _chunked_gat_attend(h, table, edge_src, edge_dst, num_nodes,
                                   a_src, a_dst, slope, drop)
    as_t = jnp.einsum("tkf,kf->tk", table, a_src,
                      precision="highest")            # [T, K]
    ad_l = jnp.einsum("nkf,kf->nk", h, a_dst,
                      precision="highest")            # [N_local, K]
    s = jax.nn.leaky_relu(
        jnp.take(ad_l, edge_dst, axis=0) + jnp.take(as_t, edge_src, axis=0),
        negative_slope=slope)                          # [E, K]
    alpha = edge_softmax(s, edge_dst, num_nodes)       # [E, K]
    w = _keep_scale(drop, K, E, alpha.dtype)
    if w is not None:
        alpha = alpha * w.T
    g = jnp.take(table, edge_src, axis=0)              # [E, K, F]
    return jax.ops.segment_sum(g * alpha[:, :, None], edge_dst,
                               num_segments=num_nodes,
                               indices_are_sorted=True)


def _chunked_gat_attend(h, table, edge_src, edge_dst, num_nodes: int,
                        a_src, a_dst, slope: float, drop=None):
    """Memory-bounded GAT: never materializes [E, K, F].

    Standard streaming softmax shape: (1) one edge-chunk scan accumulates
    the per-destination score max m; (2) a second scan accumulates both the
    normalizer z[v] = Σ exp(s_e - m[v]) and the unnormalized output
    Σ exp(s_e - m[v])·table[src_e]; out = unnorm / z.  Same math as the
    dense path (softmax shift by the exact per-dst max), different sum
    order — equal up to float reassociation.  Working set per step:
    [chunk, K, F] plus the [N, K(, F)] accumulators.  Pad edges (routed to
    pad dst rows) only pollute pad rows.  Attention dropout (``drop``)
    scales the output's terms and never the normalizer's.

    The bound must survive autodiff, where lax.scan stacks per-step
    residuals back up to O(E*K*F): the accumulate body is rematerialized
    (jax.checkpoint — backward recomputes each chunk's gather/exp instead
    of saving them) and the max scan carries no gradient at all
    (stop_gradient on m: softmax is shift-invariant, d out/d m == 0).
    """
    E, (K, F) = edge_src.shape[0], h.shape[1:]
    as_t = jnp.einsum("tkf,kf->tk", table, a_src,
                      precision="highest")            # [T, K]
    ad_l = jnp.einsum("nkf,kf->nk", h, a_dst,
                      precision="highest")            # [N_local, K]

    chunk = max(_GAT_CHUNK_TARGET_ELEMS // max(K * F, 1), _GAT_CHUNK_MIN)
    nchunks = -(-E // chunk)
    pad = nchunks * chunk - E
    # pad edges: src 0 (harmless), dst at the extra throwaway row
    src = jnp.pad(edge_src, (0, pad)).reshape(nchunks, chunk)
    dst = jnp.pad(edge_dst, (0, pad),
                  constant_values=num_nodes).reshape(nchunks, chunk)
    w = _keep_scale(drop, K, E, as_t.dtype)
    # the [E, K] view of the mask rides the scan as one more per-chunk input
    wc = None if w is None else jnp.pad(w.T, ((0, pad), (0, 0))).reshape(
        nchunks, chunk, K)

    def scores(s_ids, d_ids):
        return jax.nn.leaky_relu(
            jnp.take(ad_l, jnp.minimum(d_ids, num_nodes - 1), axis=0)
            + jnp.take(as_t, s_ids, axis=0), negative_slope=slope)

    def max_body(m, sl):
        s_ids, d_ids = sl
        return m.at[d_ids].max(scores(s_ids, d_ids),
                               indices_are_sorted=True,
                               mode="promise_in_bounds"), None
    # Scan carries must inherit the device-varying vma annotation under
    # shard_map — via aggregate._vary_like (pcast: no gradient edge), NOT
    # `+ 0 * x`.  The sentinel must also be FINITE (-1e30, not -inf):
    # non-finite carry primals let the sharded backward manufacture
    # 0 * inf NaNs — the _ring_attend trap.
    from roc_tpu.ops.aggregate import _vary_like
    NEG = jnp.asarray(-1e30, as_t.dtype)
    m0 = _vary_like(jnp.full((num_nodes + 1, K), NEG, as_t.dtype), as_t)
    m, _ = jax.lax.scan(max_body, m0, (src, dst))
    m = jnp.where(m > NEG * 0.5, m, 0.0)              # edgeless destinations
    m = jax.lax.stop_gradient(m)

    def acc_body(carry, sl):
        z, out = carry
        s_ids, d_ids = sl[:2]
        e = jnp.exp(scores(s_ids, d_ids)
                    - jnp.take(m, d_ids, axis=0))     # [chunk, K]
        z = z.at[d_ids].add(e, indices_are_sorted=True,
                            mode="promise_in_bounds")
        g = jnp.take(table, s_ids, axis=0)            # [chunk, K, F]
        ew = e if wc is None else e * sl[2]
        out = out.at[d_ids].add(g * ew[:, :, None], indices_are_sorted=True,
                                mode="promise_in_bounds")
        return (z, out), None
    z0 = _vary_like(jnp.zeros((num_nodes + 1, K), as_t.dtype), as_t)
    o0 = _vary_like(jnp.zeros((num_nodes + 1, K, F), h.dtype), h)
    (z, out), _ = jax.lax.scan(  # scan-body remat, not an activation plan:
        # residuals here would be O(E) per chunk  # roclint: allow(remat) — scan-body remat; residuals would be O(E) per chunk
        jax.checkpoint(acc_body, prevent_cse=False), (z0, o0),
        (src, dst) if wc is None else (src, dst, wc))
    # _Z_GUARD (rationale at its definition above): edgeless rows would
    # otherwise hit 0/0 in fwd or 0 * inf in the division transpose (live
    # rows have z >= 1 by the max shift)
    return (out[:num_nodes]
            / jnp.maximum(z[:num_nodes], _Z_GUARD)[:, :, None])


# ---------------------------------------------------------------------------
# Plan-backend attention: edge softmax + weighted aggregation without a
# single TPU scatter, forward OR backward.
# ---------------------------------------------------------------------------
#
# The scan paths above scatter-add per edge chunk (`.at[].add` / `.at[].max`)
# — the exact per-index-serializing lowering the sum backends were built to
# avoid (~6.5 s/aggregation at Reddit scale, ops/aggregate.py).  Here every
# segment reduction rides the same host-built chunk schedule as the matmul
# sum backend (ops/pallas/segment_sum.py), with two twists:
#   * plans are built over EDGE POSITIONS: each (chunk, slot) carries both
#     `pos` (the edge's index into [E, ...] edge arrays) and `nid` (its
#     endpoint's row in the node table), so per-edge quantities (scores,
#     exp-weights) and node gathers compose inside one scan step;
#   * two directions are prebuilt — dst-keyed (forward softmax/aggregate)
#     and src-keyed (the backward reductions onto the source table) — the
#     same role swap the reference performs by relaunching its forward
#     kernel transposed (scattergather_kernel.cu:160-170).
# Segment-max (the softmax shift) is the same one-hot window machinery with
# masked max in place of the MXU dot.
#
# LAYOUT: every per-edge array of this path (s, e, its sign, the dropout
# multiplier, de, dq) is [K, E] — heads on the sublane axis, EDGES ON THE
# LANE AXIS.  The TPU tiles the two minor dimensions to (8, 128): an [E, 8]
# float32 array is stored at 128 lanes a row, 16 x its size (12 GB apiece at
# the Reddit shape, K = 8), an [8, E] one at its size (752 MB).  Node-sized
# score tables are [K, N] for the same reason; only the [*, K*F] feature
# rows keep nodes/slots on the sublane axis.  Pinned in the jaxpr by
# tests/test_gat_attention_dropout.py
# (test_plan_path_keeps_edges_on_the_lane_axis) and tests/test_gat_plans.py
# (test_no_gather_of_the_plan_path_is_indexed_by_edge_dst).
#
# READS: edge_dst is sorted, so a node table read by it is a segment
# broadcast, and the aligned dst-keyed plan holds it (_plan_broadcast;
# _edge_contract's du rows): no gather of this path takes edge_dst as its
# index.  The src side still gathers (the src-keyed plan's column reads,
# the feature rows by the plans' nid; on the edge-sharded road of
# parallel/spmd.py a lane gather by edge_src too), and
# pays for each index list ONCE a backward where memory allows: what a layer
# sums over the src-keyed plan is one scan, its per-edge weights stacked
# into one [K', E] array read by one column gather (src_pos) and its node
# tables side by side read by one row gather (src_nid): gat's dast rides
# dtable's scan (_plan_sum's ``ride``; while the stack fits a tile's
# sublanes, gat_src_scans), tconv's dk and dv are one sum of 2K heads.
# Tconv reads dst_nid once a pass: in the backward de (v rows) and dq (k
# rows) are one scan over one gather of [k | v] rows (_contract_then_sum),
# in the forward the score (k rows) and u (v rows) are one too, the
# softmax's max and normaliser, which stand between them, carried online
# (_score_then_sum).  Gat's forward is the same scan over [h | as_src]
# rows: the score's source half rides u's rows (_additive_tables).
#
# The full GAT layer is a custom_vjp (gat_attend_plan) whose hand-derived
# backward is built from these primitives plus the src side's plain gathers
# — autodiff of the forward would otherwise transpose every gather into a
# scatter.

_PLAN_CB_SUM = 512   # chunks per scan step, one-hot dot passes (the cap)
# a _plan_sum that gathers rows steps shorter where its gathered
# [cb * EB, lanes] float32 block would pass this (plan_sum_step).  One
# scan alone over the Reddit plans on a v5e, ms a pass at 64 / 128 / 256
# / 512 chunks a step (PERF.md PR 38):
#   src, 2K F = 256 lanes   754.7 / 750.8 / 778.6 / 876.9
#   src, 328 -> 384 lanes   854.8 / 851.7 / 928.5 / 1,079.2
#   u, 164 -> 256 lanes     451.1 / 445.9 / 445.0 / 587.3
#   u, 128 lanes            369.9 / 367.0 / 365.9 / 381.2
# The 128 MiB blocks (512 chunks over 256 lanes and more) go to HBM, the
# rest lie in VMEM; this budget keeps 128-lane rows (every gat sum) at
# the cap and gives the wider ones 256 (256 lanes) or 128 (384)
_PLAN_SUM_BLOCK_BYTES = 64 << 20
# the block-landing scans (_plan_blocks).  128, 256 and 512 are within 1 ms
# a pass of each other on a v5e (the combine dot grows cb^2, the step count
# falls); 128 is _plan_max's, so both scans pad the plan alike.  Also the
# step of the scans that land blocks AND sum rows (_contract_then_sum,
# where it is worth 144 to 285 ms a pass over 512, and _score_then_sum)
_PLAN_CB_BLOCKS = 128
_PLAN_CB_MAX = 128   # smaller: the masked-max intermediate is [K, cb, cb, VB]
_LANE_GATHER_CHUNK = 1 << 20   # indices a step of a long [K, M] lane gather


class GatPlans(NamedTuple):
    """Dst- and src-keyed edge-position chunk schedules (jit-traceable
    int32 arrays; stackable on a leading parts axis for shard_map).

    dst_*: chunks over the dst-sorted edge list, windows = destination rows
           (num_rows).  ``pos`` indexes [E,...] edge arrays (dst order);
           ``nid`` is the edge's SOURCE row in the feature table.  Built
           ALIGNED (_aligned_position_plan): slot j of a chunk is position
           ``EB * block + j``, so the device reads whole blocks.
    src_*: chunks over the src-sorted edge list, windows = table rows
           (table_rows).  ``pos`` again indexes dst-ordered edge arrays
           (the src-sort permutation is folded in); ``nid`` is the edge's
           DESTINATION row.
    """
    dst_obi: jnp.ndarray    # [Cd]
    dst_edst: jnp.ndarray   # [Cd, EB] window-local dst row, VB on pads
    dst_pos: jnp.ndarray    # [Cd, EB]
    dst_nid: jnp.ndarray    # [Cd, EB]
    src_obi: jnp.ndarray    # [Cs]
    src_edst: jnp.ndarray   # [Cs, EB]
    src_pos: jnp.ndarray    # [Cs, EB]
    src_nid: jnp.ndarray    # [Cs, EB]
    num_rows: int           # static: dst windows cover [0, num_rows)
    table_rows: int         # static: src windows cover [0, table_rows)


def _position_plan(keys_sorted, pos, nids_by_pos, num_rows):
    """Chunk plan over (position, key) pairs: esrc slots carry positions
    (indices into the canonical dst-ordered edge arrays); nid is gathered
    host-side so the device never indexes edge_src/edge_dst at runtime.
    ``nids_by_pos`` must be indexed by POSITION VALUE (dst order), not by
    slot order — the plan stores positions, and nid = nids_by_pos[pos]."""
    from roc_tpu.ops.pallas.segment_sum import VB, build_chunk_plan
    plan = build_chunk_plan(pos.astype(np.int64), keys_sorted.astype(np.int64),
                            num_rows)
    # Same invariant build_aggregate_plans pins: every window gets >= 1
    # chunk (consecutive obi jump <= 1), or _one_hot_dots/_plan_max would
    # silently drop windows (lw >= cb).  The native C++ builder serves
    # plans >= 1M edges — exactly the production attention regime — so the
    # check must live here, where both builders pass through.
    assert np.all(np.diff(np.asarray(plan.obi)) <= 1), \
        "chunk plan skips output windows (obi jump > 1)"
    masked = plan.edst == VB
    if nids_by_pos.shape[0] == 0:
        nid = np.zeros_like(plan.esrc)
    else:
        nid = np.where(masked, 0,
                       nids_by_pos[np.where(masked, 0, plan.esrc)])
    return plan.obi, plan.edst, plan.esrc.astype(np.int32), \
        nid.astype(np.int32)


def _aligned_position_plan(keys_sorted, nids, num_rows):
    """The dst-keyed position plan, cut so that every chunk lies inside ONE
    aligned block of EB consecutive positions: the dst-sorted edge list is
    cut at every multiple of EB and at every window boundary, and a chunk's
    slot j IS position ``EB * block + j`` (slots outside the piece are
    masked, ``edst == VB``).  The device then reads a chunk's values as one
    aligned [K, EB] block of the per-edge array, a row gather of whole
    (8, 128) tiles, where a chunk at an arbitrary offset costs a column
    gather of EB lanes (``_slot_reader``; PERF.md PR 25: 526 ms a pass over
    the Reddit plan).  About E / EB + windows chunks against the packed
    plan's E / EB x 1.14.  Same invariants as build_chunk_plan: chunks in
    window order, every window at least one (an empty window gets one
    all-masked chunk)."""
    from roc_tpu.ops.pallas.segment_sum import EB, VB
    keys = np.asarray(keys_sorted, np.int64)
    nids = np.asarray(nids, np.int64)
    E = keys.shape[0]
    assert E == 0 or np.all(np.diff(keys) >= 0), "keys not sorted"
    num_windows = max((num_rows + VB - 1) // VB, 1)
    win, blk = keys // VB, np.arange(E, dtype=np.int64) // EB
    cut = np.ones(E, bool)
    cut[1:] = (win[1:] != win[:-1]) | (blk[1:] != blk[:-1])
    lo = np.flatnonzero(cut)                 # first position of each piece
    hi = np.append(lo[1:], E)
    has = np.zeros(num_windows, bool)
    has[win[lo]] = True
    empty = np.flatnonzero(~has)
    nothing = np.zeros_like(empty)
    c_win = np.concatenate([win[lo], empty])
    order = np.argsort(c_win, kind="stable")  # pieces of a window: in order
    c_win = c_win[order]
    c_blk = np.concatenate([blk[lo], nothing])[order]
    c_lo = np.concatenate([lo, nothing])[order]
    c_hi = np.concatenate([hi, nothing])[order]
    pos = c_blk[:, None] * EB + np.arange(EB, dtype=np.int64)[None, :]
    valid = (pos >= c_lo[:, None]) & (pos < c_hi[:, None])
    safe = np.minimum(pos, max(E - 1, 0))
    if E == 0:
        edst, nid = np.full_like(pos, VB), np.zeros_like(pos)
    else:
        edst = np.where(valid, keys[safe] - c_win[:, None] * VB, VB)
        nid = np.where(valid, nids[safe], 0)
    return (c_win.astype(np.int32), edst.astype(np.int32),
            pos.astype(np.int32), nid.astype(np.int32))


def build_gat_plans(edge_src: np.ndarray, edge_dst: np.ndarray,
                    num_rows: int, table_rows: int) -> GatPlans:
    """Host-side schedule build.  ``edge_dst`` must be sorted ascending
    (CSR order); ``edge_src`` indexes the source feature table (table-local
    ids under a halo exchange)."""
    edge_src = np.asarray(edge_src, np.int64)
    edge_dst = np.asarray(edge_dst, np.int64)
    d = _aligned_position_plan(edge_dst, edge_src, num_rows)
    order = np.argsort(edge_src, kind="stable")
    s = _position_plan(edge_src[order], order, edge_dst, table_rows)
    return GatPlans(*(jnp.asarray(a) for a in d + s),
                    num_rows=num_rows, table_rows=table_rows)


# GatPlans rides jit argument pytrees: arrays are leaves, row counts static.
jax.tree_util.register_pytree_node(
    GatPlans,
    lambda p: (p[:8], (p.num_rows, p.table_rows)),
    lambda meta, arrs: GatPlans(*arrs, num_rows=meta[0], table_rows=meta[1]))


def _pad_posplan(obi, edst, pos, nid, pad: int):
    """No-op pad chunks for an edge-position plan, routed through
    segment_sum.pad_chunks (the single owner of the pad recipe) — pos and
    nid both take esrc's treatment (zeros; every slot masked via edst=VB)."""
    from roc_tpu.ops.pallas.segment_sum import pad_chunks
    first0 = jnp.zeros_like(obi)
    obi2, _, edst2, pos2 = pad_chunks(obi, first0, edst, pos, pad, jnp)
    *_, nid2 = pad_chunks(obi, first0, edst, nid, pad, jnp)
    return obi2, edst2, pos2, nid2


def pad_gat_plans(plans: "list[GatPlans]", min_d: int = 0,
                  min_s: int = 0) -> GatPlans:
    """Stack per-shard GatPlans to common chunk counts (shard_map needs one
    static program) — the attention analog of ops.aggregate.pad_plans."""

    def stack(prefix, floor):
        quads = [(getattr(p, prefix + "obi"), getattr(p, prefix + "edst"),
                  getattr(p, prefix + "pos"), getattr(p, prefix + "nid"))
                 for p in plans]
        C = max(max(q[0].shape[0] for q in quads), floor)
        out = [_pad_posplan(*q, C - q[0].shape[0]) for q in quads]
        return [jnp.stack([o[i] for o in out]) for i in range(4)]

    meta = {(p.num_rows, p.table_rows) for p in plans}
    assert len(meta) == 1, f"shards disagree on plan geometry: {meta}"
    d, s = stack("dst_", min_d), stack("src_", min_s)
    return GatPlans(*(d + s), num_rows=plans[0].num_rows,
                    table_rows=plans[0].table_rows)


def _pad_steps(obi, edst, pos, nid, cb):
    """Pad the chunk count to a multiple of ``cb`` with no-op chunks."""
    C = obi.shape[0]
    pad = -C % cb
    obi, edst, pos, nid = _pad_posplan(obi, edst, pos, nid, pad)
    return obi, edst, pos, nid, (C + pad) // cb


def _take_lanes(x, idx):
    """``x[:, idx]`` for a ``[K, M]`` array: the one gather of the [K, E]
    layout (every per-edge array of the plan path keeps edges on the lane
    axis; module comment above).

    The TPU compiler gathers rows: it reads ``x`` as [M, K] rows and writes
    an [n, K] row-major result, K padded to 128 lanes, before transposing
    it back (AOT-compiled step, PERF.md PR 25: one 11.2 GB temporary per
    edge-sized gather at the Reddit shape).  So a long gather walks the
    index list in chunks and lands each chunk's [K, chunk] in place: the
    padded temporary is bounded by the chunk (512 MB), the result is not
    padded at all."""
    n, c = idx.shape[0], _LANE_GATHER_CHUNK
    if n <= c:
        return jnp.take(x, idx, axis=1, mode="clip")
    from roc_tpu.ops.aggregate import _vary_like
    nchunks = -(-n // c)
    ids = jnp.pad(idx, (0, nchunks * c - n)).reshape(nchunks, c)

    def body(out, sl):
        i, chunk_ids = sl
        g = jnp.take(x, chunk_ids, axis=1, mode="clip")       # [K, c]
        return jax.lax.dynamic_update_slice(out, g, (0, i * c)), None

    out = _vary_like(jnp.zeros((x.shape[0], nchunks * c), x.dtype), x)
    out, _ = jax.lax.scan(body, out, (jnp.arange(nchunks), ids))
    return out[:, :n]


def _head_expand(heads: int, head_dim: int, dtype):
    """``[K, K*F]`` 0/1 matrix with X[k, k*F + f] = 1: ``w.T @ X`` repeats a
    per-head value over the head's F lanes and ``X @ p.T`` sums a head's F
    lanes, on the MXU, without a ``[n, K]`` array (K on the lane axis) ever
    existing.  At "highest" both are exact in float32: one factor is 0/1."""
    return jnp.repeat(jnp.eye(heads, dtype=dtype), head_dim, axis=1)


def plan_sum_step(width: int) -> int:
    """Chunks a step of a :func:`_plan_sum` that gathers node rows
    ``width`` wide (K*F): the largest power of two, at most
    ``_PLAN_CB_SUM``, whose gathered ``[cb * EB, lanes]`` float32 block,
    the width tiled to 128 lanes, fits ``_PLAN_SUM_BLOCK_BYTES``."""
    from roc_tpu.ops.pallas.segment_sum import EB
    lanes = -(-width // 128) * 128
    cb = _PLAN_CB_SUM
    while cb > 1 and cb * EB * lanes * 4 > _PLAN_SUM_BLOCK_BYTES:
        cb //= 2
    return cb


def short_plan_sums(widths) -> int:
    """How many of the row-gathering :func:`_plan_sum` scans over rows of
    these widths step shorter than ``_PLAN_CB_SUM``."""
    return sum(plan_sum_step(w) < _PLAN_CB_SUM for w in widths)


def _plan_scan_shapes(obi, num_rows: int, cb_max: int):
    from roc_tpu.ops.pallas.segment_sum import VB
    cb = min(cb_max, max(8, obi.shape[0]))
    num_windows = (num_rows + VB - 1) // VB
    return cb, num_windows - 1 + cb      # acc windows: DUS never clamps


def _slot_reader(edge_w, cb: int, aligned: bool):
    """``read(po)``: the per-slot values ``edge_w[:, po]`` of one scan step
    as ``[cb, K, EB]``, for ``po`` [cb, EB] slot positions.

    ``aligned`` (every dst-keyed plan, _aligned_position_plan): a chunk's
    slots are one aligned block of EB positions, so the per-edge array is
    re-laid once a pass as [blocks, K, EB] and a step gathers cb whole
    blocks, (8, 128) tiles, instead of cb x EB columns (a column gather
    costs 15 to 20 ns an index on a v5e, 526 ms a pass over the Reddit
    plan; PERF.md PR 25).  Masked slots read their block's other values,
    always finite and always dropped (edst == VB matches no row).
    Otherwise (the src-keyed plans, positions in src order) it is the
    column gather; one head reads a flat 1-D copy, made once outside the
    scan (194 ms against 318 + a per-step squeeze XLA does not hoist)."""
    from roc_tpu.ops.pallas.segment_sum import EB
    K, E = edge_w.shape
    if aligned:
        nb = -(-E // EB)
        blocks = jnp.pad(edge_w, ((0, 0), (0, nb * EB - E))).reshape(
            K, nb, EB).transpose(1, 0, 2)                 # [blocks, K, EB]
        return lambda po: jnp.take(blocks, po[:, 0] // EB, axis=0,
                                   mode="clip")
    if K == 1:
        flat = edge_w.reshape(-1)
        return lambda po: jnp.take(flat, po.reshape(cb * EB),
                                   mode="clip").reshape(cb, 1, EB)
    return lambda po: _take_lanes(edge_w, po.reshape(cb * EB)).reshape(
        K, cb, EB).transpose(1, 0, 2)


def _over_head_lanes(slots, expand, heads_axis: int, dtype):
    """Per-slot, per-head values spread over each head's F lanes, as the
    ``[cb * EB, K * F]`` factor of a step's gathered rows: ``slots`` is
    ``[cb, K, EB]`` (``heads_axis`` 1) or ``[K, cb, EB]`` (0), ``expand``
    :func:`_head_expand`'s matrix.  Exact at "highest": one factor is 0/1."""
    return jax.lax.dot_general(                   # [cb, EB, K*F]
        slots, expand, (((heads_axis,), (0,)), ((), ())),
        precision="highest", preferred_element_type=jnp.float32
    ).astype(dtype).reshape(-1, expand.shape[1])


def _add_window_rows(acc, g, ed, ob, precision):
    """One step's ``[cb * EB, H]`` slot rows summed by window row
    (ops.aggregate._one_hot_dots) onto the row accumulator ``[.., H]``."""
    from roc_tpu.ops.aggregate import _one_hot_dots
    from roc_tpu.ops.pallas.segment_sum import VB
    cb = ob.shape[0]
    # one rounding only under `fast`: the products e * h, once, at the
    # S1 dot; the S2 dot adds float32 partial sums and stays exact
    # (0.3 % of the pass's MXU work at six passes)
    outs = _one_hot_dots(g, ed, ob, cb, precision, "highest")
    base = ob[0] * VB
    cur = jax.lax.dynamic_slice(acc, (base, 0), outs.shape)
    return jax.lax.dynamic_update_slice(acc, cur + outs, (base, 0))


def _plan_sum(edge_w, node_x, obi, edst, pos, nid, num_rows: int, precision,
              aligned: bool = False, ride=None):
    """Segment-sum over plan windows of per-slot values
    ``edge_w[:, pos] (⊗) node_x[nid]`` — the one-hot MXU machinery of
    ops.aggregate._matmul_run generalized to edge-position plans.

      edge_w: [K, E] or None;  node_x: [R2, K, F] or None (not both None).
      ``aligned``: the plan is dst-keyed (:func:`_slot_reader`).
      ``ride``: further [K', E] weights (with both operands above) that
      ride the SAME scan: ``edge_w`` and ``ride`` are read stacked, one
      column gather of a [K + K', E] array by one index list, and ``ride``
      is summed plainly, as a call without ``node_x`` sums.  On a v5e over
      the Reddit src plan (26.8 M slots; PERF.md PR 34) an index of that
      gather costs 7.3 / 10.9 / 11.5 / 13.7 / 21.9 ns at 1 / 2 / 4 / 8 / 16
      rows, so one stacked read beats two at both head counts the GAT cell
      has (K = 8: 589 ms against 2 x 367; K = 1: the [2, E] lane gather 293
      against two flat reads of 195; the whole pair 1,010 -> 863 and 715 ->
      561 ms); the stack's MEMORY decides who rides (:func:`gat_src_scans`).
    Returns [K, num_rows] (node_x None: always summed at "highest") or
    [num_rows, K, F] (``precision`` feeds the one-hot dots); with ``ride``
    both, the pair ([num_rows, K, F], [K', num_rows]).
    """
    from roc_tpu.ops.aggregate import _vary_like
    from roc_tpu.ops.pallas.segment_sum import EB, VB
    cb, acc_windows = _plan_scan_shapes(
        obi, num_rows, _PLAN_CB_SUM if node_x is None
        else plan_sum_step(node_x.shape[1] * node_x.shape[2]))
    obi, edst, pos, nid, nsteps = _pad_steps(obi, edst, pos, nid, cb)
    K = edge_w.shape[0] if edge_w is not None else node_x.shape[1]
    ref = edge_w if edge_w is not None else node_x
    read = None if edge_w is None else _slot_reader(
        edge_w if ride is None else jnp.concatenate([edge_w, ride], axis=0),
        cb, aligned)
    xs = (obi.reshape(nsteps, cb), edst.reshape(nsteps, cb, EB),
          pos.reshape(nsteps, cb, EB), nid.reshape(nsteps, cb, EB))

    def add_plain(acc, g, ob, ed):
        # [cb, K', EB] slot values onto the window-indexed [W, K' * VB]
        # sums: the S1 dot contracts the slot axis directly, so K' never
        # reaches the lane axis
        s1 = (jax.lax.broadcasted_iota(jnp.int32, (cb, VB, EB), 1)
              == ed[:, None, :]).astype(g.dtype)
        psum = jax.lax.dot_general(          # [cb, K', VB]
            g, s1, (((2,), (2,)), ((0,), (0,))), precision="highest",
            preferred_element_type=jnp.float32)
        s2 = (jax.lax.broadcasted_iota(jnp.int32, (cb, cb), 0)
              == (ob - ob[0])[None, :]).astype(g.dtype)
        outs = jax.lax.dot_general(
            s2, psum.reshape(cb, g.shape[1] * VB), (((1,), (0,)), ((), ())),
            precision="highest", preferred_element_type=jnp.float32)
        cur = jax.lax.dynamic_slice(acc, (ob[0], 0), outs.shape)
        return jax.lax.dynamic_update_slice(acc, cur + outs, (ob[0], 0))

    def plain_result(acc, heads):
        out = acc.reshape(acc_windows, heads, VB).transpose(1, 0, 2)
        return out.reshape(heads, acc_windows * VB)[:, :num_rows].astype(
            ref.dtype)

    if node_x is None:
        # [K, E] in, [K, rows] out
        def body_k(acc, sl):
            ob, ed, po, _ = sl
            return add_plain(acc, read(po), ob, ed), None     # [cb, K, EB]

        acc = _vary_like(jnp.zeros((acc_windows, K * VB), jnp.float32), ref)
        acc, _ = jax.lax.scan(body_k, acc, xs)
        return plain_result(acc, K)

    F = node_x.shape[2]
    H = K * F
    flat = node_x.reshape(node_x.shape[0], H)
    expand = _head_expand(K, F, jnp.float32) if edge_w is not None else None

    def body(carry, sl):
        acc, acc_k = carry                    # acc_k: None without ``ride``
        ob, ed, po, ni = sl
        g = jnp.take(flat, ni.reshape(cb * EB), axis=0, mode="clip")
        if edge_w is not None:
            slots = read(po)                  # [cb, K (+ K'), EB]
            g = g * _over_head_lanes(slots[:, :K], expand, 1, g.dtype)
        acc = _add_window_rows(acc, g, ed, ob, precision)
        if ride is not None:
            acc_k = add_plain(acc_k, slots[:, K:], ob, ed)
        return (acc, acc_k), None

    acc = _vary_like(jnp.zeros((acc_windows * VB, H), jnp.float32), ref)
    acc_k = None if ride is None else _vary_like(
        jnp.zeros((acc_windows, ride.shape[0] * VB), jnp.float32), ref)
    (acc, acc_k), _ = jax.lax.scan(body, (acc, acc_k), xs)
    rows = acc[:num_rows].astype(ref.dtype).reshape(num_rows, K, F)
    return rows if ride is None else (rows, plain_result(acc_k, ride.shape[0]))


def _plan_max(edge_w, obi, edst, pos, num_rows: int):
    """Segment-max over the windows of a dst-keyed plan of ``edge_w[:, pos]``
    ([K, E] -> [K, num_rows]).  Same window schedule as _plan_sum with
    masked maxima in place of the one-hot dots; rows with no live slots
    return -inf."""
    from roc_tpu.ops.aggregate import _vary_like
    from roc_tpu.ops.pallas.segment_sum import EB, VB
    cb, acc_windows = _plan_scan_shapes(obi, num_rows, _PLAN_CB_MAX)
    obi, edst, pos, _, nsteps = _pad_steps(obi, edst, pos, pos, cb)
    K = edge_w.shape[0]
    neg = jnp.asarray(-jnp.inf, edge_w.dtype)
    read = _slot_reader(edge_w, cb, True)

    def body(acc, sl):
        ob, ed, po = sl
        s = read(po)                                      # [cb, K, EB]
        in_row = (jax.lax.broadcasted_iota(jnp.int32, (cb, VB, EB), 1)
                  == ed[:, None, :])
        within = jnp.max(jnp.where(in_row[:, None], s[:, :, None, :], neg),
                         axis=3)                          # [cb, K, VB]
        lw = ob - ob[0]
        same_w = (jax.lax.broadcasted_iota(jnp.int32, (cb, cb), 0)
                  == lw[None, :])                         # [w, chunk]
        outs = jnp.max(jnp.where(same_w[:, :, None, None], within[None],
                                 neg), axis=1)            # [cb, K, VB]
        # acc is WINDOW-indexed ([W, K, VB]) — base is the window id itself,
        # unlike the row-indexed feature accumulator of _plan_sum
        cur = jax.lax.dynamic_slice(acc, (ob[0], 0, 0), (cb, K, VB))
        return jax.lax.dynamic_update_slice(
            acc, jnp.maximum(cur, outs), (ob[0], 0, 0)), None

    acc = _vary_like(jnp.full((acc_windows, K, VB), neg), edge_w)
    acc, _ = jax.lax.scan(
        body, acc, (obi.reshape(nsteps, cb), edst.reshape(nsteps, cb, EB),
                    pos.reshape(nsteps, cb, EB)))
    return acc.transpose(1, 0, 2).reshape(K, acc_windows * VB)[:, :num_rows]


def _block_steps(edst, pos, nsteps: int, cb: int, num_edges: int):
    """Where each step of ``cb`` chunks of the ALIGNED dst-keyed plan lands
    in a ``[K, E]`` array: (blocks of EB positions in all, each step's
    first LIVE block ``[nsteps]``, its chunks' block offsets from it
    ``[nsteps, cb]``).  Plan-sized, once a pass.  The live chunks of a
    step cover a contiguous run of blocks (consecutive pieces step the
    block by 0 or 1); the all-masked chunks of empty windows and the pad
    chunks carry block 0, add zeros wherever they land, and must not set
    the base."""
    from roc_tpu.ops.pallas.segment_sum import EB, VB
    nb = max(-(-num_edges // EB), 1)
    blk = (pos[:, 0] // EB).reshape(nsteps, cb)
    live = (jnp.min(edst, axis=1) < VB).reshape(nsteps, cb)
    base = jnp.minimum(jnp.min(jnp.where(live, blk, nb), axis=1), nb - 1)
    return nb, base, blk - base[:, None]


def _blocks_carry(heads: int, nb: int, cb: int, num_edges: int, ref,
                  init=None):
    """The ``[K, (nb - 1 + cb) * EB]`` array :func:`_land_blocks` adds
    into (a step's update never clamps): zeros, or ``init`` ([K, E])."""
    from roc_tpu.ops.aggregate import _vary_like
    from roc_tpu.ops.pallas.segment_sum import EB
    width = (nb - 1 + cb) * EB
    if init is None:
        return _vary_like(jnp.zeros((heads, width), ref.dtype), ref)
    return jnp.pad(init, ((0, 0), (0, width - num_edges)))


def _sum_blocks(vals, of):
    """One step's ``[K, cb, EB]`` float32 slot values, EXACT ZEROS on
    masked slots, summed block by block as ``[K, cb * EB]`` (a one-hot dot:
    one addend is the value, the others are zeros, the sum is exact);
    ``of``: the chunks' block offsets from the step's base block
    (:func:`_block_steps`)."""
    from roc_tpu.ops.pallas.segment_sum import EB
    heads, cb = vals.shape[:2]
    same_b = (jax.lax.broadcasted_iota(jnp.int32, (heads, cb, cb), 1)
              == of[None, None, :]).astype(vals.dtype)    # [K, block, chunk]
    return jax.lax.dot_general(                       # [K, block, EB]
        same_b, vals, (((2,), (1,)), ((0,), (0,))), precision="highest",
        preferred_element_type=jnp.float32).reshape(heads, cb * EB)


def _land_blocks(out, vals, b0, of):
    """:func:`_sum_blocks` of one step added into its aligned lane range of
    ``out``, from the step's base block ``b0``."""
    from roc_tpu.ops.pallas.segment_sum import EB
    outs = _sum_blocks(vals, of).astype(out.dtype)
    cur = jax.lax.dynamic_slice(out, (0, b0 * EB), outs.shape)
    return jax.lax.dynamic_update_slice(out, cur + outs, (0, b0 * EB))


def _plan_blocks(form, heads: int, obi, edst, pos, nid, num_edges: int, ref,
                 init=None):
    """``[K, E]`` in edge order from per-slot values formed chunk by chunk
    over the ALIGNED dst-keyed plan: the write side of :func:`_slot_reader`.

    ``form(ob, ed, ni)`` gives one scan step's ``[K, cb, EB]`` float32
    slot values, EXACT ZEROS on masked slots (``edst == VB``).  A chunk is
    one piece of one aligned block of EB positions; a window boundary
    inside a block makes several chunks of it, and every position is live
    in exactly one.  So a step sums its chunks block by block and adds the
    result into one aligned lane range of the output
    (:func:`_block_steps`, :func:`_land_blocks`).
    ``init`` ([K, E]) is what the values are added onto (default zeros).
    Nothing edge-sized exists besides the ``[K, E]`` result itself."""
    from roc_tpu.ops.pallas.segment_sum import EB
    cb = min(_PLAN_CB_BLOCKS, max(8, obi.shape[0]))
    obi, edst, pos, nid, nsteps = _pad_steps(obi, edst, pos, nid, cb)
    nb, base, off = _block_steps(edst, pos, nsteps, cb, num_edges)

    def body(out, sl):
        ob, ed, ni, b0, of = sl
        return _land_blocks(out, form(ob, ed, ni), b0, of), None

    out, _ = jax.lax.scan(
        body, _blocks_carry(heads, nb, cb, num_edges, ref, init),
        (obi.reshape(nsteps, cb), edst.reshape(nsteps, cb, EB),
         nid.reshape(nsteps, cb, EB), base, off))
    return out[:, :num_edges]


def _window_rows(x):
    """``[rows, width]`` node rows as ``[windows, VB * width]``: a window's
    VB rows side by side, the last window zero-filled.  A step reads its
    chunks' windows as whole rows of this (``jnp.take`` by ``obi``: as many
    rows as the plan has chunks, E / EB + N / VB a pass)."""
    from roc_tpu.ops.pallas.segment_sum import VB
    rows, width = x.shape
    W = max(-(-rows // VB), 1)
    return jnp.pad(x, ((0, W * VB - rows), (0, 0))).reshape(W, VB * width)


def _window_lanes(node_w, ob, ed, width: int):
    """A step's slots' own rows of a node table, ``[cb, width, EB]`` (slots
    on the lane axis): ``node_w`` is :func:`_window_rows` of the
    ``[rows, width]`` table, and a chunk's window's VB rows are spread over
    its slots by a one-hot product, exact at "highest"; masked slots
    (``edst == VB`` matches no row) read zeros."""
    from roc_tpu.ops.pallas.segment_sum import EB, VB
    cb = ob.shape[0]
    mine = jnp.take(node_w, ob, axis=0, mode="clip").reshape(cb, VB, width)
    s1 = (jax.lax.broadcasted_iota(jnp.int32, (cb, VB, EB), 1)
          == ed[:, None, :]).astype(mine.dtype)
    return jax.lax.dot_general(                       # [cb, width, EB]
        mine, s1, (((1,), (1,)), ((0,), (0,))), precision="highest",
        preferred_element_type=jnp.float32)


def _window_slot_rows(node_w, ob, ed, width: int):
    """:func:`_window_lanes` with the rows' width on the lane axis:
    ``[cb, EB, width]``, what multiplies a step's gathered rows."""
    from roc_tpu.ops.pallas.segment_sum import EB, VB
    cb = ob.shape[0]
    mine = jnp.take(node_w, ob, axis=0, mode="clip").reshape(cb, VB, width)
    s1 = (jax.lax.broadcasted_iota(jnp.int32, (cb, EB, VB), 2)
          == ed[:, :, None]).astype(mine.dtype)
    return jax.lax.dot_general(                       # [cb, EB, width]
        s1, mine, (((2,), (1,)), ((0,), (0,))), precision="highest",
        preferred_element_type=jnp.float32)


def _contract_heads(a, b, collapse):
    """``[K, slots]``: per head the sum over its F lanes of ``a * b``, both
    ``[slots, K * F]``; ``collapse`` is :func:`_head_expand`'s matrix."""
    return jax.lax.dot_general(
        collapse, a * b, (((1,), (1,)), ((), ())), precision="highest",
        preferred_element_type=jnp.float32)


def _plan_broadcast(node_t, obi, edst, pos, num_edges: int, init=None):
    """``node_t[:, edge_dst]`` for a ``[K, rows]`` node table, as ``[K, E]``,
    WITHOUT a gather by edge: ``edge_dst`` is sorted, so the read is a
    segment broadcast, and the aligned dst-keyed plan already holds it:
    slot j of chunk c wants row ``VB * obi[c] + edst[c, j]``, a ``[K, VB] x
    [VB, EB]`` one-hot product over the chunk's window (the transpose of
    _plan_sum's ``body_k``).  Bit for bit the gather's result (a -0.0
    reads +0.0); ``node_t`` must be finite.  With ``init`` ([K, E]) the
    result is ``init + node_t[:, edge_dst]``, one addition a slot, and the
    scan follows ``init`` in the program's order.  A lane gather by the
    same index costs 15.7 ns an index at K = 8 on a v5e (PERF.md PR 25),
    this 0.9 (PERF.md PR 28)."""
    K = node_t.shape[0]
    # node-sized, once a pass: window w's [VB, K] values as one row
    node_w = _window_rows(node_t.T)

    def form(ob, ed, _):
        return _window_lanes(node_w, ob, ed, K).transpose(1, 0, 2)

    return _plan_blocks(form, K, obi, edst, pos, pos, num_edges, node_t, init)


def _edge_contract(du, table, obi, edst, pos, nid, num_edges: int):
    """c[k, e] = Σ_f du[dst_e, k, f]·table[src_e, k, f] as ``[K, E]``, walked
    over the aligned dst-keyed plan so the [E, K, F] product never
    materializes and ``du[dst_e]`` is no gather by edge: a chunk's ``du``
    rows are its window's VB rows spread over the slots by a one-hot
    product (exact, as in :func:`_plan_broadcast`); only ``table[src_e]``
    is gathered, by the plan's ``nid`` (1 + 32 N / E slots an edge).
    ``du`` holds the dst windows' rows ([rows, K, F], rows from the plan's
    row 0)."""
    from roc_tpu.ops.pallas.segment_sum import EB
    rows, K, F = du.shape
    H = K * F
    du_w = _window_rows(du.reshape(rows, H))
    tf = table.reshape(table.shape[0], H)
    collapse = _head_expand(K, F, jnp.float32)

    def form(ob, ed, ni):
        cb = ob.shape[0]
        du_e = _window_slot_rows(du_w, ob, ed, H)
        return _contract_heads(
            du_e.reshape(cb * EB, H),
            jnp.take(tf, ni.reshape(cb * EB), axis=0, mode="clip"),
            collapse).reshape(K, cb, EB)

    return _plan_blocks(form, K, obi, edst, pos, nid, num_edges, du)


def _contract_then_sum(du, dz, k, v, e, ew, obi, edst, pos, nid,
                       num_edges: int):
    """The dst side of a dot-product score's backward in ONE scan over the
    aligned dst-keyed plan, where :func:`_edge_contract`,
    :func:`_plan_broadcast` and :func:`_plan_sum` walked it three times and
    gathered by its ``nid`` twice:

      de[k, e] = Σ_f du[dst_e, k, f]·v[src_e, k, f]       (never edge-sized)
      ds[k, e] = (ew[k, e]·de + e[k, e]·dz[k, dst_e]) / sqrt(F)    [K, E]
      dq[i]    = Σ_{e: dst_e = i} ds[:, e] (x) k[src_e]       [rows, K, F]

    Every term of ``ds`` at a slot is that slot's own (no reduction over a
    row's edges stands between the contraction and the sum, as the softmax
    does between the forward's pair), so a step gathers ONE row list, the
    side-by-side ``[k | v][nid]``, forms ``de`` from the ``v`` half, ``ds``
    in place and its addend to ``dq`` from the ``k`` half
    (:func:`_add_window_rows`).  A gathered row costs its index, not its
    bytes (PERF.md PR 34), so one doubled row beats two.  Float32 at
    "highest" throughout.  Steps of ``_PLAN_CB_BLOCKS`` chunks, not the
    sums' cap of 512: the gathered ``[cb * EB, 2 K F]`` block then lies in
    VMEM (v5e, the Reddit dst plan, K = 4: 11.1 ns a 1,024 B row against
    14.0 into HBM, and the step's products stay there too: 482 ms a pass
    against 626 at F = 32, 633 against 918 at F = 41; PERF.md PR 36), as
    :func:`plan_sum_step` keeps the plain sums' wide blocks (PR 38).

    The scan's carry is the ``[2K, E]`` stack the src-keyed scan reads
    next (``_tconv_plan_bwd``): ``[e ; ew]`` going in, ``[ds ; ew]`` coming
    out.  A step's live slots are one run of positions inside one aligned
    lane range (:func:`_block_steps`), so it slices that range, takes each
    chunk's block of ``e`` and ``ew`` out of it, and writes ``ds`` over
    ``e`` there: no edge-sized array exists beside the stack, where blocks
    of ``e`` made for this scan would be the forward's own (one expression)
    and live from there to here, a layer's worth each (the compiler's
    memory analysis for a v5e: + 0.8 GB; PERF.md PR 36).  Masked slots read
    finite values and zeros of ``du`` and ``dz``: exact zeros.
    ``du``: [rows, K, F]; ``dz``: [K, rows]; ``k``, ``v``: [T, K, F];
    ``e``, ``ew``: [K, E] (``ew`` is ``e`` without dropout).
    Returns ([ds ; ew], dq)."""
    def tables(rows, K, F):
        H = K * F
        kv = jnp.concatenate([k.reshape(-1, H), v.reshape(-1, H)], axis=1)
        expand = _head_expand(K, F, jnp.float32)

        def addend(g, ds, ob, ed, extra):
            return g[:, :H] * _over_head_lanes(ds, expand, 0, g.dtype), extra

        return kv, expand, lambda g: g[:, H:], addend, None

    sw, dq, _ = _land_ds_then_sum(du, dz, e, ew, obi, edst, pos, nid,
                                  num_edges, 1.0 / np.sqrt(du.shape[2]),
                                  tables)
    return sw, dq


def _land_ds_then_sum(du, dz, e, ew, obi, edst, pos, nid, num_edges: int,
                      scale, tables):
    """The scan both pair scores' backward makes over the aligned dst-keyed
    plan (:func:`_contract_then_sum`, :func:`_dynamic_then_sum`).
    ``tables(rows, K, F)`` gives the score's own part, made once a pass:
    (the node table a step gathers by ``nid``, :func:`_head_expand`'s
    matrix, ``de_rows(g)``: the gathered rows ``du`` is contracted with for
    ``de``, ``addend(g, ds, ob, ed, extra) -> (rows [slots, K F], extra)``:
    what the step sums by window row, and ``extra0()``: the initial value
    of a carry of the score's own, or None).  A step gathers the table
    once, forms ``ds = (ew de + e dz[dst]) * scale`` (``scale`` None: no
    factor), writes it over ``e`` in the ``[2K, E]`` carry and sums the
    addend.  Returns ([ds ; ew], the row sums [rows, K, F], extra)."""
    from roc_tpu.ops.aggregate import _vary_like
    from roc_tpu.ops.pallas.segment_sum import EB, VB
    rows, K, F = du.shape
    H = K * F
    cb, acc_windows = _plan_scan_shapes(obi, rows, _PLAN_CB_BLOCKS)
    obi, edst, pos, nid, nsteps = _pad_steps(obi, edst, pos, nid, cb)
    nb, base, off = _block_steps(edst, pos, nsteps, cb, num_edges)
    # plan-sized, once a pass: the run of positions live in each step
    live = (edst < VB).reshape(nsteps, cb * EB)
    at = pos.reshape(nsteps, cb * EB)
    lo = jnp.min(jnp.where(live, at, num_edges), axis=1)
    hi = jnp.max(jnp.where(live, at + 1, 0), axis=1)
    # node-sized, once a pass
    du_w = _window_rows(du.reshape(rows, H))
    dz_w = _window_rows(dz.T)
    table, expand, de_rows, addend, extra0 = tables(rows, K, F)

    def body(carry, sl):
        sw, acc, extra = carry
        ob, ed, ni, b0, of, p0, p1 = sl
        g = jnp.take(table, ni.reshape(cb * EB), axis=0, mode="clip")
        de = _contract_heads(
            _window_slot_rows(du_w, ob, ed, H).reshape(cb * EB, H),
            de_rows(g), expand).reshape(K, cb, EB)
        cur = jax.lax.dynamic_slice(sw, (0, b0 * EB), (2 * K, cb * EB))
        # a chunk's block of [e ; ew]; a pad chunk's offset is anything
        slots = jnp.take(cur.reshape(2 * K, cb, EB), of, axis=1,
                         mode="clip")                     # [2K, chunk, EB]
        dz_e = _window_lanes(dz_w, ob, ed, K).transpose(1, 0, 2)
        ds = slots[K:] * de + slots[:K] * dz_e            # [K, chunk, EB]
        if scale is not None:
            ds = ds * scale
        lane = b0 * EB + jax.lax.broadcasted_iota(jnp.int32, (1, cb * EB), 1)
        top = jnp.where((lane >= p0) & (lane < p1), _sum_blocks(ds, of),
                        cur[:K])
        sw = jax.lax.dynamic_update_slice(
            sw, jnp.concatenate([top, cur[K:]], axis=0), (0, b0 * EB))
        add, extra = addend(g, ds, ob, ed, extra)
        acc = _add_window_rows(acc, add, ed, ob, "highest")
        return (sw, acc, extra), None

    sw = _blocks_carry(2 * K, nb, cb, num_edges, e,
                       jnp.concatenate([e, ew], axis=0))
    acc = _vary_like(jnp.zeros((acc_windows * VB, H), jnp.float32), e)
    extra = None if extra0 is None else extra0()
    (sw, acc, extra), _ = jax.lax.scan(
        body, (sw, acc, extra),
        (obi.reshape(nsteps, cb), edst.reshape(nsteps, cb, EB),
         nid.reshape(nsteps, cb, EB), base, off, lo, hi))
    return (sw[:, :num_edges],
            acc[:rows].astype(e.dtype).reshape(rows, K, F), extra)


def _score_then_sum(x, w, obi, edst, pos, nid, num_edges: int, tables,
                    precision):
    """The forward of an attention score in ONE scan over the aligned
    dst-keyed plan, one gather by its ``nid`` a step, where a score scan
    (or a lane gather by ``edge_src``), the max, the normaliser and the
    weighted sum walked it four times and gathered by ``nid`` twice:

      s[k, e] = score(x[dst_e], t[src_e])                          [K, E]
      m[k, i] = max_{e: dst_e = i} s[k, e]
      z[k, i] = Σ_{e: dst_e = i} exp(s[k, e] - m[k, i])
      u[i]    = Σ_{e: dst_e = i} exp(s - m)[:, e]·w[:, e] (x) val(t[src_e])

    The max and the normaliser are reductions over a row's edges that stand
    between the score and the sum, so the scan carries them online: a
    running max ``m`` of the step's window rows, and the rows' ``z`` and
    ``u`` scaled by ``exp(m_old - m_new)`` whenever a step raises it (a row
    with no slot yet reads ``-inf`` and its factor is 0, never ``exp(-inf +
    inf)``).  A step lands ``s`` into the ``[K, E]`` carry as
    :func:`_plan_blocks` does, takes its masked max by window row as
    :func:`_plan_max`'s body does, forms ``e = exp(s - m[dst])`` at its
    slots and adds ``e`` to ``z`` and ``e w`` times the value rows to ``u``
    (one-hot dots: ``z`` at "highest", ``u``'s S1 dot at ``precision``,
    which rounds each product once at the MXU's default, its S2 dot at
    "highest").  Steps of ``_PLAN_CB_BLOCKS`` chunks, as
    :func:`_land_ds_then_sum`: the gathered block then lies in VMEM
    (:func:`_contract_then_sum` has the numbers).

    ``x``: [rows, K, F], the destination rows: their count, heads and
    width are ``u``'s.  ``w``: [K, E] multiplier of the sum's weights (the
    dropout mask) or None.  ``tables(rows, K, F)`` gives the score's own
    part, made once a pass (:func:`_dot_tables`, :func:`_additive_tables`):
    (the node table a step gathers by ``nid``, ``near(ob, ed)``: the step's
    slots' own term of their destination rows, spread from the windows by
    a one-hot product (exact), ``score(g, d)``: the ``[K, slots]`` scores
    of the gathered rows against it, ``values(g)``: the ``[slots, K F]``
    rows ``u`` sums).  Masked slots score an exact zero and add nothing.
    Returns (s [K, E], m [K, rows]: -inf on a row with no in-edge, z [K,
    rows], u [rows, K, F]); such a row sums zeros."""
    from roc_tpu.ops.aggregate import _one_hot_dots, _vary_like
    from roc_tpu.ops.pallas.segment_sum import EB, VB
    rows, K, F = x.shape
    H = K * F
    cb, acc_windows = _plan_scan_shapes(obi, rows, _PLAN_CB_BLOCKS)
    obi, edst, pos, nid, nsteps = _pad_steps(obi, edst, pos, nid, cb)
    nb, base, off = _block_steps(edst, pos, nsteps, cb, num_edges)
    # node-sized, once a pass
    table, near, score, values = tables(rows, K, F)
    expand = _head_expand(K, F, jnp.float32)
    read_w = None if w is None else _slot_reader(w, cb, True)
    neg = jnp.asarray(-jnp.inf, jnp.float32)

    def body(carry, sl):
        s_out, m, z, u = carry
        ob, ed, po, ni, b0, of = sl
        g = jnp.take(table, ni.reshape(cb * EB), axis=0, mode="clip")
        d = near(ob, ed)
        live = (ed < VB)[:, None, :]                          # [chunk, 1, EB]
        s = jnp.where(live.transpose(1, 0, 2),
                      score(g, d).reshape(K, cb, EB), 0.0)    # [K, chunk, EB]
        s_out = _land_blocks(s_out, s, b0, of)
        s = s.transpose(1, 0, 2)                              # [chunk, K, EB]
        # the step's max by window row (_plan_max's body), onto the rows'
        in_row = (jax.lax.broadcasted_iota(jnp.int32, (cb, VB, EB), 1)
                  == ed[:, None, :])                          # [chunk, VB, EB]
        within = jnp.max(jnp.where(in_row[:, None], s[:, :, None, :], neg),
                         axis=3)                              # [chunk, K, VB]
        lw = ob - ob[0]
        same_w = (jax.lax.broadcasted_iota(jnp.int32, (cb, cb), 0)
                  == lw[None, :])                             # [w, chunk]
        m_old = jax.lax.dynamic_slice(m, (ob[0], 0, 0), (cb, K, VB))
        m_new = jnp.maximum(m_old, jnp.max(jnp.where(
            same_w[:, :, None, None], within[None], neg), axis=1))
        m = jax.lax.dynamic_update_slice(m, m_new, (ob[0], 0, 0))
        scale = jnp.exp(jnp.where(m_old > neg, m_old - m_new, neg))
        # each slot's row max: a live slot's row has one, finite
        s1 = in_row.astype(jnp.float32)
        m_e = jax.lax.dot_general(                            # [chunk, K, EB]
            jnp.take(jnp.where(m_new > neg, m_new, 0.0), lw, axis=0), s1,
            (((2,), (1,)), ((0,), (0,))), precision="highest",
            preferred_element_type=jnp.float32)
        e = jnp.where(live, jnp.exp(s - m_e), 0.0)            # [chunk, K, EB]
        # z: the plain one-hot sum of e by window row (_plan_sum's add_plain)
        psum = jax.lax.dot_general(                           # [chunk, K, VB]
            e, s1, (((2,), (2,)), ((0,), (0,))), precision="highest",
            preferred_element_type=jnp.float32)
        outs = jax.lax.dot_general(
            same_w.astype(jnp.float32), psum.reshape(cb, K * VB),
            (((1,), (0,)), ((), ())), precision="highest",
            preferred_element_type=jnp.float32)               # [w, K VB]
        cur = jax.lax.dynamic_slice(z, (ob[0], 0), outs.shape)
        z = jax.lax.dynamic_update_slice(
            z, cur * scale.reshape(cb, K * VB) + outs, (ob[0], 0))
        # u: e w times the value rows, summed by window row
        ew = e if read_w is None else e * read_w(po)
        outs = _one_hot_dots(
            values(g) * _over_head_lanes(ew, expand, 1, g.dtype), ed, ob, cb,
            precision, "highest")                             # [w VB, K F]
        cur = jax.lax.dynamic_slice(u, (ob[0] * VB, 0), outs.shape)
        u = jax.lax.dynamic_update_slice(
            u, cur * _over_head_lanes(scale, expand, 1, u.dtype) + outs,
            (ob[0] * VB, 0))
        return (s_out, m, z, u), None

    carry = (_blocks_carry(K, nb, cb, num_edges, x),
             _vary_like(jnp.full((acc_windows, K, VB), neg), x),
             _vary_like(jnp.zeros((acc_windows, K * VB), jnp.float32), x),
             _vary_like(jnp.zeros((acc_windows * VB, H), jnp.float32), x))
    (s, m, z, u), _ = jax.lax.scan(
        body, carry,
        (obi.reshape(nsteps, cb), edst.reshape(nsteps, cb, EB),
         pos.reshape(nsteps, cb, EB), nid.reshape(nsteps, cb, EB), base, off))

    def by_row(acc):            # window-indexed [W, K, VB] -> [K, rows]
        return acc.reshape(acc_windows, K, VB).transpose(1, 0, 2).reshape(
            K, acc_windows * VB)[:, :rows].astype(x.dtype)

    return (s[:, :num_edges], by_row(m), by_row(z),
            u[:rows].astype(x.dtype).reshape(rows, K, F))


def gat_attend_plan(h, table, a_src, a_dst, plans: GatPlans, edge_ids,
                    slope: float, precision: str = "highest", drop=None):
    """GAT attention over chunk plans — scatter-free fwd AND bwd.

    Same semantics as :func:`gat_attend` (equal up to float reassociation:
    different summation order), ``drop`` = (key, rate) included: the same
    key drops the same coefficients.  ``edge_ids`` = (edge_src, edge_dst)
    [E] arrays in dst-sorted order (table-local src ids under halo).  The
    backward is hand-derived so no gather is ever transposed into a TPU
    scatter; all reductions ride the dst-/src-keyed plans.

    The forward is ONE scan over the dst-keyed plan, one gather of
    ``[table | as_src]`` rows by its ``nid`` a step (:func:`_score_then_sum`
    with :func:`_additive_tables`: the score, its max, the normaliser and
    u, the softmax carried online), then :func:`_plan_broadcast` of the
    max for the backward's ``e``.  Of the three score shapes over these
    plans (additive here, dot-product in :func:`tconv_attend_plan`, dynamic
    in :func:`gatv2_attend_plan`) the first two make their forward so.

    ``precision`` feeds ONLY the two [*, K, F] weighted feature sums (u's
    S1 dot in the forward's scan, dtable bwd) — the FLOP carriers;
    "default" is the fast policy's single-pass bf16 (one rounding of each
    product).  The [K, E] score/normalizer sums stay at "highest" always:
    their FLOPs are negligible and the softmax normalization stays exact
    in both modes.
    """
    key, rate = _drop_args(drop)
    return _gat_plan(h, table, a_src, a_dst, plans, edge_ids, key, slope,
                     precision, rate)


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _gat_plan(h, table, a_src, a_dst, plans, edge_ids, key, slope,
              precision, rate):
    return _gat_plan_out(h, table, a_src, a_dst, plans, edge_ids, key,
                         slope, precision, rate)[0]


def _gat_plan_out(h, table, a_src, a_dst, plans, edge_ids, key, slope,
                  precision, rate):
    """The forward's one scan and the division: (out, s, m, zc), all an
    evaluation pass makes (it needs no e, so no broadcast of the max)."""
    E, K = edge_ids[0].shape[0], h.shape[1]
    dst = (plans.dst_obi, plans.dst_edst, plans.dst_pos, plans.dst_nid)
    # the device scopes (obs/scopes.py): the pass, then one part a scan or
    # kernel; every line of the rule sits in one
    with scopes.scope("fwd"):
        # attention dropout: the weighted sum sees the dropped
        # coefficients, the normaliser never does (alpha~ = alpha * keep /
        # (1 - p))
        with scopes.scope("edge"):
            w = _keep_scale((key, rate), K, E, h.dtype)
        # the score (its source half riding u's rows), its max, the
        # normaliser and u: one scan over one gather of [h | as_src] rows
        # by dst_nid, the softmax carried online
        with scopes.scope("su"):
            s, m, z, u = _score_then_sum(
                h, w, *dst, E, _additive_tables(h, table, a_src, a_dst,
                                                slope), precision)
            m = jax.lax.stop_gradient(jnp.where(jnp.isfinite(m), m, 0.0))
        with scopes.scope("norm"):
            # Guard is _Z_GUARD (rationale at its definition): XLA flushes
            # subnormals to zero, and rows with no in-edges (padded shard
            # rows) have z == 0 -> 0/0 NaN.  Any live row has z >= 1 (the
            # max edge contributes exp(0)).
            zc = jnp.maximum(z, _Z_GUARD)
            out = u / zc.T[:, :, None]
    return out, s, m, zc


def _gat_plan_fwd(h, table, a_src, a_dst, plans, edge_ids, key, slope,
                  precision="highest", rate=0.0):
    out, s, m, zc = _gat_plan_out(h, table, a_src, a_dst, plans, edge_ids,
                                  key, slope, precision, rate)
    dst = (plans.dst_obi, plans.dst_edst, plans.dst_pos)
    with scopes.scope("fwd"):
        # e, read by the backward alone, from the rows' max
        with scopes.scope("bcast"):
            mb = _plan_broadcast(m, *dst, edge_ids[0].shape[0])
        with scopes.scope("edge"):
            e = jnp.exp(s - mb)                               # [K, E]
            # the slope's side: LeakyReLU with a positive slope keeps the
            # sign of its argument
            spos = s >= 0
            # both made before the output is handed on, as tconv's e
            # (_tconv_plan_fwd): nothing of the forward needs them, and
            # XLA would make them in the backward from s and the broadcast
            # max, both kept until then
            out, e, spos = jax.lax.optimization_barrier((out, e, spos))
    # the mask is NOT a residual: the backward redraws it from the key
    return out, (h, table, a_src, a_dst, plans, edge_ids, key,
                 spos, e, zc, out)


def _int_zeros(tree):
    """Cotangents of non-differentiable (integer / key) arguments."""
    return jax.tree.map(
        lambda a: np.zeros(a.shape, dtype=jax.dtypes.float0)
        if not jnp.issubdtype(a.dtype, jnp.floating) else jnp.zeros_like(a),
        tree)


def gat_src_scans(heads: int) -> int:
    """Scans over the src-keyed plan the backward of one gat op makes on
    the one-device plan road: ONE (dast rides dtable's, _plan_sum's
    ``ride``) while the stacked [2K, E] weights fit the 8 sublanes either
    part is padded to anyway, else the two it always made.  On the chip
    (gat-reddit.skewed, PR 34) riding at K = 8 saved 146 ms of the 3,768 ms
    epoch and cost 0.57 GiB of the peak (6.0211 -> 6.5951: the [16, E]
    stack, 1.5 GB, is live where the step is fullest, beside e, de and the
    mask); at K = 1 it saves 154 ms and the compiler's temporaries are the
    parent's to the megabyte."""
    return 1 if 2 * heads <= 8 else 2


def gat_fwd_scans(edges: int, edge_sharded: bool) -> int:
    """Scans over the plans the forward of one gat op makes, a training
    step: two on the plan road (:func:`_gat_plan_fwd`: ``su``, then the
    max's broadcast for ``e``); five on the edge-sharded road
    (parallel/spmd.py ``_egat_fwd``: the two broadcasts, the max, the
    normaliser and ``u``), its lane gather by ``edge_src`` a sixth where a
    shard's ``edges`` pass ``_LANE_GATHER_CHUNK``."""
    if not edge_sharded:
        return 2
    return 5 + int(edges > _LANE_GATHER_CHUNK)


def _gat_plan_bwd(slope, precision, rate, res, gout):
    h, table, a_src, a_dst, plans, edge_ids, key, qpos, e, zc, out = res
    edge_src, _ = edge_ids
    N, T = plans.num_rows, plans.table_rows
    K, E = h.shape[1], edge_src.shape[0]
    dplan = (plans.dst_obi, plans.dst_edst, plans.dst_pos)
    src = (plans.src_obi, plans.src_edst, plans.src_pos, plans.src_nid)
    with scopes.scope("bwd"):
        with scopes.scope("norm"):
            du = gout / zc.T[:, :, None]                      # [N, K, F]
            dz = -jnp.einsum("nkf,nkf->kn", gout, out,
                             precision="highest") / zc        # [K, N]
        with scopes.scope("edge"):
            w = _keep_scale((key, rate), K, E, e.dtype)       # the fwd's mask
        with scopes.scope("de"):
            de = _edge_contract(du, table, *dplan, plans.dst_nid, E)  # [K, E]
        if w is not None:
            with scopes.scope("edge"):
                de = de * w
        with scopes.scope("bcast"):
            de = _plan_broadcast(dz, *dplan, E, de)
        with scopes.scope("edge"):
            dq = e * de * jnp.where(qpos, 1.0, slope)         # [K, E]
        with scopes.scope("dq"):
            dadl = _plan_sum(dq, None, plans.dst_obi, plans.dst_edst,
                             plans.dst_pos, plans.dst_nid, N, "highest",
                             True)                            # [K, N]
        with scopes.scope("edge"):
            ew = e if w is None else e * w
        with scopes.scope("src"):
            if gat_src_scans(K) == 1:   # dq rides dtable's read of the plan
                dtable, dast = _plan_sum(ew, du, *src, T, precision, ride=dq)
            else:
                dast = _plan_sum(dq, None, *src, T, "highest")    # [K, T]
                dtable = _plan_sum(ew, du, *src, T, precision)    # [T, K, F]
        with scopes.scope("score"):     # the score products' transposes
            dtable = dtable + dast.T[:, :, None] * a_src[None]
            dh = dadl.T[:, :, None] * a_dst[None]
            da_src = jnp.einsum("kt,tkf->kf", dast, table,
                                precision="highest")
            da_dst = jnp.einsum("kn,nkf->kf", dadl, h, precision="highest")
    return (dh, dtable, da_src, da_dst) + _int_zeros((plans, edge_ids, key))


_gat_plan.defvjp(_gat_plan_fwd, _gat_plan_bwd)


# ---------------------------------------------------------------------------
# Dot-product attention (the Graph Transformer operator of Shi et al.,
# UniMP, arXiv:2009.03509 eqs 3-4; PyG's TransformerConv): the score of an
# in-edge j -> i is q_i . k_j / sqrt(F) per head, where GAT's is the rank-one
# a_dst . h_i + a_src . h_j.  Both rows are needed at every edge, so the
# forward contracts the window's q rows with gathered k rows in the scan
# that sums the v rows, and the backward has three weighted row sums where
# additive attention has one.
# ---------------------------------------------------------------------------

def tconv_attend(q, k, v, edge_src, edge_dst, num_nodes: int, drop=None):
    """Multi-head dot-product attention over in-edges, the xla road
    (sorted segment reductions; autodiff keeps what it likes):

      q: [N_local, K, F] queries of the destination rows;
      k, v: [T, K, F] key and value tables of the source rows;
      s_e = q[dst_e] . k[src_e] / sqrt(F) per head; alpha = edge_softmax(s);
      out[i] = sum_e alpha~_e v[src_e], alpha~ the coefficients after
      ``drop`` = (key, rate) (:func:`attention_keep`; not renormalised).
    Returns [N_local, K, F].  Materialises [E, K, F] twice: small graphs
    and the CPU tests; the plan road below is the one sized for a chip."""
    E, (K, F) = edge_src.shape[0], q.shape[1:]
    kg = jnp.take(k, edge_src, axis=0)                 # [E, K, F]
    s = jnp.einsum("ekf,ekf->ek", jnp.take(q, edge_dst, axis=0), kg,
                   precision="highest") / np.sqrt(F)
    alpha = edge_softmax(s, edge_dst, num_nodes)       # [E, K]
    w = _keep_scale(drop, K, E, alpha.dtype)
    if w is not None:
        alpha = alpha * w.T
    return jax.ops.segment_sum(
        jnp.take(v, edge_src, axis=0) * alpha[:, :, None], edge_dst,
        num_segments=num_nodes, indices_are_sorted=True)


def tconv_attend_plan(q, k, v, plans: GatPlans, num_edges: int, drop=None):
    """:func:`tconv_attend` over the chunk plans :func:`build_gat_plans`
    builds, scatter-free forward AND backward, equal to it up to float
    reassociation; the same key drops the same coefficients.

    Every sum is float32 at "highest", whatever ``-aggr-precision`` says:
    the score side as :func:`gat_attend_plan` keeps it (both contractions,
    the max, the normaliser), and the sums of value rows too (u forward;
    dq, dk, dv backward).  The values are zero-mean projections, so a row's
    weighted sum keeps little of its terms' size and one bf16 rounding of
    each product does not average out as it does over GAT's class-mean
    features: on the chip (PR 33) the logits then read 5.2e-4 to 1.4e-3 of
    the reference by seed, against 1.1e-3 to 2.3e-3 with a bf16 accumulate,
    which no bound separates; at "highest" they read 2e-7 to 5e-7 and the
    epoch costs 1.25 % more (9.4314 -> 9.5497 s: the row gather is the
    pass, not the one-hot dots).  Three scans a layer gather node rows,
    reading six tables: [k | v] side by side for the score and u, ONE scan
    over the dst-keyed plan with the softmax carried online
    (:func:`_score_then_sum`); in the backward [k | v] again for the
    contraction and dq, ONE scan over the dst-keyed plan, and q beside du
    for dk and dv, ONE scan of 2K heads over the src-keyed plan."""
    key, rate = _drop_args(drop)
    return _tconv_plan(q, k, v, plans, key, num_edges, rate)


def _additive_tables(h, table, a_src, a_dst, slope: float):
    """:func:`_score_then_sum`'s ``tables`` of GAT's additive score,
    ``LeakyReLU(a_dst . h_i + a_src . t_j)`` per head.  The source half is
    one number a head and a source row, so it rides u's own rows as K more
    columns, ``[t | as_src]`` (72 lanes at K = 8, F = 8; 42 at K = 1, F =
    41: both within the 128 lanes u's rows take anyway, and a gathered row
    costs its index, not its bytes: PERF.md section 6), where it was a
    lane gather by ``edge_src`` of its own (``_take_lanes``).  A step takes
    it out of the gathered rows by a one-hot product, ``[K, slots]`` (heads
    on sublanes; exact at "highest").  The destination half ``ad = a_dst .
    h`` is spread over the slots from the windows as
    :func:`_plan_broadcast` spreads it, ``[K, slots]`` too: spreading
    ``h`` and contracting it at every slot, as the dot score does ``q``,
    would make a ``[slots, K F]`` block a step for K numbers a slot.  Both
    halves are float32 at "highest" (at the MXU's default ``h`` and ``a``
    would be rounded to bf16 inside the exp) and ``s`` is their one sum
    through the LeakyReLU, as the three scans it replaces made it.
    ``table`` may have more rows than ``h`` (a shard's ``x ++ halo``)."""
    from roc_tpu.ops.pallas.segment_sum import EB

    def tables(rows, K, F):
        H = K * F
        ad_w = _window_rows(jnp.einsum("nkf,kf->nk", h, a_dst,
                                       precision="highest"))
        as_t = jnp.einsum("tkf,kf->tk", table, a_src, precision="highest")
        ts = jnp.concatenate([table.reshape(-1, H), as_t], axis=1)
        pick = jnp.eye(K, H + K, H, dtype=jnp.float32)    # as_src's columns

        def near(ob, ed):
            return _window_lanes(ad_w, ob, ed, K).transpose(1, 0, 2).reshape(
                K, ob.shape[0] * EB)

        def score(g, ad_e):
            as_e = jax.lax.dot_general(
                pick, g, (((1,), (1,)), ((), ())), precision="highest",
                preferred_element_type=jnp.float32)
            return jax.nn.leaky_relu(as_e + ad_e, negative_slope=slope)

        return ts, near, score, lambda g: g[:, :H]

    return tables


def _dot_tables(q, k, v):
    """:func:`_score_then_sum`'s ``tables`` of a dot-product score: ``k``
    (the score) and ``v`` (u's rows) side by side, one row list a step, the
    slots' own ``q`` rows, and per head ``q_i . k_j / sqrt(F)``.  The table
    is made from ``k`` and ``v`` behind an optimization barrier: the
    backward makes the same ``[k | v]`` from the same two arrays
    (:func:`_contract_then_sum`), and XLA would make both here, as one
    expression or as one fusion, and keep the backward's alive from the
    forward, a layer's worth each (+0.4 to 0.6 GB at the train step's peak
    by the compiler's buffer assignment for a v5e at the Reddit shape)."""
    from roc_tpu.ops.pallas.segment_sum import EB

    def tables(rows, K, F):
        H = K * F
        q_w = _window_rows(q.reshape(rows, H))
        kb, vb = jax.lax.optimization_barrier((k, v))
        kv = jnp.concatenate([kb.reshape(-1, H), vb.reshape(-1, H)], axis=1)
        collapse = _head_expand(K, F, jnp.float32)

        def near(ob, ed):
            return _window_slot_rows(q_w, ob, ed, H).reshape(
                ob.shape[0] * EB, H)

        def score(g, q_e):
            return _contract_heads(q_e, g[:, :H], collapse) * (
                1.0 / np.sqrt(F))

        return kv, near, score, lambda g: g[:, H:]

    return tables


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _tconv_plan(q, k, v, plans, key, num_edges, rate):
    return _tconv_plan_out(q, k, v, plans, key, num_edges, rate)[0]


def _tconv_plan_out(q, k, v, plans, key, num_edges, rate):
    """The forward's one scan and the division: (out, s, m, zc), all an
    evaluation pass makes (it needs no e, so no broadcast of the max)."""
    E, K = num_edges, q.shape[1]
    dst = (plans.dst_obi, plans.dst_edst, plans.dst_pos, plans.dst_nid)
    # the device scopes, as in _gat_plan_fwd
    with scopes.scope("fwd"):
        # the weighted sum sees the dropped coefficients, the normaliser
        # never
        with scopes.scope("edge"):
            w = _keep_scale((key, rate), K, E, q.dtype)
        # the score, its max, the normaliser and u: one scan over one
        # gather of [k | v] rows by dst_nid, the softmax carried online
        with scopes.scope("su"):
            s, m, z, u = _score_then_sum(q, w, *dst, E, _dot_tables(q, k, v),
                                         "highest")
            m = jax.lax.stop_gradient(jnp.where(jnp.isfinite(m), m, 0.0))
        with scopes.scope("norm"):
            # _Z_GUARD (rationale at its definition): rows with no in-edge
            # (padded rows) have z == 0; any live row has z >= 1
            zc = jnp.maximum(z, _Z_GUARD)
            out = u / zc.T[:, :, None]
    return out, s, m, zc


def _tconv_plan_fwd(q, k, v, plans, key, num_edges, rate):
    out, s, m, zc = _tconv_plan_out(q, k, v, plans, key, num_edges, rate)
    dst = (plans.dst_obi, plans.dst_edst, plans.dst_pos)
    with scopes.scope("fwd"):
        # e, the backward's one [K, E] residual, from the rows' max
        with scopes.scope("bcast"):
            mb = _plan_broadcast(m, *dst, num_edges)
        with scopes.scope("edge"):
            e = jnp.exp(s - mb)                                   # [K, E]
            # made before the output is handed on: nothing of the forward
            # needs e, and XLA would make every layer's next to the
            # backward, each layer's s kept until then (+1.1 GiB at the
            # train step's peak by the compiler's count for a v5e at the
            # Reddit shape)
            out, e = jax.lax.optimization_barrier((out, e))
    # ONE [K, E] residual: e.  The mask is redrawn from the key.
    return out, (q, k, v, plans, key, e, zc, out)


def _tconv_plan_bwd(num_edges, rate, res, gout):
    q, k, v, plans, key, e, zc, out = res
    N, T, E = plans.num_rows, plans.table_rows, num_edges
    K, F = q.shape[1:]
    dst = (plans.dst_obi, plans.dst_edst, plans.dst_pos, plans.dst_nid)
    src = (plans.src_obi, plans.src_edst, plans.src_pos, plans.src_nid)
    with scopes.scope("bwd"):
        with scopes.scope("norm"):
            du = gout / zc.T[:, :, None]                          # [N, K, F]
            dz = -jnp.einsum("nkf,nkf->kn", gout, out,
                             precision="highest") / zc            # [K, N]
        with scopes.scope("edge"):
            w = _keep_scale((key, rate), K, E, e.dtype)   # the fwd's mask
            ew = e if w is None else e * w
        # de = du[dst] . v[nid], ds = (e w de + e dz[dst]) / sqrt(F) and
        # dq = sum ds (x) k[nid] walk the SAME dst-keyed plan and nothing
        # summed over a row's edges stands between them: one scan, one
        # gather of [k | v] rows by dst_nid a step (three scans and two
        # gathers before PR 36), and ds lands where the next scan reads it
        with scopes.scope("dedq"):
            sw, dq = _contract_then_sum(du, dz, k, v, e, ew, *dst, E)
        # dk = sum ds (x) q[nid] and dv = sum (e w) (x) du[nid] walk the
        # SAME src-keyed plan, and _plan_sum treats heads independently: the
        # pair is one scan of 2K heads, one column gather of the stacked
        # [2K, E] weights sw = [ds ; e w] by src_pos and one row gather of
        # the side-by-side [N, 2K, F] table by src_nid a step, every output
        # column the contraction it was (v5e, the Reddit src plan, K = 4:
        # 1,253 -> 844 ms a layer at F = 32, 1,572 -> 1,043 at F = 41;
        # PERF.md PR 34)
        with scopes.scope("edge"):      # the table: no scan
            side = jnp.concatenate([q, du], axis=1)
        with scopes.scope("src"):
            dkv = _plan_sum(sw, side, *src, T, "highest")         # [T, 2K, F]
            dk, dv = dkv[:, :K], dkv[:, K:]
    return (dq, dk, dv) + _int_zeros((plans, key))


_tconv_plan.defvjp(_tconv_plan_fwd, _tconv_plan_bwd)


# ---------------------------------------------------------------------------
# Dynamic attention (GATv2: Brody, Alon, Yahav, "How Attentive are Graph
# Attention Networks?", ICLR 2022, arXiv:2105.14491 eq 7; PyG's
# GATv2Conv(share_weights=False)): the score of an in-edge j -> i is
# a . LeakyReLU(xr_i + xl_j) per head, where GAT's is the rank-one
# LeakyReLU(a_dst . h_i + a_src . h_j).  The nonlinearity sits at every
# channel between the pair and ``a``, so the score needs both rows at every
# edge (the score is a _plan_blocks form over gathered xl rows and the
# window's xr rows), and the backward needs the slope at every edge and
# channel, which no [E, K F] array could keep: each backward scan
# regathers the rows and recomputes it.
# ---------------------------------------------------------------------------

def gatv2_attend(xl, xr, a, edge_src, edge_dst, num_nodes: int, drop=None,
                 slope: float = 0.2):
    """Multi-head dynamic attention over in-edges, the xla road (sorted
    segment reductions; autodiff keeps what it likes):

      xl: [T, K, F] source rows (scores and values); xr: [N_local, K, F]
      destination rows; a: [K, F];
      s_e = sum_f a[k, f] LeakyReLU(xr[dst_e] + xl[src_e]) per head;
      alpha = edge_softmax(s); out[i] = sum_e alpha~_e xl[src_e], alpha~
      the coefficients after ``drop`` = (key, rate) (:func:`attention_keep`;
      not renormalised).
    Returns [N_local, K, F].  Materialises [E, K, F]: small graphs and the
    CPU tests; the plan road below is the one sized for a chip."""
    E, (K, _) = edge_src.shape[0], xl.shape[1:]
    g = jnp.take(xl, edge_src, axis=0)                 # [E, K, F]
    p = jnp.take(xr, edge_dst, axis=0) + g
    s = jnp.einsum("ekf,kf->ek", jax.nn.leaky_relu(p, negative_slope=slope),
                   a, precision="highest")
    alpha = edge_softmax(s, edge_dst, num_nodes)       # [E, K]
    w = _keep_scale(drop, K, E, alpha.dtype)
    if w is not None:
        alpha = alpha * w.T
    return jax.ops.segment_sum(g * alpha[:, :, None], edge_dst,
                               num_segments=num_nodes,
                               indices_are_sorted=True)


def gatv2_attend_plan(xl, xr, a, plans: GatPlans, num_edges: int, drop=None,
                      slope: float = 0.2):
    """:func:`gatv2_attend` over the chunk plans :func:`build_gat_plans`
    builds, scatter-free forward AND backward, equal to it up to float
    reassociation; the same key drops the same coefficients.  No [E, K F]
    array exists outside one scan step.

    Four scans a layer gather node rows, one index list each: forward the
    score (xl by dst_nid, xr spread from the window) and u (xl); backward
    ONE scan over the dst-keyed plan that regathers xl, forms de and ds,
    recomputes the slope and lands dxr and the sum for da
    (:func:`_dynamic_then_sum`), and ONE over the src-keyed plan that
    gathers [xr | du] side by side and sums both terms of dxl
    (:func:`_dynamic_src_sum`).  Every sum is float32 at "highest",
    whatever ``-aggr-precision`` says, as tconv_attend_plan's are: on a
    v5e at the Reddit shape the logits read 2.2e-7 of the float32
    reference so, and 2.1e-4 to 2.6e-4 with u at the MXU's default (one
    bf16 rounding of each product; PERF.md section 2)."""
    key, rate = _drop_args(drop)
    return _gatv2_plan(xl, xr, a, plans, key, num_edges, float(slope), rate)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _gatv2_plan(xl, xr, a, plans, key, num_edges, slope, rate):
    return _gatv2_plan_fwd(xl, xr, a, plans, key, num_edges, slope, rate)[0]


def _dynamic_score(xl, xr, a, obi, edst, pos, nid, num_edges: int,
                   slope: float):
    """s[k, e] = Σ_f a[k, f] LeakyReLU(xr[dst_e, k, f] + xl[src_e, k, f]) as
    ``[K, E]``, over the aligned dst-keyed plan: a step gathers its slots'
    ``xl`` rows by the plan's ``nid``, spreads its windows' ``xr`` rows over
    the slots (one-hot, exact) and collapses each head's F lanes against
    ``a`` on the MXU; the [slots, K F] pre-activation lives in the step."""
    from roc_tpu.ops.pallas.segment_sum import EB, VB
    rows, K, F = xr.shape
    H = K * F
    xr_w = _window_rows(xr.reshape(rows, H))
    lf = xl.reshape(xl.shape[0], H)
    collapse = _head_expand(K, F, jnp.float32)
    a_lanes = a.reshape(1, H).astype(jnp.float32)

    def form(ob, ed, ni):
        cb = ob.shape[0]
        p = _window_slot_rows(xr_w, ob, ed, H).reshape(cb * EB, H) \
            + jnp.take(lf, ni.reshape(cb * EB), axis=0, mode="clip")
        s = _contract_heads(jax.nn.leaky_relu(p, negative_slope=slope),
                            a_lanes, collapse)                # [K, slots]
        # a masked slot gathered row 0: an exact zero there, as
        # _plan_blocks asks
        return jnp.where((ed < VB).reshape(1, cb * EB), s, 0.0).reshape(
            K, cb, EB)

    return _plan_blocks(form, K, obi, edst, pos, nid, num_edges, xr)


def _gatv2_plan_fwd(xl, xr, a, plans, key, num_edges, slope, rate):
    N, E = plans.num_rows, num_edges
    K = xl.shape[1]
    dst = (plans.dst_obi, plans.dst_edst, plans.dst_pos, plans.dst_nid)
    # the device scopes, as in _gat_plan_fwd
    with scopes.scope("fwd"):
        with scopes.scope("score"):
            s = _dynamic_score(xl, xr, a, *dst, E, slope)         # [K, E]
        with scopes.scope("max"):
            m = _plan_max(s, *dst[:3], N)
            m = jax.lax.stop_gradient(jnp.where(jnp.isfinite(m), m, 0.0))
        with scopes.scope("bcast"):
            mb = _plan_broadcast(m, *dst[:3], E)
        with scopes.scope("edge"):
            e = jnp.exp(s - mb)                                   # [K, E]
        with scopes.scope("norm"):
            z = _plan_sum(e, None, *dst, N, "highest", True)      # [K, N]
        # the weighted sum sees the dropped coefficients, the normaliser
        # never
        with scopes.scope("edge"):
            w = _keep_scale((key, rate), K, E, e.dtype)
            ew = e if w is None else e * w
        with scopes.scope("u"):
            u = _plan_sum(ew, xl, *dst, N, "highest", True)       # [N, K, F]
        with scopes.scope("norm"):
            # _Z_GUARD (rationale at its definition): rows with no in-edge
            # (padded rows) have z == 0; any live row has z >= 1
            zc = jnp.maximum(z, _Z_GUARD)
            out = u / zc.T[:, :, None]
    # ONE [K, E] residual, e, and the two node tables: no slope is kept,
    # each backward scan recomputes it; the mask is redrawn from the key
    return out, (xl, xr, a, plans, key, e, zc, out)


def _dynamic_then_sum(du, dz, xl, xr, a, e, ew, obi, edst, pos, nid,
                      num_edges: int, slope: float):
    """The dst side of a dynamic score's backward in ONE scan over the
    aligned dst-keyed plan, one gather of ``xl`` rows by its ``nid`` a step
    (the pattern of :func:`_contract_then_sum`):

      de[k, e]  = Σ_f du[dst_e, k, f]·xl[src_e, k, f]     (never edge-sized)
      ds[k, e]  = ew[k, e]·de + e[k, e]·dz[k, dst_e]                [K, E]
      p         = xr[dst_e] + xl[src_e]                    (never edge-sized)
      dxr[i]    = Σ_{e: dst_e = i} ds (x) a · LeakyReLU'(p)     [rows, K, F]
      da[k, f]  = Σ_e ds[k, e] · LeakyReLU(p)[k, f]                  [K, F]

    The slope is the derivative the forward's ``jax.nn.leaky_relu`` has: 1
    where p >= 0, ``slope`` below.  The scan's carry is the ``[2K, E]``
    stack the src-keyed scan reads next (``[e ; ew]`` going in, ``[ds ;
    ew]`` coming out) and the ``[K * F]`` sum for da.
    Masked slots read zeros of ``du`` and ``dz``, so ds and every addend
    are exact zeros there.  Float32 at "highest" throughout.
    ``du``: [rows, K, F]; ``dz``: [K, rows]; ``xl``: [T, K, F]; ``xr``:
    [rows, K, F]; ``e``, ``ew``: [K, E].  Returns ([ds ; ew], dxr, da)."""
    from roc_tpu.ops.aggregate import _vary_like

    def tables(rows, K, F):
        H = K * F
        xr_w = _window_rows(xr.reshape(rows, H))
        lf = xl.reshape(xl.shape[0], H)
        expand = _head_expand(K, F, jnp.float32)
        a_lanes = a.reshape(1, H).astype(jnp.float32)

        def addend(g, ds, ob, ed, da):
            p = _window_slot_rows(xr_w, ob, ed, H).reshape(-1, H) + g
            ds_l = _over_head_lanes(ds, expand, 0, g.dtype)   # [slots, K F]
            da = da + jnp.sum(
                ds_l * jax.nn.leaky_relu(p, negative_slope=slope), axis=0)
            return ds_l * a_lanes * jnp.where(p >= 0, 1.0, slope), da

        return lf, expand, lambda g: g, addend, lambda: _vary_like(
            jnp.zeros((H,), jnp.float32), e)

    sw, dxr, da = _land_ds_then_sum(du, dz, e, ew, obi, edst, pos, nid,
                                    num_edges, None, tables)
    return sw, dxr.astype(xr.dtype), da.reshape(*a.shape).astype(a.dtype)


def _dynamic_src_sum(sw, du, xl, xr, a, obi, edst, pos, nid,
                     table_rows: int, slope: float):
    """dxl of a dynamic score, ONE scan over the src-keyed plan (windows:
    source rows; ``nid``: each edge's destination):

      dxl[j] = Σ_{e: src_e = j} ew (x) du[dst_e]
                              + ds (x) a · LeakyReLU'(xr[dst_e] + xl[j])

    the value path and the score path of the same slots.  A step reads the
    stacked ``[2K, E]`` weights ``sw = [ds ; ew]`` by ONE column gather of
    its positions (:func:`_slot_reader`), gathers ``[xr | du]`` side by side
    by ONE row list and spreads its windows' own ``xl`` rows over the slots
    (one-hot, exact) to recompute the slope.  Steps of
    :func:`plan_sum_step` of the gathered row's width, as a row sum of
    :func:`_plan_sum`.  Masked slots (``edst == VB``) match no window row.
    Float32 at "highest".  Returns [table_rows, K, F]."""
    from roc_tpu.ops.aggregate import _vary_like
    from roc_tpu.ops.pallas.segment_sum import EB, VB
    rows, K, F = du.shape
    H = K * F
    cb, acc_windows = _plan_scan_shapes(obi, table_rows, plan_sum_step(2 * H))
    obi, edst, pos, nid, nsteps = _pad_steps(obi, edst, pos, nid, cb)
    read = _slot_reader(sw, cb, False)
    side = jnp.concatenate([xr.reshape(rows, H), du.reshape(rows, H)], axis=1)
    xl_w = _window_rows(xl.reshape(xl.shape[0], H))
    expand = _head_expand(K, F, jnp.float32)
    a_lanes = a.reshape(1, H).astype(jnp.float32)

    def body(acc, sl):
        ob, ed, po, ni = sl
        g = jnp.take(side, ni.reshape(cb * EB), axis=0, mode="clip")
        slots = read(po)                                  # [cb, 2K, EB]
        p = g[:, :H] + _window_slot_rows(xl_w, ob, ed, H).reshape(
            cb * EB, H)
        add = _over_head_lanes(slots[:, K:], expand, 1, g.dtype) * g[:, H:] \
            + _over_head_lanes(slots[:, :K], expand, 1, g.dtype) * a_lanes \
            * jnp.where(p >= 0, 1.0, slope)
        return _add_window_rows(acc, add, ed, ob, "highest"), None

    acc = _vary_like(jnp.zeros((acc_windows * VB, H), jnp.float32), sw)
    acc, _ = jax.lax.scan(
        body, acc, (obi.reshape(nsteps, cb), edst.reshape(nsteps, cb, EB),
                    pos.reshape(nsteps, cb, EB), nid.reshape(nsteps, cb, EB)))
    return acc[:table_rows].astype(xl.dtype).reshape(table_rows, K, F)


def _gatv2_plan_bwd(num_edges, slope, rate, res, gout):
    xl, xr, a, plans, key, e, zc, out = res
    T, E = plans.table_rows, num_edges
    K = xl.shape[1]
    dst = (plans.dst_obi, plans.dst_edst, plans.dst_pos, plans.dst_nid)
    src = (plans.src_obi, plans.src_edst, plans.src_pos, plans.src_nid)
    with scopes.scope("bwd"):
        with scopes.scope("norm"):
            du = gout / zc.T[:, :, None]                          # [N, K, F]
            dz = -jnp.einsum("nkf,nkf->kn", gout, out,
                             precision="highest") / zc            # [K, N]
        with scopes.scope("edge"):
            w = _keep_scale((key, rate), K, E, e.dtype)   # the fwd's mask
            ew = e if w is None else e * w
        # de, ds, dxr and da: one scan over one gather of xl rows by
        # dst_nid, as tconv's dedq; ds lands where the next scan reads it
        with scopes.scope("dedq"):
            sw, dxr, da = _dynamic_then_sum(du, dz, xl, xr, a, e, ew, *dst,
                                            E, slope)
        with scopes.scope("src"):
            dxl = _dynamic_src_sum(sw, du, xl, xr, a, *src, T, slope)
    return (dxl, dxr, da) + _int_zeros((plans, key))


_gatv2_plan.defvjp(_gatv2_plan_fwd, _gatv2_plan_bwd)
