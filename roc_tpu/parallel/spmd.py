"""SPMD multi-chip training over a 1-D vertex-shard mesh.

The TPU-native replacement for the reference's entire distribution stack
(SURVEY.md §5.8): where ROC maps whole node tensors into every node's
zero-copy memory and lets Legion's coherence move the bytes
(scattergather.cc:69-73), we shard every node tensor over the mesh's
'parts' axis and exchange exactly what aggregation needs with explicit ICI
collectives inside one `shard_map`-ped train step:

  v0 (`halo=False`): `all_gather` the shard's activations — byte-equivalent
      to the reference's full replication, one collective per aggregation.
  v1 (`halo=True`, default): gather only the rows other shards reference,
      via precomputed halo maps + one `all_to_all` (roc_tpu/parallel/halo.py).

Gradients: `psum` over 'parts' (replaces the reference's gather-all-replicas-
to-one-GPU serial sum, optimizer_kernel.cu:88-94); Adam then runs replicated
on every chip — same math, no single-device bottleneck.  Backward of the
halo exchange is AD's transpose of the collective (the reference hand-wrote
this as "same kernel, transposed roles", scattergather_kernel.cu:160-170).

Multi-host: the same code runs under `jax.distributed.initialize()`; the
'parts' axis then spans hosts and XLA routes the same collectives over
ICI within a slice and DCN across slices.
"""

from __future__ import annotations

import dataclasses
import os
import sys
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from roc_tpu import fault, obs, ops
from roc_tpu.analysis import retrace as _retrace
from roc_tpu.graph.partition import (Partition, edge_block_arrays,
                                     edge_block_arrays_t, partition_graph)
from roc_tpu.models.model import GraphCtx, refuse_pair_attention
from roc_tpu.obs import scopes
from roc_tpu.parallel.halo import HaloMaps, build_halo_maps
from roc_tpu.ops.softmax import MASK_NONE
from roc_tpu.parallel.mesh import PARTS_AXIS, make_mesh
from roc_tpu.train.driver import BaseTrainer


@dataclasses.dataclass
class ShardedGraphData:
    """Per-shard edge arrays, leading axis = 'parts' (sharded).  ``backend``
    and ``mode`` are pytree metadata (static).

    mode="vertex": contiguous vertex shards own their in-edges (the
    reference's partitioning); edge_dst is shard-local.  mode="edge":
    exactly-equal edge blocks (mid-vertex cuts allowed — zero padding tax
    under skew); both endpoints are padded-global and aggregation ends in a
    psum_scatter (see partition.edge_block_arrays)."""
    edge_src: jnp.ndarray            # [P, E] int32 (table-local for halo,
                                     #              padded-global for v0)
    edge_dst: jnp.ndarray            # [P, E] int32, ascending per shard
    in_degree: jnp.ndarray           # [P, S] float32
    send_idx: Optional[jnp.ndarray]  # [P, P, K] int32, halo mode only
    ring_src: Optional[jnp.ndarray] = None   # [P, P, Eo] int32, ring mode
    ring_dst: Optional[jnp.ndarray] = None   # [P, P, Eo] int32, ring mode
    plans: object = None             # stacked AggregatePlans ([P, ...] axes)
    gat_plans: object = None         # stacked ops.edge.GatPlans
    ring_plans: object = None        # ring.RingPlans ([P, P, ...] axes)
    backend: str = dataclasses.field(default="xla", metadata={"static": True})
    mode: str = dataclasses.field(default="vertex",
                                  metadata={"static": True})
    precision: str = dataclasses.field(default="exact",
                                       metadata={"static": True})
    # Wire format for feature exchanges over ICI (_wire_down/_wire_up).
    # Static metadata on purpose: it changes tree_structure(gd), so the
    # SPMD step cache (_build_steps sig) can never serve a jitted step
    # traced for the other dtype.
    xch_dtype: str = dataclasses.field(default="fp32",
                                       metadata={"static": True})
    xch_round: str = dataclasses.field(default="nearest",
                                       metadata={"static": True})
    xch_comp: str = dataclasses.field(default="plain",
                                      metadata={"static": True})


jax.tree_util.register_dataclass(
    ShardedGraphData,
    data_fields=["edge_src", "edge_dst", "in_degree", "send_idx",
                 "ring_src", "ring_dst", "plans", "gat_plans", "ring_plans"],
    meta_fields=["backend", "mode", "precision", "xch_dtype", "xch_round",
                 "xch_comp"])


@dataclasses.dataclass(frozen=True)
class EdgePlans:
    """Windowed chunk plans for edge-sharded matmul aggregation.

    Each block's scatter targets are a contiguous padded-id range (fwd:
    dst-sorted cuts; bwd: src-sorted cuts — edge_block_arrays[_t]), so
    plans are built over a common ``span``-row window per direction and
    placed into the global [P*S] accumulator at a per-block ``base``.
    Plan size is O(E/P + span/VB) per block instead of O(P*S/VB) — the
    empty-window chunk floor does not grow with the mesh.
    Array leaves carry a leading [P] axis (sharded); spans are static."""
    fwd_obi: jnp.ndarray      # [P, Cf]
    fwd_first: jnp.ndarray
    fwd_edst: jnp.ndarray     # [P, Cf, EB] window-local scatter ids
    fwd_esrc: jnp.ndarray     # [P, Cf, EB] global gather ids
    fwd_base: jnp.ndarray     # [P] int32 window base row
    bwd_obi: jnp.ndarray
    bwd_first: jnp.ndarray
    bwd_edst: jnp.ndarray
    bwd_esrc: jnp.ndarray
    bwd_base: jnp.ndarray
    span_fwd: int = dataclasses.field(metadata={"static": True}, default=0)
    span_bwd: int = dataclasses.field(metadata={"static": True}, default=0)


jax.tree_util.register_dataclass(
    EdgePlans,
    data_fields=["fwd_obi", "fwd_first", "fwd_edst", "fwd_esrc", "fwd_base",
                 "bwd_obi", "bwd_first", "bwd_edst", "bwd_esrc", "bwd_base"],
    meta_fields=["span_fwd", "span_bwd"])


def _block_window(keys, NS: int, allgather=None):
    """(base [L], span): each block's VB-aligned window over its key
    range, span raised to the (optionally allgathered) maximum and
    clamped so base + span <= NS — the accumulator has exactly NS rows,
    and dynamic_update_slice would otherwise clamp the start and shift a
    block's values onto wrong rows.  Relative ids still fit: keys.max
    <= NS - 1 <= base + span - 1."""
    from roc_tpu.ops.pallas.segment_sum import VB
    base = (keys.min(axis=1) // VB) * VB
    span = int((keys.max(axis=1) + 1 - base).max())
    span = min(-(-_allgather_floors([[span]], allgather)[0] // VB) * VB,
               NS)
    return np.minimum(base, NS - span), span


def _windowed_block_plans(gather, scatter, NS: int, allgather=None):
    """Per-block chunk plans over each block's contiguous scatter window.

    gather/scatter: [L, Eb] padded-global ids, scatter nondecreasing per
    block (L = local blocks; all P single-host).  Returns (obi, first,
    edst, esrc stacked [L, C(, EB)], base [L], span).  ``allgather``
    raises the static shapes (span, chunk count C) to the global maxima —
    the -perhost contract of shard_load.allgather_floors."""
    from roc_tpu.ops.pallas.segment_sum import build_chunk_plan, pad_chunks

    L_ = scatter.shape[0]
    bases, span = _block_window(scatter, NS, allgather)
    plans = [build_chunk_plan(
        np.asarray(gather[p], np.int32),
        np.asarray(scatter[p] - bases[p], np.int32), span)
        for p in range(L_)]
    for pl in plans:   # same invariant build_aggregate_plans pins
        assert np.all(np.diff(np.asarray(pl.obi)) <= 1)
    C = _allgather_floors([[pl.obi.shape[0] for pl in plans]],
                          allgather)[0]
    padded = [pad_chunks(pl.obi, pl.first, pl.edst, pl.esrc,
                         C - pl.obi.shape[0], jnp) for pl in plans]
    stack = [jnp.stack([q[i] for q in padded]) for i in range(4)]
    return stack[0], stack[1], stack[2], stack[3], \
        jnp.asarray(bases, jnp.int32), span


def build_edge_plans(graph, meta, fwd_arrays=None) -> EdgePlans:
    """Fwd + transposed-bwd windowed plans for edge-sharded aggregation.
    ``fwd_arrays``: pass an existing edge_block_arrays(graph, meta) result
    to skip rebuilding it."""
    b_gat, b_sct = edge_block_arrays_t(graph, meta)
    f_gat, f_sct = fwd_arrays if fwd_arrays is not None \
        else edge_block_arrays(graph, meta)
    return build_edge_plans_arrays(meta, f_gat, f_sct, b_gat, b_sct)


def build_edge_plans_arrays(meta, f_gat, f_sct, b_gat, b_sct,
                            allgather=None) -> EdgePlans:
    """EdgePlans from prebuilt (or per-host byte-range-loaded) block
    arrays; ``allgather`` makes the static shapes globally consistent."""
    NS = meta.num_parts * meta.shard_nodes
    fo, ff, fd, fs, fb, span_f = _windowed_block_plans(f_gat, f_sct, NS,
                                                       allgather)
    bo, bf, bd, bs, bb, span_b = _windowed_block_plans(b_gat, b_sct, NS,
                                                       allgather)
    return EdgePlans(fwd_obi=fo, fwd_first=ff, fwd_edst=fd, fwd_esrc=fs,
                     fwd_base=fb, bwd_obi=bo, bwd_first=bf, bwd_edst=bd,
                     bwd_esrc=bs, bwd_base=bb,
                     span_fwd=span_f, span_bwd=span_b)


def _edge_mm_half(x, obi, edst, esrc, base, span: int, precision):
    """One direction of the edge-mode aggregation: all-gather the source
    table, windowed scatter-free sum over this block's edges, place at the
    block's window base in the global accumulator, reduce onto owners."""
    from roc_tpu.ops.aggregate import _matmul_run
    table = jax.lax.all_gather(x, PARTS_AXIS, tiled=True)    # [P*S, H]
    part_loc = _matmul_run(table, obi, edst, esrc, span, precision)
    return _scatter_to_owner(part_loc, base, table.shape[0])


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def edge_aggregate_matmul(x, plans: EdgePlans, precision):
    """Edge-sharded sum aggregation on the matmul backend (inside
    shard_map; plans fields are this shard's blocks).  The backward is the
    same computation over the transposed (src-sorted) blocks — AD's
    transpose of the gather would emit the serialized TPU scatter this
    backend exists to avoid, hence the custom vjp."""
    return _edge_mm_half(x, plans.fwd_obi, plans.fwd_edst, plans.fwd_esrc,
                         plans.fwd_base, plans.span_fwd, precision)


def _ea_fwd(x, plans, precision):
    return edge_aggregate_matmul(x, plans, precision), plans


def _ea_bwd(precision, plans, g):
    dx = _edge_mm_half(g, plans.bwd_obi, plans.bwd_edst, plans.bwd_esrc,
                       plans.bwd_base, plans.span_bwd, precision)
    zero = jax.tree.map(
        lambda a: np.zeros(a.shape, dtype=jax.dtypes.float0), plans)
    return dx, zero


edge_aggregate_matmul.defvjp(_ea_fwd, _ea_bwd)


@dataclasses.dataclass(frozen=True)
class EdgeBinnedPlans:
    """Binned two-phase schedules for edge-sharded aggregation — the
    composition VERDICT r2 flagged missing: each block's contiguous
    scatter window (the same windowing EdgePlans proves out for matmul)
    becomes the binned kernel's output space, so the fastest kernel runs
    under the skew-proof distribution mode.  ``plans.fwd/bwd`` are stacked
    :class:`roc_tpu.ops.aggregate.BinnedPlans` payloads ([P, ...] axes);
    bases place each block's [span, H] result in the global accumulator."""
    plans: object             # ops.BinnedPlans (stacked fwd+bwd payloads)
    fwd_base: jnp.ndarray     # [P] int32
    bwd_base: jnp.ndarray     # [P] int32


jax.tree_util.register_dataclass(
    EdgeBinnedPlans, data_fields=["plans", "fwd_base", "bwd_base"],
    meta_fields=[])


def build_edge_binned_plans(graph, meta, fwd_arrays=None):
    """Per-block binned plans over the blocks' scatter windows, or None
    where the binned occupancy model says the padding would eat the win
    (caller falls back to the matmul windowed plans)."""
    from roc_tpu.ops.pallas.binned import binned_viable
    NS = meta.num_parts * meta.shard_nodes
    f_gat, f_sct = fwd_arrays if fwd_arrays is not None \
        else edge_block_arrays(graph, meta)
    b_gat, b_sct = edge_block_arrays_t(graph, meta)
    P_, Eb = f_sct.shape
    from roc_tpu.ops.pallas.binned import build_binned_plan

    def direction(gather, scatter):
        bases, span = _block_window(scatter, NS)
        if not binned_viable(span, NS, Eb):
            return None
        return [build_binned_plan(
            np.asarray(gather[p], np.int64),
            np.asarray(scatter[p] - bases[p], np.int64), span, NS)
            for p in range(P_)], bases

    f = direction(f_gat, f_sct)
    b = direction(b_gat, b_sct)
    if f is None or b is None:
        return None
    fwd_list, f_bases = f
    bwd_list, b_bases = b
    stacked = ops.pad_binned_plans(
        [ops.BinnedPlans(fwd=fw, bwd=bw)
         for fw, bw in zip(fwd_list, bwd_list)])
    return EdgeBinnedPlans(plans=stacked,
                           fwd_base=jnp.asarray(f_bases, jnp.int32),
                           bwd_base=jnp.asarray(b_bases, jnp.int32))


def _eb_half(x, plan, base, interpret, precision):
    """One direction of binned edge-mode aggregation: all-gather the
    source table, binned sum over this block's window, place at the
    block's base, reduce onto owners (same shape as _edge_mm_half)."""
    from roc_tpu.ops.pallas.binned import run_binned
    table = jax.lax.all_gather(x, PARTS_AXIS, tiled=True)    # [NS, H]
    part_loc = run_binned(table, plan, interpret, precision)  # [span, H]
    return _scatter_to_owner(part_loc, base, table.shape[0])


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def edge_aggregate_binned(x, eplans: EdgeBinnedPlans, interpret,
                          precision="fast"):
    """Edge-sharded sum aggregation on the binned backend (inside
    shard_map; plan payloads are this shard's block).  Backward = the
    same kernel over the transposed (src-sorted) block windows."""
    return _eb_half(x, eplans.plans.fwd, eplans.fwd_base, interpret,
                    precision)


def _eb_fwd(x, eplans, interpret, precision):
    return edge_aggregate_binned(x, eplans, interpret, precision), eplans


def _eb_bwd(interpret, precision, eplans, g):
    dx = _eb_half(g, eplans.plans.bwd, eplans.bwd_base, interpret,
                  precision)
    zero = jax.tree.map(
        lambda a: np.zeros(a.shape, dtype=jax.dtypes.float0), eplans)
    return dx, zero


edge_aggregate_binned.defvjp(_eb_fwd, _eb_bwd)


# ---------------------------------------------------------------------------
# Edge-sharded attention on the plan backend: scatter-free fwd AND bwd.
# (VERDICT r3 item 5 — _edge_attend's autodiff backward transposes its
# segment ops into serialized TPU scatters; this is the windowed plan
# treatment that docstring promised.)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EdgeGatPlans:
    """Per-block edge-position chunk plans for edge-sharded GAT.

    ``plans`` is a stacked :class:`roc_tpu.ops.edge.GatPlans` ([P, ...]
    leaves): dst-keyed windows are local to each block's contiguous
    dst range (span ``plans.num_rows``, placed at ``dst_base``); src-keyed
    windows cover each block's src id range (span ``plans.table_rows`` at
    ``src_base``).  A block's sources are arbitrary global ids, so the src
    span is typically ~the whole padded id space and its empty-window
    chunk floor costs ~NS/VB extra chunks per backward — the documented
    price of mid-vertex cuts (the fwd dst windows stay tight)."""
    plans: object             # ops.edge.GatPlans (stacked)
    dst_base: jnp.ndarray     # [P] int32
    src_base: jnp.ndarray     # [P] int32


jax.tree_util.register_dataclass(
    EdgeGatPlans, data_fields=["plans", "dst_base", "src_base"],
    meta_fields=[])


def build_edge_gat_plans(graph, meta, fwd_arrays=None) -> EdgeGatPlans:
    """Host-side schedules for :func:`edge_gat_attend` — dst- and src-keyed
    edge-position plans per block, windows local to each block's id span
    (the GatPlans analog of build_edge_plans)."""
    es, ed = fwd_arrays if fwd_arrays is not None \
        else edge_block_arrays(graph, meta)       # [P, Eb] global, dst-sorted
    return build_edge_gat_plans_arrays(meta, es, ed)


def build_edge_gat_plans_arrays(meta, es, ed,
                                allgather=None) -> EdgeGatPlans:
    """EdgeGatPlans from prebuilt (or per-host byte-range-loaded) block
    arrays; ``allgather`` raises window spans and chunk counts to the
    global maxima (the -perhost static-shape contract)."""
    from roc_tpu.ops.edge import (GatPlans, _aligned_position_plan,
                                  _position_plan, pad_gat_plans)
    NS = meta.num_parts * meta.shard_nodes
    es = np.asarray(es, np.int64)
    ed = np.asarray(ed, np.int64)
    L_, Eb = es.shape

    dbase, span_d = _block_window(ed, NS, allgather)
    orders = np.argsort(es, axis=1, kind="stable")
    es_sorted = np.take_along_axis(es, orders, axis=1)
    sbase, span_s = _block_window(es_sorted, NS, allgather)
    plans = []
    for p in range(L_):
        d = _aligned_position_plan(ed[p] - dbase[p], es[p], span_d)
        s = _position_plan(es_sorted[p] - sbase[p], orders[p], ed[p],
                           span_s)
        plans.append(GatPlans(*(jnp.asarray(a) for a in d + s),
                              num_rows=span_d, table_rows=span_s))
    f = _allgather_floors([[p.dst_obi.shape[0] for p in plans],
                           [p.src_obi.shape[0] for p in plans]], allgather)
    return EdgeGatPlans(plans=pad_gat_plans(plans, min_d=f[0], min_s=f[1]),
                        dst_base=jnp.asarray(dbase, jnp.int32),
                        src_base=jnp.asarray(sbase, jnp.int32))


def _scatter_to_owner(part_loc, base, NS: int):
    """Place a block's [span, H] partial at its window base in the global
    [NS, H] accumulator and reduce onto owners (the all_gather-transpose
    shape every edge-mode path shares)."""
    acc = jax.lax.pcast(jnp.zeros((NS, part_loc.shape[1]), part_loc.dtype),
                        PARTS_AXIS, to="varying")
    acc = jax.lax.dynamic_update_slice(acc, part_loc, (base, 0))
    return jax.lax.psum_scatter(acc, PARTS_AXIS, scatter_dimension=0,
                                tiled=True)


def edge_gat_attend(h, a_src, a_dst, egp: EdgeGatPlans, edge_ids,
                    slope: float, precision: str = "highest", drop=None):
    """GAT attention under edge sharding, scatter-free fwd and bwd (inside
    shard_map; egp fields are this shard's block).

    Same semantics as :func:`_edge_attend` (equal up to float
    reassociation): block-local plan reductions over exactly Eb edges,
    one `pmax` for the global softmax shift, `psum_scatter` onto owners —
    but every segment reduction rides the one-hot window machinery of
    ops.edge (_plan_max/_plan_sum, per-edge arrays [K, Eb]), and the
    backward is hand-derived so no gather transposes into a TPU scatter
    (the reference's transposed-role relaunch,
    scattergather_kernel.cu:160-170, at block granularity).  ``drop`` =
    (key, rate): attention dropout as in ops.edge.gat_attend_plan, the
    mask redrawn from the key in the backward."""
    from roc_tpu.ops.edge import _drop_args
    key, rate = _drop_args(drop)
    return _egat(h, a_src, a_dst, egp, edge_ids, key, slope, precision,
                 rate)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _egat(h, a_src, a_dst, egp, edge_ids, key, slope, precision, rate):
    return _egat_fwd(h, a_src, a_dst, egp, edge_ids, key, slope, precision,
                     rate)[0]


def _egat_fwd(h, a_src, a_dst, egp, edge_ids, key, slope, precision, rate):
    from roc_tpu.ops.edge import (_keep_scale, _plan_broadcast, _plan_max,
                                  _plan_sum, _take_lanes)
    es, _ = edge_ids
    S, K, F = h.shape
    pl = egp.plans
    span_d = pl.num_rows
    dplan = (pl.dst_obi, pl.dst_edst, pl.dst_pos)

    def at_dst(node_t, onto=None):
        """``node_t[:, ed]`` (added to ``onto``) for a [K, NS] table: the
        block's destinations are global ids inside its window [dst_base,
        dst_base + span_d), so the read is the plan's segment broadcast of
        that slice."""
        return _plan_broadcast(
            jax.lax.dynamic_slice(node_t, (0, egp.dst_base), (K, span_d)),
            *dplan, es.shape[0], onto)
    table = jax.lax.all_gather(h.reshape(S, K * F), PARTS_AXIS, tiled=True)
    NS = table.shape[0]
    table = table.reshape(NS, K, F)
    # project locally, gather the small [K, NS] score vectors (projecting
    # the gathered table would repeat every shard's flops on every device)
    as_t = jax.lax.all_gather(jnp.einsum("skf,kf->ks", h, a_src),
                              PARTS_AXIS, axis=1, tiled=True)
    ad_t = jax.lax.all_gather(jnp.einsum("skf,kf->ks", h, a_dst),
                              PARTS_AXIS, axis=1, tiled=True)
    q = at_dst(ad_t, _take_lanes(as_t, es))                      # [K, Eb]
    s = jax.nn.leaky_relu(q, negative_slope=slope)
    NEG = jnp.float32(-1e30)     # finite sentinel: see _ring_attend note
    m_loc = jnp.maximum(_plan_max(s, *dplan, span_d), NEG)
    m_all = jax.lax.dynamic_update_slice(
        jax.lax.pcast(jnp.full((K, NS), NEG, s.dtype), PARTS_AXIS,
                      to="varying"),
        m_loc, (0, egp.dst_base))
    # stop_gradient BEFORE pmax: shift invariance; pmax has no diff rule
    m = jax.lax.pmax(jax.lax.stop_gradient(m_all), PARTS_AXIS)   # [K, NS]
    e = jnp.exp(s - at_dst(m))                                   # [K, Eb]
    z_loc = _plan_sum(e, None, pl.dst_obi, pl.dst_edst, pl.dst_pos,
                      pl.dst_nid, span_d, "highest", True)      # [K, spanD]
    w = _keep_scale((key, rate), K, es.shape[0], e.dtype)
    u_loc = _plan_sum(e if w is None else e * w, table, pl.dst_obi,
                      pl.dst_edst, pl.dst_pos, pl.dst_nid, span_d,
                      precision, True)                    # [spanD, K, F]
    z = _scatter_to_owner(z_loc.T, egp.dst_base, NS)             # [S, K]
    u = _scatter_to_owner(u_loc.reshape(span_d, K * F),
                          egp.dst_base, NS).reshape(S, K, F)
    # _Z_GUARD (ops/edge.py): big enough to survive BOTH the XLA
    # subnormal flush AND the autodiff division transpose (0/0 on
    # edgeless rows); live rows have z >= 1 by the max shift
    zc = jnp.maximum(z, _Z_GUARD)
    out = u / zc[:, :, None]
    return out, (h, table, a_src, a_dst, egp, edge_ids, key, q >= 0, e, zc,
                 out)


def _egat_bwd(slope, precision, rate, res, gout):
    from roc_tpu.ops.edge import (_edge_contract, _int_zeros, _keep_scale,
                                  _plan_broadcast, _plan_sum)
    h, table, a_src, a_dst, egp, edge_ids, key, qpos, e, zc, out = res
    es, _ = edge_ids
    S, K, F = h.shape
    NS = table.shape[0]
    pl = egp.plans
    span_d, span_s = pl.num_rows, pl.table_rows
    du = gout / zc[:, :, None]                                   # [S, K, F]
    dz = -jnp.einsum("skf,skf->sk", gout, out) / zc              # [S, K]
    # the cotangents live on owner rows; every block's edges reference
    # arbitrary destinations, so gather them back to the global id space
    du_t = jax.lax.all_gather(du.reshape(S, K * F), PARTS_AXIS,
                              tiled=True).reshape(NS, K, F)
    dz_t = jax.lax.all_gather(dz.T, PARTS_AXIS, axis=1, tiled=True)  # [K, NS]
    w = _keep_scale((key, rate), K, es.shape[0], e.dtype)   # the fwd's mask
    # the block's destinations lie in [dst_base, dst_base + span_d): both
    # reads by destination are the dst plan's broadcast of that slice
    dplan = (pl.dst_obi, pl.dst_edst, pl.dst_pos)
    de = _edge_contract(
        jax.lax.dynamic_slice(du_t, (egp.dst_base, 0, 0), (span_d, K, F)),
        table, *dplan, pl.dst_nid, es.shape[0])                  # [K, Eb]
    if w is not None:
        de = de * w
    de = _plan_broadcast(
        jax.lax.dynamic_slice(dz_t, (0, egp.dst_base), (K, span_d)),
        *dplan, es.shape[0], de)
    dq = e * de * jnp.where(qpos, 1.0, slope)
    dadl = _scatter_to_owner(
        _plan_sum(dq, None, pl.dst_obi, pl.dst_edst, pl.dst_pos,
                  pl.dst_nid, span_d, "highest", True).T,
        egp.dst_base, NS)                                        # [S, K]
    dast = _scatter_to_owner(
        _plan_sum(dq, None, pl.src_obi, pl.src_edst, pl.src_pos,
                  pl.src_nid, span_s, "highest").T,
        egp.src_base, NS)                                        # [S, K]
    dtab = _scatter_to_owner(
        _plan_sum(e if w is None else e * w, du_t, pl.src_obi, pl.src_edst,
                  pl.src_pos, pl.src_nid, span_s, precision
                  ).reshape(span_s, K * F),
        egp.src_base, NS).reshape(S, K, F)
    dh = dtab + dast[:, :, None] * a_src[None] \
        + dadl[:, :, None] * a_dst[None]
    # per-shard partials; the trainer psums replicated param grads upstream
    da_src = jnp.einsum("sk,skf->kf", dast, h)
    da_dst = jnp.einsum("sk,skf->kf", dadl, h)
    return (dh, da_src, da_dst) + _int_zeros((egp, edge_ids, key))


_egat.defvjp(_egat_fwd, _egat_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def ring_owner_matmul(buf, fwd, bwd, S: int, precision):
    """One ring step's owner-group aggregation on the matmul plan backend:
    out[d] = Σ buf[src] over the visiting owner's edge group, scatter-free.
    ``fwd``/``bwd`` are this owner's (obi, edst, esrc) plan slices (the
    bwd is the src-sorted transpose).  AD of the gather would emit the
    serialized TPU scatter the plan backends exist to avoid."""
    from roc_tpu.ops.aggregate import _matmul_run
    return _matmul_run(buf, *fwd, S + 1, precision)[:S]  # row S: pad drop


def _rom_fwd(buf, fwd, bwd, S, precision):
    return ring_owner_matmul(buf, fwd, bwd, S, precision), (fwd, bwd)


def _rom_bwd(S, precision, res, g):
    fwd, bwd = res
    from roc_tpu.ops.aggregate import _matmul_run
    # zero row at S: pad slots (dst sentinel) gather exact zeros
    gpad = jnp.concatenate([g, jnp.zeros_like(g[:1])], axis=0)
    dbuf = _matmul_run(gpad, *bwd, S, precision)
    f0 = lambda arrs: tuple(np.zeros(a.shape, dtype=jax.dtypes.float0)  # noqa: E731
                            for a in arrs)
    return dbuf, f0(fwd), f0(bwd)


ring_owner_matmul.defvjp(_rom_fwd, _rom_bwd)


def _build_shard_plans(backend: str, srcs, dsts, S: int, table_rows: int,
                       allgather=None, storage_dtype: str = "fp32"):
    """Per-shard aggregation plans, stacked to one static program.  Under
    multihost, ``allgather`` raises the pad floors to the global chunk-count
    maxima so every process compiles the same program."""
    if backend == "binned":
        # ROC_BINNED_FLAT=1 forces the flat compacted chunk schedule for
        # every shard plan (hardware A/B lever for sweep_binned /
        # hw_revalidate; default remains choose_geometry's pick).  The
        # fused single-grid path is stripped at stacking time
        # (pad_binned_plans) — sharded plans take the flat two-pass scan.
        # Under bf16 storage the forced flat preset rides the 16-row
        # bf16-unit variant so the staging buffers halve with the wire.
        geom = None
        if os.environ.get("ROC_BINNED_FLAT") == "1":
            from roc_tpu.ops.pallas.binned import GEOM_FLAT, GEOM_FLAT_BF16
            geom = GEOM_FLAT_BF16 if storage_dtype == "bf16" else GEOM_FLAT
        plan_list = [ops.build_binned_plans(srcs[i], dsts[i], S, table_rows,
                                            geom=geom,
                                            storage_dtype=storage_dtype)
                     for i in range(len(srcs))]
        f = _allgather_floors(
            [[p.fwd.p1_blk.shape[1] for p in plan_list],
             [p.fwd.p2_obi.shape[1] for p in plan_list],
             [p.bwd.p1_blk.shape[1] for p in plan_list],
             [p.bwd.p2_obi.shape[1] for p in plan_list]], allgather)
        return ops.pad_binned_plans(plan_list, min_fwd=(f[0], f[1]),
                                    min_bwd=(f[2], f[3]))
    # Host arrays, the parts side by side (the sorts and the native chunk
    # builder release the GIL); _place_parts puts each part's block on its
    # own device.  Staging them through the default device held every
    # shard's plans on chip 0 during set-up (5.4 GiB at the products size)
    # and fetched them back.
    from concurrent.futures import ThreadPoolExecutor
    from roc_tpu import native
    from roc_tpu.ops.aggregate import build_aggregate_plans_host
    native.available()          # load the library once, before the threads
    with ThreadPoolExecutor(max(1, min(len(srcs),
                                       os.cpu_count() or 1))) as pool:
        plan_list = list(pool.map(
            lambda i: build_aggregate_plans_host(srcs[i], dsts[i], S,
                                                 table_rows),
            range(len(srcs))))
    f = _allgather_floors([[p.fwd_obi.shape[0] for p in plan_list],
                           [p.bwd_obi.shape[0] for p in plan_list]],
                          allgather)
    return ops.pad_plans(plan_list, min_fwd=f[0], min_bwd=f[1])


# Canonical home is graph.shard_load (the allgather utilities layer);
# re-exported here for the in-module call sites and backward compat.
from roc_tpu.graph.shard_load import allgather_floors as _allgather_floors  # noqa: E402,E501
from roc_tpu.ops.edge import _Z_GUARD  # noqa: E402  (guard rationale there)


def shard_graph(part: Partition, halo: Optional[HaloMaps],
                backend: str = "xla",
                precision: str = "exact",
                gat_backend: str = "xla",
                xch: tuple = ("fp32", "nearest", "plain")
                ) -> ShardedGraphData:
    if halo is not None:
        src = halo.edge_src_local
    else:
        src = part.edge_src.astype(np.int32)
    P_, S = part.num_parts, part.shard_nodes
    table_rows = S + P_ * halo.K if halo is not None else P_ * S
    plans = None
    if backend in ("matmul", "binned"):
        plans = _build_shard_plans(
            backend, src, part.edge_dst, S, table_rows,
            storage_dtype="bf16" if xch[0] == "bf16" else "fp32")
    gat_plans = None
    if gat_backend == "plan":
        from roc_tpu.ops.edge import build_gat_plans, pad_gat_plans
        with obs.span("gat_plan_build", parts=P_):
            gat_plans = pad_gat_plans(
                [build_gat_plans(src[i], part.edge_dst[i], S, table_rows)
                 for i in range(P_)])
    # host arrays: the trainer places each part's block on its own device
    # (_place_parts), and none is staged on the default one
    return ShardedGraphData(
        edge_src=np.asarray(src, np.int32),
        edge_dst=np.asarray(part.edge_dst, np.int32),
        in_degree=np.asarray(part.in_degree, np.float32),
        send_idx=None if halo is None else np.asarray(halo.send_idx),
        plans=plans,
        gat_plans=gat_plans,
        backend=backend,
        precision=precision,
        xch_dtype=xch[0], xch_round=xch[1], xch_comp=xch[2],
    )


# -- bf16 wire codec for feature exchanges ----------------------------------
# Every vertex-mode collective that moves FEATURES over ICI (halo
# all_to_all, allgather table, ring ppermute hops — and their overcommit
# variants) funnels through this encode/decode pair.  xch_dtype="bf16"
# halves the bytes per hop; the decode happens at the aggregation
# boundary, so all accumulation stays fp32.  Gradient collectives (psum)
# and the edge-mode psum_scatter reductions stay fp32: those accumulate
# IN the collective, where a bf16 wire would round partial sums, not
# inputs.

_SR_SEED = 0x0b16  # fixed fold-in base: SR pattern is deterministic per
#                    trace (reproducible runs), decorrelated across shards


@jax.custom_vjp
def _sr_bf16(x):
    """Stochastically round fp32 -> bf16: add 16 random low bits to the
    fp32 significand and truncate — unbiased (E[sr(x)] = x), so rounding
    error accumulates as noise rather than drift over deep unrolls.
    Straight-through gradient (the rounding is zero-mean; its derivative
    is 1 almost everywhere)."""
    key = jax.random.fold_in(jax.random.PRNGKey(_SR_SEED),
                             jax.lax.axis_index(PARTS_AXIS))
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    r = jax.random.bits(key, x.shape, jnp.uint16).astype(jnp.uint32)
    u = (u + r) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32).astype(jnp.bfloat16)


def _sr_fwd(x):
    return _sr_bf16(x), None


def _sr_bwd(_, g):
    return (g.astype(jnp.float32),)


_sr_bf16.defvjp(_sr_fwd, _sr_bwd)


def _wire_down(x, gd_block):
    """Encode features for an ICI exchange per the graph's static wire
    metadata.  bf16 ("nearest" or "stochastic" rounding) halves the bytes;
    "compensated" sends a (hi, lo) bf16 pair concatenated on the feature
    axis — same bytes as fp32, the parity control that exercises the bf16
    pipeline without its rounding.  fp32 (default), or an already-bf16
    compute dtype, is the identity."""
    if gd_block.xch_dtype != "bf16" or x.dtype != jnp.float32:
        return x
    if gd_block.xch_comp == "compensated":
        hi = x.astype(jnp.bfloat16)
        lo = (x - hi.astype(x.dtype)).astype(jnp.bfloat16)
        return jnp.concatenate([hi, lo], axis=-1)
    if gd_block.xch_round == "stochastic":
        return _sr_bf16(x)
    return x.astype(jnp.bfloat16)


def _wire_up(y, gd_block, dtype, H: int):
    """Decode a _wire_down-encoded exchange back to the compute ``dtype``
    at the aggregation boundary.  ``H`` is the pre-encode feature width —
    it disambiguates the compensated (2H-wide) pair from a pass-through."""
    if gd_block.xch_comp == "compensated" and y.shape[-1] == 2 * H:
        return y[..., :H].astype(dtype) + y[..., H:].astype(dtype)
    return y.astype(dtype)


def _exchange(gd_block, exchange: str, x):
    """Materialize the per-shard source table for a [S, H] local tensor:
    local rows ++ halo rows (one all_to_all) or the all-gathered tensor.
    (Ring mode never builds a table — see _ring_aggregate.)
    The device scope `roc.exchange` (obs/scopes.py) with its parts: ``down``
    (the send rows' gather and wire encoding), ``wire`` (the collective),
    ``up`` (decoding and the table's assembly); `python -m roc_tpu.obs
    report -profile` prices each."""
    H = x.shape[-1]
    if exchange == "halo":
        with scopes.scope("roc.exchange"):
            with scopes.scope("down"):
                send = _wire_down(jnp.take(x, gd_block.send_idx, axis=0),
                                  gd_block)                     # [P, K, H]
            with scopes.scope("wire"):
                recv = jax.lax.all_to_all(send, PARTS_AXIS,
                                          split_axis=0, concat_axis=0)
            with scopes.scope("up"):
                halo = _wire_up(recv, gd_block, x.dtype, H)
                return jnp.concatenate(
                    [x, halo.reshape(-1, H)], axis=0)           # [S+P*K, H]
    with scopes.scope("roc.exchange"):
        with scopes.scope("down"):
            send = _wire_down(x, gd_block)
        with scopes.scope("wire"):
            table = jax.lax.all_gather(send, PARTS_AXIS,
                                       tiled=True)              # [P*S, H]
        with scopes.scope("up"):
            return _wire_up(table, gd_block, x.dtype, H)


def _ring_aggregate(gd_block, shard_nodes: int, x, aggr: str):
    """Rotate shards around the mesh with ppermute, aggregating each
    visiting shard's contribution (see parallel/ring.py).  One [S, H]
    buffer in flight; XLA overlaps each hop with the step's aggregation."""
    P_ = gd_block.ring_src.shape[0]
    S = shard_nodes
    if aggr not in ("sum", "avg", "max", "min"):
        raise ValueError(f"unknown aggr {aggr!r}")
    p = jax.lax.axis_index(PARTS_AXIS)
    base = "sum" if aggr in ("sum", "avg") else aggr
    perm = [(i, (i + 1) % P_) for i in range(P_)]
    H = x.shape[-1]

    rp = gd_block.ring_plans

    def step(carry, k):
        buf, acc = carry
        # the carry rotates in wire format (each ppermute hop moves the
        # encoded bytes); decode at the aggregation boundary
        xb = _wire_up(buf, gd_block, x.dtype, H)
        owner = jax.lax.rem(p - k + P_, P_)       # whose rows buf holds
        if rp is not None and base == "sum":
            # plan fast path: the owner's group aggregation is one-hot
            # matmuls over its prebuilt chunk plan (fwd AND bwd)
            fwd = tuple(jnp.take(a, owner, axis=0)
                        for a in (rp.fwd_obi, rp.fwd_edst, rp.fwd_esrc))
            bwd = tuple(jnp.take(a, owner, axis=0)
                        for a in (rp.bwd_obi, rp.bwd_edst, rp.bwd_esrc))
            part = ring_owner_matmul(
                xb, fwd, bwd, S,
                ops.matmul_precision(gd_block.precision))
            acc = acc + part
            buf = jax.lax.ppermute(buf, PARTS_AXIS, perm)
            return (buf, acc), None
        es = jnp.take(gd_block.ring_src, owner, axis=0)       # [Eo]
        ed = jnp.take(gd_block.ring_dst, owner, axis=0)       # [Eo], pad=S
        gathered = jnp.take(xb, es, axis=0)
        if base == "sum":
            part = jax.ops.segment_sum(gathered, ed, num_segments=S + 1,
                                       indices_are_sorted=True)[:S]
        elif base == "max":
            # raw segment op: per-step empties must stay -inf so the
            # cross-step maximum cannot be polluted by a 0 fill
            part = jax.ops.segment_max(gathered, ed, num_segments=S + 1,
                                       indices_are_sorted=True)[:S]
        else:
            part = jax.ops.segment_min(gathered, ed, num_segments=S + 1,
                                       indices_are_sorted=True)[:S]
        if base == "sum":
            acc = acc + part
        elif base == "max":
            acc = jnp.maximum(acc, part)
        else:
            acc = jnp.minimum(acc, part)
        buf = jax.lax.ppermute(buf, PARTS_AXIS, perm)
        return (buf, acc), None

    # pcast: the scan carry must share x's device-varying vma annotation
    # under shard_map.  NOT the `+ 0 * x` trick — with a non-finite init
    # (max/min) that creates a gradient edge into x through which a
    # non-finite cotangent can NaN-poison dx (bug found in _ring_attend).
    init = jax.lax.pcast(
        jnp.full((S, H), {"sum": 0.0, "max": -jnp.inf, "min": jnp.inf}
                 [base], x.dtype), PARTS_AXIS, to="varying")
    (_, acc), _ = jax.lax.scan(step, (_wire_down(x, gd_block), init),
                               jnp.arange(P_))
    if aggr == "avg":
        acc = ops.divide_by_degree(acc, gd_block.in_degree)
    if base in ("max", "min"):
        # rows with no in-edges anywhere stayed at the segment identity:
        # zero exactly those (convention shared with ops.scatter_gather;
        # NaN from genuine divergence must still propagate)
        empty = jnp.isneginf(acc) if base == "max" else jnp.isposinf(acc)
        acc = jnp.where(empty, 0, acc)
    return acc


def _edge_attend(gd_block, h, a_src, a_dst, slope: float, drop=None):
    """GAT attention under edge sharding — the last cell of the
    model × distribution matrix.

    The softmax couples edges of one destination across blocks (a vertex's
    in-edges may be split mid-vertex — that is edge sharding's point), so
    the per-destination max and normalizer become collectives: each block
    scores its own Eb edges against the all-gathered table, block-local
    segment maxima combine with one `pmax`, and the shifted exp sums /
    weighted sums reduce onto owners with `psum_scatter` — the same
    all_gather + psum_scatter shape as the edge-mode sum path, plus one
    [NS, K] pmax for the shift.  Work is exactly Eb edges per device under
    ANY skew (the property the mode exists for).  Pad edges land on pad
    node rows (in-range, masked by the mask=NONE convention downstream).

    Backward is jax autodiff: the segment ops transpose into TPU scatters,
    so on hardware this is the correctness path, not the fast path — the
    plan treatment (windowed per-block schedules like EdgePlans) is the
    known follow-up if edge-sharded attention ever becomes hot.
    ``drop`` = (key, rate): attention dropout over this block's Eb edges
    (ops.edge.attention_keep), on the weighted sum only.
    """
    from roc_tpu.ops.edge import _keep_scale
    S, K, F = h.shape[0], h.shape[1], h.shape[2]
    table = jax.lax.all_gather(
        h.reshape(S, K * F), PARTS_AXIS, tiled=True).reshape(-1, K, F)
    NS = table.shape[0]
    es, ed = gd_block.edge_src, gd_block.edge_dst   # [Eb] padded-global
    # project locally ([S, K] einsums), gather the small score vectors —
    # projecting the gathered [NS, K, F] table would repeat all P shards'
    # flops on every device
    as_t = jax.lax.all_gather(jnp.einsum("nkf,kf->nk", h, a_src),
                              PARTS_AXIS, tiled=True)   # [NS, K]
    ad_t = jax.lax.all_gather(jnp.einsum("nkf,kf->nk", h, a_dst),
                              PARTS_AXIS, tiled=True)   # [NS, K]
    s = jax.nn.leaky_relu(
        jnp.take(ad_t, ed, axis=0) + jnp.take(as_t, es, axis=0),
        negative_slope=slope)                        # [Eb, K]
    NEG = jnp.float32(-1e30)   # finite sentinel: see _ring_attend note
    m_part = jax.ops.segment_max(s, ed, num_segments=NS,
                                 indices_are_sorted=True)
    m_part = jnp.maximum(m_part, NEG)
    # stop_gradient BEFORE pmax: the shift carries no gradient (softmax
    # shift invariance), and pmax has no differentiation rule anyway
    m = jax.lax.pmax(jax.lax.stop_gradient(m_part),
                     PARTS_AXIS)                    # [NS, K] global max
    e = jnp.exp(s - jnp.take(m, ed, axis=0))        # [Eb, K]
    z_part = jax.ops.segment_sum(e, ed, num_segments=NS,
                                 indices_are_sorted=True)
    g = jnp.take(table, es, axis=0)                 # [Eb, K, F]
    w = _keep_scale(drop, K, es.shape[0], e.dtype)
    ew = e if w is None else e * w.T
    u_part = jax.ops.segment_sum(g * ew[:, :, None], ed, num_segments=NS,
                                 indices_are_sorted=True)
    z = jax.lax.psum_scatter(z_part, PARTS_AXIS, scatter_dimension=0,
                             tiled=True)            # [S, K] owner rows
    u = jax.lax.psum_scatter(u_part.reshape(NS, K * F), PARTS_AXIS,
                             scatter_dimension=0,
                             tiled=True).reshape(S, K, F)
    # _Z_GUARD (ops/edge.py): big enough to survive BOTH the XLA
    # subnormal flush AND the autodiff division transpose (0/0 on
    # edgeless rows); live rows have z >= 1 by the max shift
    return u / jnp.maximum(z, _Z_GUARD)[:, :, None]


def _ring_attend(gd_block, S: int, h, a_src, a_dst, slope: float,
                 drop=None):
    """GAT attention in ring mode — LITERAL ring attention on the vertex/
    context axis (SURVEY §5.7: the vertex-shard axis IS the sequence axis).

    No source table is ever materialized: shards rotate with ppermute and
    each step folds the visiting owner's edge group into an ONLINE softmax
    (flash/ring-attention recurrence): running per-destination max m,
    normalizer z, and unnormalized output u, rescaled by exp(m_old−m_new)
    as the max tightens.  Peak memory is two [S, K, F] buffers + the
    accumulators — the property that lets ring attention scale to
    contexts (here: graphs) whose gathered tables would not fit.

    The per-step body is rematerialized (jax.checkpoint) so autodiff
    recomputes each owner group's scores instead of stacking P steps of
    residuals.  Pad edges carry dst = S (masked); destinations with no
    in-edges anywhere keep z = 0 and emit 0 (same convention as the
    table-based paths).  ``drop`` = (key, rate): attention dropout, one
    mask per visiting owner group (the key folded with the owner's index;
    the rematerialized step redraws it), on the weighted sum only.
    """
    from roc_tpu.ops.edge import _keep_scale
    P_ = gd_block.ring_src.shape[0]
    K, F = h.shape[1], h.shape[2]
    p = jax.lax.axis_index(PARTS_AXIS)
    perm = [(i, (i + 1) % P_) for i in range(P_)]
    ad_l = jnp.einsum("nkf,kf->nk", h, a_dst)             # [S, K]
    ad_pad = jnp.concatenate([ad_l, jnp.zeros((1, K), ad_l.dtype)])
    # "No mass yet" sentinel is a FINITE large negative, not -inf: every
    # arising exp(sentinel - x) underflows cleanly to 0 in fwd AND bwd,
    # whereas -inf sentinels produce -inf - -inf = NaN in where-branch
    # forwards whose vjps then feed 0 * NaN into the scan-carry gradient
    # (the standard where-NaN-grad trap; first hit here, hence the note).
    NEG = jnp.float32(-1e30)

    def step(carry, k):
        buf, m, z, u = carry
        # wire-format carry: decode the visiting shard at the boundary
        hb = _wire_up(buf, gd_block, h.dtype, F)
        owner = jax.lax.rem(p - k + P_, P_)
        es = jnp.take(gd_block.ring_src, owner, axis=0)   # [Eo]
        ed = jnp.take(gd_block.ring_dst, owner, axis=0)   # [Eo], pad = S
        as_t = jnp.einsum("nkf,kf->nk", hb, a_src)        # [S, K]
        s = jax.nn.leaky_relu(
            jnp.take(ad_pad, ed, axis=0) + jnp.take(as_t, es, axis=0),
            negative_slope=slope)                          # [Eo, K]
        # pad rows must not move the max: sink them to the sentinel
        s = jnp.where((ed == S)[:, None], NEG, s)
        m_step = jax.ops.segment_max(s, ed, num_segments=S + 1,
                                     indices_are_sorted=True)[:S]
        m_step = jnp.maximum(m_step, NEG)      # empty segments: -inf → NEG
        m_new = jnp.maximum(m, m_step)
        m_new = jax.lax.stop_gradient(m_new)   # softmax shift-invariance
        shift = jnp.concatenate(
            [m_new, jnp.zeros((1, K), m_new.dtype)])[ed]
        e = jnp.exp(s - shift)     # pads: exp(NEG - 0) underflows to 0
        z_step = jax.ops.segment_sum(e, ed, num_segments=S + 1,
                                     indices_are_sorted=True)[:S]
        g = jnp.take(hb, es, axis=0)                      # [Eo, K, F]
        w = None if drop is None else _keep_scale(
            (jax.random.fold_in(drop[0], owner), drop[1]), K, es.shape[0],
            e.dtype)
        ew = e if w is None else e * w.T
        u_step = jax.ops.segment_sum(g * ew[:, :, None], ed,
                                     num_segments=S + 1,
                                     indices_are_sorted=True)[:S]
        # rescale prior mass to the tightened max; no-mass-yet rows have
        # m == NEG and m_new either still NEG (scale exp(0)=1 on zero
        # mass — harmless) or real (scale underflows to 0)
        scale = jnp.exp(m - m_new)
        z = z * scale + z_step
        u = u * scale[:, :, None] + u_step
        buf = jax.lax.ppermute(buf, PARTS_AXIS, perm)
        return (buf, m_new, z, u), None

    # carries must share h's device-varying vma; pcast annotates without
    # creating a (zero-valued but NaN-propagating) gradient edge into h
    # the way the `+ 0 * h` trick would
    m0 = jax.lax.pcast(jnp.full((S, K), NEG), PARTS_AXIS, to="varying")
    z0 = jax.lax.pcast(jnp.zeros((S, K)), PARTS_AXIS, to="varying")
    u0 = jax.lax.pcast(jnp.zeros((S, K, F)), PARTS_AXIS, to="varying")
    (_, _, z, u), _ = jax.lax.scan(  # ring-step remat keeps the rotating
        # buffer out of the residual set  # roclint: allow(remat) — ring-step remat keeps the rotating buffer out of the residual set
        jax.checkpoint(step, prevent_cse=False),
        (_wire_down(h, gd_block), m0, z0, u0), jnp.arange(P_))
    # _Z_GUARD (ops/edge.py): big enough to survive BOTH the XLA
    # subnormal flush AND the autodiff division transpose (0/0 on
    # edgeless rows); live rows have z >= 1 by the max shift
    return u / jnp.maximum(z, _Z_GUARD)[:, :, None]


def _shard_gctx(gd_block, shard_nodes: int, exchange: str) -> GraphCtx:
    """Build the per-shard GraphCtx (runs inside shard_map; gd_block fields
    already have the leading parts-axis block squeezed)."""
    from roc_tpu.train.driver import pallas_interpret
    edge_src, edge_dst = gd_block.edge_src, gd_block.edge_dst
    interp = pallas_interpret()

    if gd_block.mode == "edge":
        def aggregate_edge(x, aggr):
            # Every device sums exactly Eb edges into the padded-global id
            # space (dst ascending there), then one reduce-scatter lands
            # each vertex shard's rows on its owner.  Work balance is exact
            # even for hub vertices; comms are O(N) (all_gather + scatter) —
            # the trade documented in docs/PERF.md.
            if aggr not in ("sum", "avg"):
                raise ValueError(
                    f"edge-sharded aggregation supports sum/avg, not {aggr}"
                    " (use vertex sharding for max/min models)")
            if gd_block.backend == "binned" and gd_block.plans is not None:
                out = edge_aggregate_binned(x, gd_block.plans, interp,
                                            gd_block.precision)
            elif gd_block.plans is not None:    # matmul backend: scatter-free
                out = edge_aggregate_matmul(
                    x, gd_block.plans,
                    ops.matmul_precision(gd_block.precision))
            else:
                table = jax.lax.all_gather(x, PARTS_AXIS,
                                           tiled=True)  # [P*S, H]
                partial = ops.scatter_gather(table, edge_src, edge_dst,
                                             table.shape[0], "sum")
                out = jax.lax.psum_scatter(partial, PARTS_AXIS,
                                           scatter_dimension=0, tiled=True)
            if aggr == "avg":   # all in-edges of a vertex => count = degree
                out = ops.divide_by_degree(out, gd_block.in_degree)
            return out

        def attend_edge(h, a_src, a_dst, slope, drop=None):
            if gd_block.gat_plans is not None:
                return edge_gat_attend(
                    h, a_src, a_dst, gd_block.gat_plans,
                    (edge_src, edge_dst),
                    slope, ops.matmul_precision(gd_block.precision), drop)
            return _edge_attend(gd_block, h, a_src, a_dst, slope, drop)

        return GraphCtx(aggregate=aggregate_edge,
                        in_degree=gd_block.in_degree, attend=attend_edge)

    if gd_block.mode == "ring":
        def aggregate_ring(x, aggr):
            return _ring_aggregate(gd_block, shard_nodes, x, aggr)

        def attend_ring(h, a_src, a_dst, slope, drop=None):
            return _ring_attend(gd_block, shard_nodes, h, a_src, a_dst,
                                slope, drop)

        return GraphCtx(aggregate=aggregate_ring,
                        in_degree=gd_block.in_degree, attend=attend_ring)

    def aggregate(x, aggr):
        table = _exchange(gd_block, exchange, x)
        return _vertex_aggregate(table, gd_block, shard_nodes, aggr, interp)

    def attend(h, a_src, a_dst, slope, drop=None):
        kk, fd = h.shape[1], h.shape[2]
        table = _exchange(gd_block, exchange,
                          h.reshape(h.shape[0], kk * fd))
        return _vertex_attend(table, gd_block, shard_nodes, h, a_src,
                              a_dst, slope, drop)

    return GraphCtx(aggregate=aggregate, in_degree=gd_block.in_degree,
                    attend=attend)


def _part_view(tree_, j: int):
    """Select local part ``j`` from a [k, ...]-stacked per-device block."""
    return jax.tree.map(lambda a: a[j], tree_)


def _vertex_aggregate(table, gdj, S: int, aggr: str, interp: bool):
    """One part's vertex-mode aggregation over its source table — the
    single backend dispatch shared by _shard_gctx (k=1) and
    _shard_gctx_over (k parts stacked per device).  avg rides the sum
    fast path: per-shard in_degree is the live in-edge count (pad rows
    carry 1, and their sums are zero anyway)."""
    if gdj.plans is not None and aggr in ("sum", "avg"):
        if gdj.backend == "binned":
            out = ops.scatter_gather_binned(table, gdj.plans, interp,
                                            gdj.precision)
        else:
            out = ops.scatter_gather_matmul(
                table, gdj.plans, S, table.shape[0],
                ops.matmul_precision(gdj.precision))
        if aggr == "avg":
            out = ops.divide_by_degree(out, gdj.in_degree)
        return out
    return ops.scatter_gather(table, gdj.edge_src, gdj.edge_dst, S, aggr)


def _vertex_attend(table_flat, gdj, S: int, h_local, a_src, a_dst, slope,
                   drop=None):
    """One part's GAT attention (plan backend when built, else dense/
    chunked) — shared by both vertex gctx builders.  ``table_flat`` is the
    exchanged [T, K*F] source table for this part."""
    kk, fd = h_local.shape[1], h_local.shape[2]
    tab = table_flat.reshape(-1, kk, fd)
    if gdj.gat_plans is not None:
        from roc_tpu.ops.edge import gat_attend_plan
        return gat_attend_plan(h_local, tab, a_src, a_dst, gdj.gat_plans,
                               (gdj.edge_src, gdj.edge_dst), slope,
                               ops.matmul_precision(gdj.precision), drop)
    return ops.gat_attend(h_local, tab, gdj.edge_src, gdj.edge_dst, S,
                          a_src, a_dst, slope, drop)


def _overcommit_tables(gd_block, k: int, S: int, exchange: str, x):
    """Per-local-part source tables when k parts share one device (the
    reference's parts>GPUs overcommit, gnn.cc:61-63).  ``x`` is [k*S, H]
    (this device's k shards stacked in part order).

    halo: ONE all_to_all moves every (sender part i, receiver part j) halo
    block between devices; receiver part j's table is its own S rows ++
    the [P*K] halo rows reassembled in global part order — exactly the
    layout edge_src_local/plans were built against, so the per-part
    aggregation code is unchanged.  allgather: one table serves all k
    parts (padded-global ids index [P*S] in device-major == part order)."""
    H = x.shape[-1]
    if exchange != "halo":
        return [_exchange(gd_block, exchange, x)] * k
    sidx = gd_block.send_idx                 # [k_i, P, K] (i = sender)
    k_, P_, K = sidx.shape
    D = P_ // k
    # [D_to, k_i(sender here), k_j(receiver there), K] with stacked-row
    # offsets: send_idx values are local to sender part i
    idx = sidx.reshape(k, D, k, K).transpose(1, 0, 2, 3) \
        + (jnp.arange(k, dtype=sidx.dtype) * S)[None, :, None, None]
    with scopes.scope("roc.exchange", "down"):
        send = _wire_down(jnp.take(x, idx.reshape(D, k * k * K), axis=0),
                          gd_block)
    with scopes.scope("roc.exchange", "wire"):
        recv = jax.lax.all_to_all(send, PARTS_AXIS, split_axis=0,
                                  concat_axis=0)
    with scopes.scope("roc.exchange", "up"):
        recv = _wire_up(recv, gd_block, x.dtype, H)
    recv = recv.reshape(D, k, k, K, H)       # [from-dev, from-part, j, K, H]
    tables = []
    for j in range(k):
        halo = recv[:, :, j].reshape(P_ * K, H)   # global part order
        tables.append(jnp.concatenate([x[j * S:(j + 1) * S], halo], axis=0))
    return tables


def _shard_gctx_over(gd_block, S: int, k: int, exchange: str) -> GraphCtx:
    """Overcommit (k>1) counterpart of :func:`_shard_gctx`: one exchange
    for the device's stacked block, then the standard per-part aggregation
    over each part's own plan/edge slice, concatenated back."""
    from roc_tpu.train.driver import pallas_interpret
    interp = pallas_interpret()
    assert gd_block.mode == "vertex", "overcommit is vertex-mode only"

    def aggregate(x, aggr):
        tables = _overcommit_tables(gd_block, k, S, exchange, x)
        return jnp.concatenate(
            [_vertex_aggregate(tables[j], _part_view(gd_block, j), S, aggr,
                               interp) for j in range(k)], axis=0)

    def attend(h, a_src, a_dst, slope, drop=None):
        kk, fd = h.shape[1], h.shape[2]
        tables = _overcommit_tables(gd_block, k, S, exchange,
                                    h.reshape(h.shape[0], kk * fd))
        # one mask per local part: the device's key folded with j
        drops = [None if drop is None
                 else (jax.random.fold_in(drop[0], j), drop[1])
                 for j in range(k)]
        return jnp.concatenate(
            [_vertex_attend(tables[j], _part_view(gd_block, j), S,
                            h[j * S:(j + 1) * S], a_src, a_dst, slope,
                            drops[j])
             for j in range(k)], axis=0)

    return GraphCtx(aggregate=aggregate,
                    in_degree=gd_block.in_degree.reshape(-1), attend=attend)


def _plan_chunks(plans, direction: str) -> tuple:
    """(chunks a part, slots a chunk) of one direction of a stacked
    vertex-mode plan set, from its static shapes: the matmul backend's
    [P, C, EB] gather ids, the binned backend's [P, G, C1, CH] phase-1
    rows (every edge sits in one slot of either)."""
    if isinstance(plans, ops.BinnedPlans):
        _, G, C1, width = getattr(plans, direction).p1_srcl.shape
        return G * C1, width
    _, chunks, width = getattr(plans, direction + "_esrc").shape
    return chunks, width


def _padded_max_tax(meta) -> float:
    """E_padded/E_live - 1: what every shard overpays because all shards run
    the padded-max edge count (the skew cost of vertex partitioning)."""
    live = np.asarray(meta.num_edges_valid, np.float64)
    return meta.shard_edges * meta.num_parts / max(live.sum(), 1.0) - 1.0


def _squeeze_gd(gd: ShardedGraphData) -> ShardedGraphData:
    """Drop the size-1 parts-axis block dim that shard_map leaves on each
    per-device block."""
    return jax.tree.map(lambda a: a[0], gd)


class SpmdTrainer(BaseTrainer):
    """Multi-chip trainer: same Trainer interface, mesh underneath."""

    def _place_nodes(self, part_loader, spec: NamedSharding, row_shape=()):
        """Assemble a global node tensor from per-part host blocks, placing
        each part directly on its device (k consecutive parts stacked per
        device under overcommit).  Under `jax.distributed` each process
        only loads/places the parts of its addressable devices (possibly
        none — row_shape supplies the trailing dims so the global shape
        never depends on local shards existing)."""
        devices = list(self.mesh.devices.reshape(-1))
        pidx = jax.process_index()
        k = self.k
        shards = [jax.device_put(
            np.concatenate([part_loader(d * k + i) for i in range(k)])
            if k > 1 else part_loader(d), dev)
            for d, dev in enumerate(devices) if dev.process_index == pidx]
        global_shape = (self.part.num_parts * self.part.shard_nodes,) \
            + tuple(row_shape)
        return jax.make_array_from_single_device_arrays(
            global_shape, spec, shards)

    def _local_part_ids(self):
        """Parts whose devices this process owns.  The halo exchange and
        plan-count allgather assume parts are process-major contiguous
        (jax.devices() orders devices by process) — asserted here."""
        devices = list(self.mesh.devices.reshape(-1))
        pidx = jax.process_index()
        ids = [p for p, d in enumerate(devices) if d.process_index == pidx]
        L = len(devices) // jax.process_count()
        assert ids == list(range(pidx * L, pidx * L + L)), (
            f"non-contiguous local parts {ids}: mesh device order is not "
            "process-major")
        return ids

    def _xch_meta(self) -> tuple:
        """(xch_dtype, xch_round, xch_comp) wire metadata for the feature
        exchanges, from the config's bf16-storage knobs.  Edge-shard mode
        is excluded: its psum_scatter reductions accumulate in-network,
        where a bf16 wire would round partial sums rather than inputs."""
        cfg = self.config
        if not cfg.bf16_storage or self._use_edge_shard:
            return ("fp32", "nearest", "plain")
        return ("bf16", cfg.bf16_rounding, cfg.bf16_exchange)

    def _build_graph_full(self, backend: str,
                          gat_backend: str = "xla") -> ShardedGraphData:
        """Single-host path: whole graph in memory, all P parts built."""
        cfg, ds = self.config, self.dataset
        assert self.part is not None, "_setup partitions before building"
        if self._use_edge_shard:
            self.halo = None
            eb_src, eb_dst = edge_block_arrays(ds.graph, self.part.meta)
            assert self.part.num_parts * self.part.shard_nodes < 2**31
            plans = None
            if backend == "binned":
                plans = build_edge_binned_plans(
                    ds.graph, self.part.meta, fwd_arrays=(eb_src, eb_dst))
                if plans is None:
                    if jax.process_index() == 0:
                        print("# -edge-shard binned: block windows fail "
                              "the occupancy bound; using matmul",
                              file=sys.stderr)
                    backend = "matmul"
                    self._backend_why = "edge_blocks_fail_binned_occupancy"
            if backend == "matmul":
                # Windowed one-hot plans per block (TPU would otherwise
                # serialize each block's scatter); backward rides the
                # src-sorted transposed blocks via edge_aggregate_matmul's
                # custom vjp.
                plans = build_edge_plans(ds.graph, self.part.meta,
                                         fwd_arrays=(eb_src, eb_dst))
            gat_plans = None
            if gat_backend == "plan":
                with obs.span("gat_plan_build", mode="edge"):
                    gat_plans = build_edge_gat_plans(
                        ds.graph, self.part.meta,
                        fwd_arrays=(eb_src, eb_dst))
            return ShardedGraphData(
                edge_src=jnp.asarray(eb_src, jnp.int32),
                edge_dst=jnp.asarray(eb_dst, jnp.int32),
                in_degree=jnp.asarray(self.part.in_degree, jnp.float32),
                send_idx=None, plans=plans, gat_plans=gat_plans,
                backend=backend, mode="edge",
                precision=cfg.aggregate_precision)
        if self._exchange_mode == "ring":
            from roc_tpu.parallel.ring import build_ring_groups, \
                build_ring_plans
            self.halo = None
            rm = build_ring_groups(self.part)
            ring_plans = None
            if backend == "matmul":
                rp = build_ring_plans(rm, self.part.shard_nodes)
                ring_plans = jax.tree.map(jnp.asarray, rp)
            xd, xr, xc = self._xch_meta()
            return ShardedGraphData(
                edge_src=jnp.asarray(self.part.edge_src, jnp.int32),
                edge_dst=jnp.asarray(self.part.edge_dst, jnp.int32),
                in_degree=jnp.asarray(self.part.in_degree, jnp.float32),
                send_idx=None,
                ring_src=jnp.asarray(rm.ring_src),
                ring_dst=jnp.asarray(rm.ring_dst),
                plans=None, ring_plans=ring_plans, backend=backend,
                mode="ring", precision=cfg.aggregate_precision,
                xch_dtype=xd, xch_round=xr, xch_comp=xc)
        if self._exchange_mode == "halo":
            with obs.span("halo_build", parts=self.part.num_parts):
                self.halo = build_halo_maps(self.part)
        else:
            self.halo = None
        if backend == "matmul" and cfg.aggregate_backend == "auto":
            # The global viability check (BaseTrainer's resolve) sees the
            # whole-graph geometry; the per-shard plan only spans the halo
            # table (S own rows + P*K received), which for locality-heavy
            # partitions is far smaller than P*S — ask the same policy
            # again there before settling for matmul, and keep its reason.
            from roc_tpu.train.driver import resolve_backend_why
            S_ = self.part.shard_nodes
            table_rows = S_ + self.part.num_parts * self.halo.K \
                if self.halo is not None else self.part.num_parts * S_
            backend, self._backend_why = resolve_backend_why(
                "auto", int(self.part.num_edges_valid.max()), S_, table_rows)
        with obs.span("plan_build", backend=backend,
                      parts=self.part.num_parts):
            return shard_graph(self.part, self.halo, backend,
                               cfg.aggregate_precision,
                               gat_backend=gat_backend,
                               xch=self._xch_meta())

    def _build_graph_perhost(self, backend: str,
                             gat_backend: str = "xla") -> ShardedGraphData:
        """Pod-scale path: this process reads only its parts' `.lux` byte
        ranges and builds only local rows of every [P, ...] array (see
        roc_tpu/graph/shard_load.py).  Returned leaves have L rows; the
        caller places them per device via _place_parts."""
        from roc_tpu.graph import lux, shard_load
        cfg = self.config
        assert cfg.filename, "-perhost needs -file (an on-disk .lux dataset)"
        path = cfg.filename + lux.LUX_SUFFIX
        nproc = jax.process_count()
        ag = shard_load.jax_allgather() if nproc > 1 \
            else shard_load.single_process_allgather
        meta = shard_load.meta_from_lux(path, cfg.num_parts,
                                        jax.process_index(), ag)
        self.part = meta
        part_ids = self._local_part_ids()
        if self._use_edge_shard:
            # Edge-shard × perhost (round 4, the last loading × mode cell):
            # the dst-sorted edge list IS the on-disk cols section, so the
            # exactly-edge-balanced fwd blocks are plain byte-range reads;
            # the src-sorted bwd blocks read the transposed sidecar
            # (prefix + TLUX_SUFFIX, written offline by lux.write_transpose
            # — the same preprocessing pattern as *.add_self_edge.lux
            # itself).  Only static shapes (window spans, chunk counts) are
            # allgathered.
            self.halo = None
            f_gat, f_sct = shard_load.load_edge_blocks(path, meta, part_ids)
            assert meta.num_parts * meta.shard_nodes < 2**31
            if backend == "binned":
                if jax.process_index() == 0:
                    print("# -edge-shard -perhost rides the matmul "
                          "windowed plans (binned block windows need the "
                          "whole graph's occupancy stats)", file=sys.stderr)
                backend = "matmul"
                self._backend_why = "edge_shard_perhost_rides_matmul"
            plans = None
            if backend == "matmul":
                # bwd (src-sorted) blocks come from the transposed sidecar
                tpath = cfg.filename + lux.TLUX_SUFFIX
                if not os.path.exists(tpath):
                    raise FileNotFoundError(
                        f"-edge-shard -perhost needs the transposed "
                        f"sidecar {tpath}; generate it once with "
                        f"roc_tpu.graph.lux.write_transpose(prefix, graph)"
                        f" or tools/convert.py --with-transpose")
                if os.path.getmtime(tpath) < os.path.getmtime(path):
                    # same freshness rule as the .feats.bin cache
                    # (lux._cache_fresh): a regenerated graph with equal
                    # N/E would otherwise pair new fwd blocks with stale
                    # bwd blocks — silently wrong gradients
                    raise ValueError(
                        f"{tpath} is older than {path}: regenerate the "
                        f"transposed sidecar (tools/convert.py "
                        f"--with-transpose or lux.write_transpose)")
                b_gat, b_sct = shard_load.load_edge_blocks(tpath, meta,
                                                           part_ids)
                plans = build_edge_plans_arrays(meta, f_gat, f_sct, b_gat,
                                                b_sct, allgather=ag)
            gat_plans = None
            if gat_backend == "plan":
                with obs.span("gat_plan_build", mode="edge"):
                    gat_plans = build_edge_gat_plans_arrays(
                        meta, f_gat, f_sct, allgather=ag)
            return ShardedGraphData(
                edge_src=jnp.asarray(f_gat, jnp.int32),
                edge_dst=jnp.asarray(f_sct, jnp.int32),
                in_degree=jnp.asarray(
                    shard_load.load_local_degrees(path, meta, part_ids),
                    jnp.float32),
                send_idx=None, plans=plans, gat_plans=gat_plans,
                backend=backend, mode="edge",
                precision=cfg.aggregate_precision)
        local = shard_load.load_local_shards(path, meta, part_ids)
        if self._exchange_mode == "ring":
            # Ring × perhost (closes a round-3 documented fallback): every
            # ring ingredient is LOCAL — a shard's edges grouped by source
            # owner come straight from its own byte-range slice; only the
            # static shapes (group pad width Eo, plan chunk counts) need
            # cross-process agreement, via the same allgathered floors as
            # the halo path.
            from roc_tpu.parallel.ring import (build_ring_groups_arrays,
                                               build_ring_plans)
            self.halo = None
            P_, S = meta.num_parts, meta.shard_nodes
            rm = build_ring_groups_arrays(local.edge_src, local.edge_dst,
                                          P_, S, allgather=ag)
            ring_plans = None
            if backend == "matmul":
                rp = build_ring_plans(rm, S, allgather=ag)
                ring_plans = jax.tree.map(jnp.asarray, rp)
            xd, xr, xc = self._xch_meta()
            return ShardedGraphData(
                edge_src=jnp.asarray(local.edge_src, jnp.int32),
                edge_dst=jnp.asarray(local.edge_dst, jnp.int32),
                in_degree=jnp.asarray(local.in_degree, jnp.float32),
                send_idx=None,
                ring_src=jnp.asarray(rm.ring_src),
                ring_dst=jnp.asarray(rm.ring_dst),
                plans=None, ring_plans=ring_plans, backend=backend,
                mode="ring", precision=cfg.aggregate_precision,
                xch_dtype=xd, xch_round=xr, xch_comp=xc)
        lhalo = shard_load.build_halo_local(meta, local, ag) \
            if self._exchange_mode == "halo" else None
        self.halo = lhalo
        P_, S = meta.num_parts, meta.shard_nodes
        src = lhalo.edge_src_local if lhalo is not None else local.edge_src
        table_rows = S + P_ * lhalo.K if lhalo is not None else P_ * S
        xd, xr, xc = self._xch_meta()
        plans = None
        if backend in ("matmul", "binned"):
            plans = _build_shard_plans(
                backend, src, local.edge_dst, S, table_rows, allgather=ag,
                storage_dtype="bf16" if xd == "bf16" else "fp32")
        gat_plans = None
        if gat_backend == "plan":
            from roc_tpu.ops.edge import build_gat_plans, pad_gat_plans
            with obs.span("gat_plan_build", parts=len(part_ids)):
                local_plans = [build_gat_plans(src[i], local.edge_dst[i], S,
                                               table_rows)
                               for i in range(len(part_ids))]
                f = _allgather_floors(
                    [[p.dst_obi.shape[0] for p in local_plans],
                     [p.src_obi.shape[0] for p in local_plans]], ag)
                gat_plans = pad_gat_plans(local_plans, min_d=f[0],
                                          min_s=f[1])
        return ShardedGraphData(
            edge_src=jnp.asarray(src, jnp.int32),
            edge_dst=jnp.asarray(local.edge_dst, jnp.int32),
            in_degree=jnp.asarray(local.in_degree, jnp.float32),
            send_idx=None if lhalo is None else jnp.asarray(lhalo.send_idx),
            plans=plans,
            gat_plans=gat_plans,
            backend=backend,
            precision=cfg.aggregate_precision,
            xch_dtype=xd, xch_round=xr, xch_comp=xc)

    def _place_parts(self, gd: ShardedGraphData,
                     spec: NamedSharding) -> ShardedGraphData:
        """Assemble global [P, ...] graph arrays from per-part host blocks,
        placing each part's block directly on its device (no host ever
        holds a full array; the leading axis is the 'parts' axis)."""
        devices = list(self.mesh.devices.reshape(-1))
        part_ids = self._local_part_ids()
        P_ = self.part.num_parts

        k = self.k

        def place(leaf):
            arr = np.asarray(leaf)
            if k > 1:          # single-process overcommit: all P parts here
                shards = [jax.device_put(arr[d * k:(d + 1) * k], dev)
                          for d, dev in enumerate(devices)]
                return jax.make_array_from_single_device_arrays(
                    (P_,) + arr.shape[1:], spec, shards)
            local = arr if arr.shape[0] == len(part_ids) else arr[part_ids]
            shards = [jax.device_put(local[i][None], devices[p])
                      for i, p in enumerate(part_ids)]
            return jax.make_array_from_single_device_arrays(
                (P_,) + local.shape[1:], spec, shards)

        return jax.tree.map(place, gd)

    def _wire_geometry(self, gd: ShardedGraphData) -> tuple:
        """(the exchange as obs.channel counts it, halo rows a peer sends):
        edge-sharded blocks all_gather their rows whatever -exchange says;
        K is 0 without a send map.  One place for the step's in-graph
        wire_bytes and exchange_info's per-epoch figures."""
        on_wire = "allgather" if gd.mode == "edge" else self._exchange_mode
        K = int(gd.send_idx.shape[-1]) if gd.send_idx is not None else 0
        return on_wire, K

    def exchange_info(self) -> dict:
        """What this trainer resolved for its partition and its exchange,
        from static geometry alone (no device work): the mode ("halo" |
        "allgather" | "ring", or "edge" under -edge-shard); the feature
        rows and bytes ONE device puts on the wire in a training epoch
        (one exchange an aggregation forward and its transpose backward,
        by the wire dtype; obs.channel.exchange_rows has the per-mode
        count); the halo rows a peer sends (K, padded) and their share of
        a shard's table (P*K of S + P*K rows); the share of live edges
        whose source another part owns (the partition's edge cut); the
        padded-max tax (SURVEY section 7: every shard runs the fullest
        shard's edge count, so skew becomes padding; the reference
        balances edges because kernel work follows them, gnn.cc:806-829);
        the live edges of the emptiest and fullest shard; for a vertex-
        sharded plan backend the chunks a part's forward and backward
        plan hold (after padding to the common count) and the share of
        their slots the fullest part's live edges fill; the aggregation
        backend with the reason the policy gave where it decided
        (driver.resolve_backend_why, or the exchange that overrode it)."""
        m, gd = self.part, self.gdata
        P_, S = int(m.num_parts), int(m.shard_nodes)
        mode = "edge" if gd.mode == "edge" else self._exchange_mode
        on_wire, K = self._wire_geometry(gd)
        widths = self._aggregate_widths()
        rows = 2 * len(widths) * obs.channel.exchange_rows(
            on_wire, P_, S, send_cols=K)
        nbytes = 2 * obs.channel.wire_bytes_per_step(
            on_wire, P_, S, widths, send_cols=K, xch_dtype=gd.xch_dtype,
            xch_comp=gd.xch_comp)
        live = np.asarray(m.num_edges_valid, np.int64)
        info = {"mode": mode, "parts": P_, "rows_per_epoch": int(rows),
                "bytes_per_epoch": int(nbytes), "halo_rows_per_peer": K,
                "halo_fraction": P_ * K / (S + P_ * K) if K else 0.0,
                "edge_cut_share": None,
                "padded_max_tax": float(_padded_max_tax(m)),
                "shard_edges_live_min": int(live.min()),
                "shard_edges_live_max": int(live.max())}
        halo_src = getattr(getattr(self, "halo", None), "edge_src_local",
                           None)
        if halo_src is not None and len(halo_src) == P_:
            # a remote source reads the received block, past the S own rows
            # (pad edges sit on an own pad row: never counted)
            cut = sum(int(np.count_nonzero(e >= S)) for e in halo_src)
        elif mode != "edge" and hasattr(m, "edge_src"):
            # padded-global ids (allgather, ring): the owner is id // S
            cut = sum(int(np.count_nonzero(
                (e < p * S) | (e >= (p + 1) * S)))
                for p, e in enumerate(m.edge_src))
        else:           # per-host loading holds local parts only
            cut = None
        if cut is not None:
            info["edge_cut_share"] = cut / max(int(live.sum()), 1)
        if gd.mode == "vertex" and gd.plans is not None:
            # an edge fills one slot of one chunk a pass; the rest of the
            # chunks x width slots are padding the scans gather all the same
            for d in ("fwd", "bwd"):
                chunks, width = _plan_chunks(gd.plans, d)
                info["agg_chunks_" + d] = chunks
                info["agg_slot_fill_" + d] = \
                    int(live.max()) / max(chunks * width, 1)
        info["agg_backend"] = gd.backend
        info["agg_backend_reason"] = self._backend_why
        return info

    def announce(self):
        """The exchange's facts before the base trainer's (attention, the
        step's scopes)."""
        self._announce_exchange()
        super().announce()

    def _announce_exchange(self):
        """The sharded trainer's own start-up line, `# exchange: ...` on
        stderr in every run (once a pod, not once a host), and the same
        facts as one `exchange` record + gauges under -obs (what
        _announce_attention_info is to a gat model); again after a
        reshard."""
        info = self.exchange_info()
        if jax.process_index() == 0:
            from roc_tpu.obs.report import exchange_line
            print(exchange_line(info), file=sys.stderr, flush=True)
        if self._metrics is None:
            return
        self._metrics.emit("exchange", **info)
        for name in ("rows_per_epoch", "bytes_per_epoch"):
            self._metrics.set_gauge("exchange_" + name, info[name])
        for name in ("halo_rows_per_peer", "halo_fraction", "edge_cut_share",
                     "padded_max_tax", "shard_edges_live_min",
                     "shard_edges_live_max", "agg_chunks_fwd",
                     "agg_chunks_bwd", "agg_slot_fill_fwd",
                     "agg_slot_fill_bwd"):
            if info.get(name) is not None:
                self._metrics.set_gauge(name, info[name])
        self._metrics.set_gauge("exchange_mode", 1.0, mode=info["mode"])
        self._metrics.set_gauge("agg_backend", 1.0,
                                backend=info["agg_backend"],
                                reason=info["agg_backend_reason"])

    # Auto edge-shard threshold: below this padded-max tax, vertex+halo
    # wins on comms; above it, the padding dominates (measured crossover in
    # docs/PERF.md — 28% tax was already a wash, 362% a 3.6x win).
    EDGE_SHARD_TAX = 0.30

    def _resolve_edge_shard(self) -> bool:
        es = self.config.edge_shard
        if es in (True, "on"):
            return True
        if es in (False, None, "off"):
            return False
        if self._exchange_mode == "ring":
            # an explicit -exchange ring is a deliberate distribution
            # choice; auto edge-shard must not silently override it
            return False
        # "auto": a perf heuristic — only skewed partitions benefit (the
        # padded-max tax IS the skew cost).
        if self.k > 1:        # overcommit is vertex-mode only
            return False
        aggrs = self._model_aggrs()
        has_gat = any(op.kind == "gat" for op in self.model.ops)
        if has_gat and self._gat_backend() != "plan":
            # On the xla attention backend, _edge_attend is the
            # correctness path (its autodiff backward scatters serialize
            # on TPU) — not an auto perf win.  Since round 4 the PLAN
            # backend (edge_gat_attend) is scatter-free fwd+bwd, so GAT
            # auto-enables exactly when plan attention would serve it;
            # explicit -edge-shard on is honored either way.
            return False
        if aggrs - {"sum", "avg"}:
            return False
        if not aggrs and not has_gat:
            return False
        tax = _padded_max_tax(self.part)
        if tax > self.EDGE_SHARD_TAX:
            if jax.process_index() == 0:
                print(f"# padded-max tax {tax * 100:.0f}% > "
                      f"{self.EDGE_SHARD_TAX:.0%}: auto-enabling edge-"
                      f"sharded aggregation (-edge-shard off to override)",
                      file=sys.stderr)
            return True
        return False

    def _setup(self):
        cfg, ds, model = self.config, self.dataset, self.model
        P_ = cfg.num_parts
        self.mesh = make_mesh(P_)
        self.k = P_ // self.mesh.devices.size   # parts per device (>1 =
        self.part = None                        # reference's overcommit)
        self._exchange_mode = cfg.exchange_mode()
        refuse_pair_attention(model, "SpmdTrainer ("
                             + ("overcommit, " if self.k > 1 else "")
                             + ("-edge-shard, "
                                if cfg.edge_shard in (True, "on") else "")
                             + f"-exchange {self._exchange_mode}, "
                               f"-parts {P_})")
        if self.k > 1:
            if jax.process_count() > 1 or cfg.perhost_load:
                raise ValueError(
                    "parts-per-device overcommit is single-process only; "
                    "use num_parts == total devices under jax.distributed")
            if self._exchange_mode == "ring" or cfg.edge_shard in (True,
                                                                   "on"):
                raise ValueError(
                    f"num_parts={P_} > {self.mesh.devices.size} devices "
                    f"(overcommit) supports the halo/allgather vertex "
                    f"exchanges only; use -parts {self.mesh.devices.size} "
                    f"for ring/edge-shard")
            if jax.process_index() == 0 and cfg.verbose:
                print(f"# overcommit: {P_} parts on "
                      f"{self.mesh.devices.size} device(s), "
                      f"k={self.k} shard blocks per device "
                      f"(gnn.cc:61-63 numParts>numGPUs)", file=sys.stderr)
        if cfg.perhost_load:
            # Explicit -edge-shard composes with -perhost since round 4
            # (blocks are byte-range reads; bwd needs the transposed
            # sidecar).  "auto" stays off here: the tax heuristic wants
            # the partition stats before any loading is done, and the
            # transposed sidecar may not exist — opt in explicitly.
            self._use_edge_shard = cfg.edge_shard in (True, "on")
            if self._use_edge_shard and self._model_has_gat() \
                    and self._gat_backend() != "plan":
                raise ValueError(
                    "-edge-shard -perhost with a GAT model needs the plan "
                    "attention backend (-aggr-backend matmul/binned); the "
                    "xla path's _edge_attend serializes on TPU")
        else:
            with obs.span("partition", parts=P_):
                self.part = partition_graph(ds.graph, P_)
            self._use_edge_shard = self._resolve_edge_shard()
        if self._use_edge_shard and self._exchange_mode == "ring":
            if jax.process_index() == 0:
                print("# -edge-shard on overrides -exchange ring (edge "
                      "blocks have their own psum_scatter exchange)",
                      file=sys.stderr)
            self._exchange_mode = "halo"   # ignored by the edge path
        backend = self._effective_backend()
        if self._exchange_mode == "ring" and backend == "binned":
            # ring aggregates per visiting owner group over prebuilt chunk
            # plans (ring_owner_matmul); the binned kernels' bin schedule
            # doesn't apply to the rotating buffer — matmul is the ring
            # fast path.
            if cfg.aggregate_backend not in ("auto",) and \
                    jax.process_index() == 0:
                print(f"# -exchange ring: aggregate_backend="
                      f"{cfg.aggregate_backend} rides the matmul ring "
                      f"plans", file=sys.stderr)
            backend = "matmul"
            self._backend_why = "exchange_ring_rides_matmul"

        # Plan-backend attention composes with halo/allgather vertex
        # sharding (gat_attend_plan), single-host or perhost, and — since
        # round 4 — with edge sharding (edge_gat_attend: per-block windowed
        # plans + pmax + psum_scatter, scatter-free fwd AND bwd).  Ring
        # mode attends via its own online-softmax recurrence (_ring_attend
        # — no plans, no table).
        gat_backend = self._gat_backend() \
            if self._exchange_mode != "ring" else "xla"
        gd = self._build_graph_perhost(backend, gat_backend) \
            if cfg.perhost_load else self._build_graph_full(backend,
                                                            gat_backend)
        # Remember the resolved backends + sharding specs: reshard() rebuilds
        # graph data and steps from these without re-running the auto policy.
        self._backend_resolved = backend
        self._gat_backend_resolved = gat_backend
        self._node_spec = NamedSharding(self.mesh, P(PARTS_AXIS))
        self._repl_spec = NamedSharding(self.mesh, P())

        with obs.span("place_data", parts=P_):
            self._place_data(gd)
        with obs.span("init_params"):
            self.params = jax.device_put(model.init_params(self.key),
                                         self._repl_spec)
            self.opt_state = jax.device_put(
                self.optimizer.init(self.params), self._repl_spec)
        # Plan activation memory once per setup, before the steps trace:
        # reshards keep the plan (the per-device shard shape is frozen), so
        # the step cache below still hits on a same-structure rebuild.
        with obs.span("mem_plan"):
            self._resolve_mem_plan()
        with obs.span("step_build", parts=P_):
            self._build_steps(gd)

    def _place_data(self, gd: ShardedGraphData):
        """Place the node tensors + graph data for the current partition
        (called from _setup and again on every reshard)."""
        ds = self.dataset
        node_spec = self._node_spec

        # Node tensors: [P*S, ...], padded + permuted, sharded on axis 0 —
        # placed PER DEVICE so no host materializes the full padded array
        # and, under multihost, each process reads only its own parts from
        # (possibly memmapped) storage: sharded host loading.
        self.x = self._place_nodes(
            lambda p: self.part.pad_part(ds.features, p,
                                         dtype=np.dtype(self.dtype)),
            node_spec, row_shape=ds.features.shape[1:])
        from roc_tpu.graph.lux import one_hot

        def onehot_part(p):
            # pad rows carry label 0; harmless — their mask is NONE
            ids = self.part.pad_part(ds.label_ids, p, fill=0)
            return one_hot(ids, ds.num_classes)
        self.labels = self._place_nodes(onehot_part, node_spec,
                                        row_shape=(ds.num_classes,))
        # Pad rows get MASK_NONE so they never count in loss or metrics.
        self.mask = self._place_nodes(
            lambda p: self.part.pad_part(ds.mask, p, fill=MASK_NONE,
                                         dtype=np.int32), node_spec)

        self.gdata = self._place_parts(gd, node_spec)

    def _build_steps(self, gd: ShardedGraphData):
        """Build the jitted shard_map step functions for a graph-data
        pytree.  Rebuilt on reshard: the pytree STRUCTURE (plan shapes,
        static metadata) can change with the cut, and gd_specs below is
        derived from it — but the padded S/E stay frozen, so XLA's compile
        cache (keyed on the HLO) absorbs the rebuild when the structure
        comes back identical."""
        model = self.model
        S = self.part.shard_nodes
        exchange = self._exchange_mode
        optimizer = self.optimizer
        k = self.k
        # Same-structure rebuilds (a balancer reshard that kept every plan
        # shape) must not even re-trace: reuse the SAME jitted callables,
        # keyed on the graph pytree's structure + leaf shapes/dtypes (the
        # static half of jax's own cache key).  This is what lets the
        # retrace guard (analysis/retrace.py) assert literal zero.
        mem_plan = getattr(self, "mem_plan", None)
        obs_on = bool(self.config.obs)
        sig = (S, exchange, k, obs_on,
               mem_plan.key() if mem_plan is not None else None,
               jax.tree_util.tree_structure(gd),
               tuple((tuple(leaf.shape), str(leaf.dtype))
                     for leaf in jax.tree_util.tree_leaves(gd)))
        cache = self.__dict__.setdefault("_step_cache", {})
        cached = cache.get(sig)
        if cached is not None:
            self._train_step, self._eval_step, self._logits_step = cached
            return
        # pallas_call can't annotate vma yet; the matmul backend is plain
        # XLA.  Binned plans mean pallas_call traces inside shard_map.
        check_vma = gd.plans is None or gd.backend == "matmul"

        def block_gctx(gd_block):
            """Per-device GraphCtx: one part (squeezed) or k stacked."""
            if k > 1:
                return _shard_gctx_over(gd_block, S, k, exchange)
            return _shard_gctx(_squeeze_gd(gd_block), S, exchange)

        # model.loss with the memory plan's checkpoint policy applied (the
        # model's own loss under an all-KEEP plan — identical program)
        loss_fn = self._loss_fn()

        def local_loss(params, x, labels, mask, gd_block, key):
            gctx = block_gctx(gd_block)
            return loss_fn(params, x, labels, mask, gctx, key=key,
                           train=True)

        gd_specs = jax.tree.map(lambda a: P(PARTS_AXIS), gd)

        # In-graph metrics channel (obs/channel.py): the contract is zero
        # host syncs, zero NEW collectives, zero retraces.  Norms use
        # values the step already replicates (grads after its psum, the
        # updated params); wire bytes are a trace-time constant from the
        # static exchange geometry (one forward exchange per aggregation;
        # backward roughly doubles it); edge counts reduce only the local
        # block, one scalar per device.
        if obs_on:
            on_wire, send_cols = self._wire_geometry(gd)
            wire_bytes = obs.channel.wire_bytes_per_step(
                on_wire, self.part.num_parts, S, self._aggregate_widths(),
                send_cols=send_cols, xch_dtype=gd.xch_dtype,
                xch_comp=gd.xch_comp)
            # Ledger prediction at step-build time (host-side, outside the
            # traced body); _obs_epoch pairs it with the per-epoch value
            # from the metrics channel.  The channel returns this same
            # analytic constant today, so a ratio off 1.0 means the
            # exchange geometry the step was built for is not the one the
            # epoch ran.
            led = obs.get_ledger()
            if led.attached:
                from roc_tpu.obs.ledger import content_key
                self._wire_key = content_key(
                    mode=on_wire, parts=self.part.num_parts, shard_nodes=S)
                led.predict("wire_bytes", self._wire_key, wire_bytes,
                            "bytes")
            metric_specs = {"grad_norm": P(), "param_norm": P(),
                            "wire_bytes": P(), "edges": P(PARTS_AXIS)}
            step_out_specs = (P(), P(), P(), P(), metric_specs)
        else:
            step_out_specs = (P(), P(), P(), P())

        @partial(jax.shard_map, mesh=self.mesh, check_vma=check_vma,
                 in_specs=(P(), P(), P(PARTS_AXIS), P(PARTS_AXIS),
                           P(PARTS_AXIS), gd_specs, P(), P(), P()),
                 out_specs=step_out_specs)
        def step_shard(params, opt_state, x, labels, mask, gd, key, alpha,
                       gscale):
            # this body only runs while jax traces it — a retrace counter
            _retrace.note_trace("train_step")
            # per-device dropout masks: fold the device index into the key
            # (k stacked parts draw distinct rows of the same stream)
            with scopes.scope("roc.rng"):
                key = jax.random.fold_in(key,
                                         jax.lax.axis_index(PARTS_AXIS))
            # Differentiate a device-VARYING view of the replicated params:
            # the cotangents then stay local and the psum below is the one
            # gradient all-reduce.  Under check_vma jax would otherwise
            # all-reduce them itself (the transpose of its implicit
            # replicated->varying cast), and psum of that already-summed
            # value multiplies it by P — P-times-too-large grads against
            # the weight-decay term, and two all-reduces per weight.  (It
            # is also what the hand-written custom-vjp backwards need:
            # their shard-local cotangents typecheck only against varying
            # primals.)
            params_v = jax.tree.map(
                lambda p: jax.lax.pcast(p, PARTS_AXIS, to="varying"), params)
            loss_l, grads_l = jax.value_and_grad(local_loss)(
                params_v, x, labels, mask, gd, key)
            # all-reduce over ICI (replaces gather-to-one-GPU + serial sum)
            with scopes.scope("roc.allreduce"):
                grads = jax.tree.map(
                    lambda g: jax.lax.psum(g, PARTS_AXIS), grads_l)
                loss = jax.lax.psum(loss_l, PARTS_AXIS)
            # gscale is 1.0 on healthy steps (exact multiply); the chaos
            # harness feeds NaN to exercise the non-finite guard.  Applied
            # AFTER the psums so loss/grads are already replicated and the
            # guard's skip decision is identical on every device.
            loss = loss * gscale
            grads = jax.tree.map(lambda g: g * gscale, grads)
            new_params, new_opt, nonfinite, gnorm = fault.guarded_update(
                optimizer, params, grads, opt_state, alpha, loss=loss)
            if not obs_on:
                return new_params, new_opt, loss, nonfinite
            metrics = {
                "grad_norm": gnorm,
                "param_norm": obs.channel.global_norm(new_params),
                # float32: exact for any realistic per-step byte count's
                # leading digits, and immune to the x64-disabled int trap
                "wire_bytes": jnp.float32(wire_bytes),
                # live in-edges targeting this device's rows ([1] per
                # device -> a [num_devices] global, one count per shard)
                "edges": jnp.sum(gd.in_degree).astype(jnp.int32)[None],
            }
            return new_params, new_opt, loss, nonfinite, metrics

        @partial(jax.shard_map, mesh=self.mesh, check_vma=check_vma,
                 in_specs=(P(), P(PARTS_AXIS), P(PARTS_AXIS), P(PARTS_AXIS),
                           gd_specs),
                 out_specs=P())
        def eval_shard(params, x, labels, mask, gd):
            _retrace.note_trace("eval_step")
            gctx = block_gctx(gd)
            logits = model.apply(params, x, gctx, train=False)
            m = ops.perf_metrics(logits, labels, mask)
            with scopes.scope("roc.allreduce"):
                return jax.tree.map(lambda v: jax.lax.psum(v, PARTS_AXIS),
                                    m)

        @partial(jax.shard_map, mesh=self.mesh, check_vma=check_vma,
                 in_specs=(P(), P(PARTS_AXIS), gd_specs),
                 out_specs=P(PARTS_AXIS))
        def logits_shard(params, x, gd):
            _retrace.note_trace("logits_step")
            gctx = block_gctx(gd)
            return model.apply(params, x, gctx, train=False)

        self._train_step = jax.jit(step_shard, donate_argnums=(0, 1))
        self._eval_step = jax.jit(eval_shard)
        self._logits_step = jax.jit(logits_shard)
        cache[sig] = (self._train_step, self._eval_step, self._logits_step)

    # -- online load balancing (roc_tpu/balance/) -------------------------
    def _balance_supported(self) -> bool:
        """reshard() handles the single-process vertex-sharded modes
        (halo / allgather exchange, k = 1).  Edge-shard mode is already
        exactly balanced; ring and overcommit keep extra per-cut state
        (rotation groups, stacked blocks) — ROADMAP follow-ons."""
        return (isinstance(self.part, Partition)
                and not self.config.perhost_load
                and not self._use_edge_shard
                and self._exchange_mode in ("halo", "allgather")
                and self.k == 1
                and jax.process_count() == 1)

    def reshard(self, new_bounds: np.ndarray) -> float:
        """Apply a repartition at an epoch boundary; returns wall seconds.

        The new cut is laid out under the OLD padded shard shape
        (partition_graph's shard_nodes/shard_edges overrides), so every
        array keeps its static shape and dtype: the rebuilt jitted steps
        hit XLA's compile cache whenever the plan structure is unchanged,
        and the content-keyed ROC_PLAN_CACHE re-serves plan builds.  Params
        and optimizer state are node-independent (GCN/GAT weights are
        [H_in, H_out]) — no weight migration, only data placement moves.
        """
        assert self._balance_supported(), \
            "reshard: unsupported trainer mode (see _balance_supported)"
        with obs.span("reshard", parts=self.part.num_parts) as sp:
            old = self.part
            self.part = partition_graph(
                self.dataset.graph, old.num_parts,
                bounds=np.asarray(new_bounds, np.int64),
                shard_nodes=old.shard_nodes, shard_edges=old.shard_edges)
            gd = self._build_graph_full(self._backend_resolved,
                                        self._gat_backend_resolved)
            self._place_data(gd)
            self._build_steps(gd)
        self._announce_exchange()
        return sp.dur_s
