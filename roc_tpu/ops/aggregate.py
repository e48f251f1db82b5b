"""Sparse neighborhood aggregation (the reference's ScatterGather op).

Semantics (scattergather_kernel.cu:20-76): for every destination vertex v,
``out[v] = Σ_{e : dst(e)=v} x[src(e)]`` — a sum over in-edges.  The reference
runs a block-cooperative CUDA kernel with a CUB prefix-scan; on TPU the same
contraction has three backends: gather + sorted segment-sum (`xla`, the
oracle), scatter-free one-hot MXU matmuls over a host-built chunk plan
(`matmul`, fp32-exact), and the binned two-phase Pallas kernels
(`binned`, the hardware fast path — roc_tpu/ops/pallas/binned.py).

Backward needs no hand-written task pair (the reference reuses its forward
kernel on the transposed role, scattergather_kernel.cu:160-170): JAX
autodiff of gather+segment_sum *is* the transposed aggregation.

Aggregation variants (AggrType, gnn.h:77-81 — the reference enumerates
AVG/MAX/MIN/SUM but only wires SUM): all four are provided here.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from roc_tpu.obs import scopes
from roc_tpu.obs.tracer import span as _obs_span


def _vary_like(init, ref):
    """Promote a scan-carry init to ``ref``'s device-varying vma annotation
    WITHOUT a gradient edge: the `+ 0 * ref` spelling creates one, through
    which a non-finite cotangent transposes to 0 * inf = NaN and a
    non-finite ref element broadcasts NaN into the whole carry primal —
    the _ring_attend bug class (spmd.py pcast note).  Axis-agnostic
    (reads ref's vma), so it is a no-op outside shard_map."""
    import jax as _jax
    return _jax.lax.pcast(init, tuple(_jax.typeof(ref).vma), to="varying")


# Above this many elements in the gathered [E, H] intermediate, sum
# aggregation switches to an edge-chunked scan with in-place accumulation
# (bounded memory).  2^28 elems = 1 GiB fp32.
_CHUNK_THRESHOLD_ELEMS = 1 << 28
_CHUNK_TARGET_ELEMS = 1 << 25      # ~128 MiB fp32 per chunk


def _chunked_segment_sum(x, edge_src, edge_dst, num_nodes: int):
    """Memory-bounded sum aggregation: scan over edge chunks, scatter-adding
    into a donated accumulator.

    XLA materializes jnp.take's [E, H] result before segment_sum; at
    reference scale (reddit: 2.3e7 edges x 256 features x 4 B = 24 GB) that
    alone overflows a chip's HBM.  The reference never faces this because
    each GPU task only touches its partition's edge slice and stages rows
    through a fixed framebuffer cache (load_task.cu:365-374) — this scan is
    the single-chip analog: fixed [chunk, H] working set, out + one chunk
    in flight.  Pad edges route to an extra throwaway row (num_nodes).
    """
    E, H = edge_src.shape[0], x.shape[1]
    chunk = max(_CHUNK_TARGET_ELEMS // max(H, 1), 1024)
    nchunks = -(-E // chunk)
    pad = nchunks * chunk - E
    src = jnp.pad(edge_src, (0, pad))                      # row 0: harmless
    dst = jnp.pad(edge_dst, (0, pad), constant_values=num_nodes)
    # The scan carry must be device-varying like x under shard_map's vma
    # tracking; without the promotion the chunked path crashes the moment
    # a SHARD's E*H crosses the threshold — caught at products shape with
    # H=32, just past the bound the round-3 test grazed under.
    acc = _vary_like(jnp.zeros((num_nodes + 1, H), x.dtype), x)

    def body(acc, sl):
        s, d = sl
        return acc.at[d].add(jnp.take(x, s, axis=0),
                             indices_are_sorted=True,
                             mode="promise_in_bounds"), None
    acc, _ = jax.lax.scan(
        body, acc, (src.reshape(nchunks, chunk), dst.reshape(nchunks, chunk)))
    return acc[:num_nodes]


def scatter_gather(x, edge_src, edge_dst, num_nodes: int, aggr: str = "sum"):
    """out[v] = aggr over in-edges of x[src].

    Args:
      x: [N_table, H] source feature table (may be larger than num_nodes when
         it includes halo/remote rows).
      edge_src: [E] int indices into x.
      edge_dst: [E] int destination rows, sorted ascending (CSR order).
      num_nodes: number of output rows (static).
      aggr: one of sum/avg/max/min.
    """
    if (aggr == "sum"
            and edge_src.shape[0] * x.shape[1] > _CHUNK_THRESHOLD_ELEMS):
        return _chunked_segment_sum(x, edge_src, edge_dst, num_nodes)
    gathered = jnp.take(x, edge_src, axis=0)
    if aggr == "sum":
        return jax.ops.segment_sum(gathered, edge_dst, num_segments=num_nodes,
                                   indices_are_sorted=True)
    if aggr == "avg":
        s = jax.ops.segment_sum(gathered, edge_dst, num_segments=num_nodes,
                                indices_are_sorted=True)
        cnt = jax.ops.segment_sum(jnp.ones_like(edge_dst, dtype=x.dtype),
                                  edge_dst, num_segments=num_nodes,
                                  indices_are_sorted=True)
        return s / jnp.maximum(cnt, 1.0)[:, None]
    if aggr in ("max", "min"):
        seg = jax.ops.segment_max if aggr == "max" else jax.ops.segment_min
        out = seg(gathered, edge_dst, num_segments=num_nodes,
                  indices_are_sorted=True)
        # Empty neighborhoods fill with the segment identity (+-inf), which
        # NaN-poisons any later linear layer (inf * 0 weight).  Zero exactly
        # those — the zero-preserving convention the shard-padding machinery
        # relies on (graph/partition.py).  Matching the identity (not
        # isfinite) keeps genuine NaN blow-ups visible.
        empty = jnp.isneginf(out) if aggr == "max" else jnp.isposinf(out)
        return jnp.where(empty, 0, out)
    raise ValueError(f"unknown aggr {aggr!r}")


def divide_by_degree(out, in_degree):
    """avg from a sum aggregation: out / max(in_degree, 1), matching the
    xla oracle's count guard.  The single semantics shared by every avg
    call site (single-device plan path, sharded plan path, ring,
    edge-shard): in_degree is the live in-edge count per output row (pad
    rows carry 1 and their sums are zero, so they stay zero).

    The division runs in float32 regardless of out.dtype: a bf16 cast of
    the degree rounds counts above 256 (up to ~0.4% relative error in avg),
    so the degree stays exact and only the quotient is cast back."""
    deg = jnp.maximum(in_degree, 1.0).astype(jnp.float32)
    return (out.astype(jnp.float32) / deg[:, None]).astype(out.dtype)


# ---------------------------------------------------------------------------
# Chunk plans shared by the one-hot (matmul) backend.
# ---------------------------------------------------------------------------

class AggregatePlans(NamedTuple):
    """Fwd + transposed-bwd chunk schedules as jit-traceable arrays.

    Kept as a flat NamedTuple of int32 arrays so it rides inside the graph-
    data pytree passed to jitted steps (and can be stacked + sharded on a
    leading parts axis for shard_map)."""
    fwd_obi: jnp.ndarray    # [C_f]
    fwd_first: jnp.ndarray  # [C_f]
    fwd_edst: jnp.ndarray   # [C_f, EB]
    fwd_esrc: jnp.ndarray   # [C_f, EB]
    bwd_obi: jnp.ndarray    # [C_b]
    bwd_first: jnp.ndarray  # [C_b]
    bwd_edst: jnp.ndarray   # [C_b, EB]
    bwd_esrc: jnp.ndarray   # [C_b, EB]


def build_aggregate_plans_host(edge_src: np.ndarray, edge_dst: np.ndarray,
                               num_rows: int,
                               table_rows: int) -> AggregatePlans:
    """Chunk schedules for out = A@x (fwd) and grad_x = A^T@grad (bwd), as
    NumPy arrays: nothing here touches a device, so the sharded trainer
    builds its parts' plans side by side and places each on its own chip.

    The transposed plan re-sorts the edge list by source — the exact move
    the reference makes by launching its forward kernel with input/output
    roles swapped (scattergather_kernel.cu:160-170)."""
    from roc_tpu.ops.pallas.segment_sum import build_chunk_plan
    fwd = build_chunk_plan(np.asarray(edge_src, np.int32),
                           np.asarray(edge_dst, np.int32), num_rows)
    order = np.argsort(edge_src, kind="stable")
    bwd = build_chunk_plan(np.asarray(edge_dst)[order].astype(np.int32),
                           np.asarray(edge_src)[order].astype(np.int32),
                           table_rows)
    # _one_hot_dots relies on consecutive obi increasing by at most 1 (every
    # window, even an empty one, gets >= 1 chunk) so that within a scan step
    # lw = ob - ob[0] < CB; a plan builder that skipped empty windows would
    # silently drop contributions there.  Pin the invariant here, where every
    # plan (python or native) passes through.
    for plan in (fwd, bwd):
        assert np.all(np.diff(np.asarray(plan.obi)) <= 1), \
            "chunk plan skips output windows (obi jump > 1)"
    return AggregatePlans(
        fwd_obi=fwd.obi, fwd_first=fwd.first, fwd_edst=fwd.edst,
        fwd_esrc=fwd.esrc, bwd_obi=bwd.obi, bwd_first=bwd.first,
        bwd_edst=bwd.edst, bwd_esrc=bwd.esrc)


def build_aggregate_plans(edge_src: np.ndarray, edge_dst: np.ndarray,
                          num_rows: int, table_rows: int) -> AggregatePlans:
    """:func:`build_aggregate_plans_host`, placed on the default device."""
    host = build_aggregate_plans_host(edge_src, edge_dst, num_rows,
                                      table_rows)
    with _obs_span("plan_to_device"):
        return AggregatePlans(*(jnp.asarray(a) for a in host))


def pad_plans(plans: "list[AggregatePlans]", min_fwd: int = 0,
              min_bwd: int = 0) -> AggregatePlans:
    """Stack per-shard host plans to common chunk counts (shard_map needs
    one static program), as NumPy arrays: the trainer places each part's
    block on its own device.  Pad chunks are the canonical no-ops of
    :func:`roc_tpu.ops.pallas.segment_sum.pad_chunks`.

    ``min_fwd``/``min_bwd`` raise the target chunk counts — the per-host
    loader passes the allgathered global maxima so every process compiles
    the same program even though each only sees its local parts' plans."""
    from roc_tpu.ops.pallas.segment_sum import pad_chunks

    def stack(prefix):
        quads = [(getattr(p, prefix + "obi"), getattr(p, prefix + "first"),
                  getattr(p, prefix + "edst"), getattr(p, prefix + "esrc"))
                 for p in plans]
        C = max(max(q[0].shape[0] for q in quads),
                min_fwd if prefix == "fwd_" else min_bwd)
        padded = [pad_chunks(*q, C - q[0].shape[0], np) for q in quads]
        return [np.stack([p[i] for p in padded]) for i in range(4)]

    f, b = stack("fwd_"), stack("bwd_")
    return AggregatePlans(fwd_obi=f[0], fwd_first=f[1], fwd_edst=f[2],
                          fwd_esrc=f[3], bwd_obi=b[0], bwd_first=b[1],
                          bwd_edst=b[2], bwd_esrc=b[3])


# ---------------------------------------------------------------------------
# Matmul backend (sum; avg = sum/in-degree at the call sites):
# scatter-free aggregation in pure XLA.
# ---------------------------------------------------------------------------
#
# TPU scatter is serialized per index (measured ~6.5 s for one Reddit-scale
# aggregation on v5e); the reference never pays this because its CUDA kernel
# scatter-adds through shared-memory atomics (scattergather_kernel.cu:20-76).
# The TPU-native answer is to turn the scatter into MXU matmuls against
# one-hot matrices, using the same host-built chunk schedule as the Pallas
# kernel: chunks of EB dst-sorted edges, each owning a VB-row output window.
# Per scan step (CB chunks):
#   G    = x[esrc]                          gather  [CB*EB, H]
#   psum = S1 @ G   (batched, S1 one-hot)   scatter within window  [CB, VB, H]
#   outs = S2 @ psum (S2 one-hot over chunks->windows)             [CB, VB, H]
#   acc[window range] += outs               dynamic-slice RMW (windows in a
#                                           step are contiguous: obi sorted)
# No scatter instruction anywhere; everything is gather + matmul + DUS.

_MM_CB = 512   # chunks per scan step
# The S2 combine of the sum scans, whatever ``precision`` the S1 products
# take: "high" is three bf16 passes over the float32 partial sums (S2's
# one-hot operand is exact in one), so a window's chunk sums are added with
# 16 bits of significand kept and not 8.  On the chip, one aggregation of a
# products-size shard at width 256 (PERF.md PR 29): error against float32
# 1.104e-3 at "default", 1.914e-4 at "high" and at "highest" alike (what is
# left is the features' one rounding); a scan takes 520.5 / 509.1 / 523.6 ms,
# and the gcn-products.p4 epoch 0.36 % more at "high" than at "default".
_MM_COMBINE = "high"


def _one_hot_dots(g, ed, ob, cb, precision, combine_precision):
    """S1/S2 one-hot matmuls for one scan step (see module comment).
    ``combine_precision`` feeds the S2 dot, which adds the chunks' float32
    partial sums of a window: at the MXU's default precision it would
    round each PARTIAL SUM to bf16, a second rounding on top of the
    features' (:data:`_MM_COMBINE` has the chip's numbers).  The attention
    path passes "highest" (PERF.md PR 25), the sum scans ``_MM_COMBINE``
    ("highest" where the S1 products are)."""
    from roc_tpu.ops.pallas.segment_sum import EB, VB
    H = g.shape[-1]
    s1 = (jax.lax.broadcasted_iota(jnp.int32, (cb, VB, EB), 1)
          == ed[:, None, :]).astype(g.dtype)
    psum = jax.lax.dot_general(
        s1, g.reshape(cb, EB, H), (((2,), (1,)), ((0,), (0,))),
        precision=precision, preferred_element_type=jnp.float32)
    lw = ob - ob[0]                                   # [CB] in [0, CB)
    s2 = (jax.lax.broadcasted_iota(jnp.int32, (cb, cb), 0)
          == lw[None, :]).astype(g.dtype)
    outs = jax.lax.dot_general(
        s2, psum.reshape(cb, VB * H), (((1,), (0,)), ((), ())),
        precision=combine_precision, preferred_element_type=jnp.float32)
    return outs.reshape(cb * VB, H)   # fp32: accumulated across steps


def _matmul_run(x, obi, edst, esrc, num_rows: int, precision):
    """out = A @ x over the chunk plan, scatter-free (sum aggregation)."""
    from roc_tpu.ops.pallas.segment_sum import EB, VB
    from roc_tpu.ops.pallas.segment_sum import pad_chunks
    H = x.shape[-1]
    C = obi.shape[0]
    cb = min(_MM_CB, max(8, C))
    nsteps = -(-C // cb)
    obi, _, edst, esrc = pad_chunks(obi, jnp.zeros_like(obi), edst, esrc,
                                    nsteps * cb - C, jnp)
    num_windows = (num_rows + VB - 1) // VB
    acc_rows = (num_windows - 1 + cb) * VB   # DUS windows never clamp
    combine = "highest" if precision == "highest" else _MM_COMBINE

    def body(acc, sl):
        ob, es, ed = sl
        g = jnp.take(x, es.reshape(cb * EB), axis=0, mode="clip")
        outs = _one_hot_dots(g, ed, ob, cb, precision, combine)
        base = ob[0] * VB
        cur = jax.lax.dynamic_slice(acc, (base, 0), (cb * VB, H))
        return jax.lax.dynamic_update_slice(acc, cur + outs, (base, 0)), None

    # Accumulate across steps in fp32 even for bf16 activations (the Pallas
    # path does the same via x.astype(fp32); the reference sums in fp32);
    # carry promoted to x's device-varying annotation, axis-agnostically.
    acc = _vary_like(jnp.zeros((acc_rows, H), jnp.float32), x)
    acc, _ = jax.lax.scan(
        body, acc, (obi.reshape(nsteps, cb), esrc.reshape(nsteps, cb, EB),
                    edst.reshape(nsteps, cb, EB)))
    return acc[:num_rows].astype(x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def scatter_gather_matmul(x, plans: AggregatePlans, num_rows: int,
                          table_rows: int, precision: str = "highest"):
    """Sum-aggregation via one-hot MXU matmuls (no scatter, no Pallas).

    Plan-driven like the binned backend; `precision` feeds
    the one-hot dots — "highest" keeps fp32-exact sums (the one-hot factor
    is exact in bf16, so error comes only from rounding the features), while
    "default" trades ~1e-2 relative error for single-pass MXU throughput.
    """
    with scopes.scope("fwd", "mm"):
        return _matmul_run(x, plans.fwd_obi, plans.fwd_edst, plans.fwd_esrc,
                           num_rows, precision)


def _mm_fwd(x, plans, num_rows, table_rows, precision):
    return scatter_gather_matmul(x, plans, num_rows, table_rows,
                                 precision), plans


def _mm_bwd(num_rows, table_rows, precision, plans, g):
    with scopes.scope("bwd", "mm"):
        gx = _matmul_run(g, plans.bwd_obi, plans.bwd_edst, plans.bwd_esrc,
                         table_rows, precision)
    zero = jax.tree.map(
        lambda a: np.zeros(a.shape, dtype=jax.dtypes.float0), plans)
    return gx, zero


scatter_gather_matmul.defvjp(_mm_fwd, _mm_bwd)


# ---------------------------------------------------------------------------
# Binned backend (sum; avg = sum/in-degree at the call sites):
# two-phase Pallas kernels, gather-free.
# ---------------------------------------------------------------------------

class BinnedPlans(NamedTuple):
    """Fwd + transposed-bwd binned schedules (see ops/pallas/binned.py).

    Same role as :class:`AggregatePlans` for the plan-based one-hot
    backends; the payloads are :class:`roc_tpu.ops.pallas.binned.BinnedPlan`
    dataclasses (registered pytrees with static geometry fields).

    ``mm`` (optional) is the matmul side of a HYBRID plan: on power-law
    graphs the thin (sub-``hub_minc``) cells' edges pay less on the
    per-edge one-hot matmul path than as slot padding, so choose_geometry
    can split the edge list — dense hub cells stay binned, the tail rides
    an :class:`AggregatePlans` whose output simply adds in.  A = A_dense +
    A_thin, so fwd sums the two paths and bwd sums their transposes."""
    fwd: object
    bwd: object
    mm: object = None


def build_binned_plans(edge_src: np.ndarray, edge_dst: np.ndarray,
                       num_rows: int, table_rows: int,
                       geom=None,
                       storage_dtype: str = "fp32") -> BinnedPlans:
    """Schedules for out = A@x (fwd) and grad_x = A^T@grad (bwd) — the bwd
    plan swaps roles exactly as the reference re-launches its forward
    kernel transposed (scattergather_kernel.cu:160-170).

    geom: None = the module-default geometry; a Geometry = both directions
    at that geometry; "auto" = per-direction choose_geometry from actual
    cell statistics (the directions transpose, so a directed graph can
    legitimately want different windows each way), falling back to the
    default where the model prefers matmul (the caller already chose
    binned).  A (fwd_spec, bwd_spec) pair sets each direction separately.

    A forward geometry with ``hub_minc`` set (choose_geometry's hybrid
    verdict, or an explicit caller) splits the edges: the binned pair
    covers only the dense-cell edges and ``mm`` carries the rest.

    ROC_BINNED_GEOM=<preset name> (binned.GEOM_PRESETS) overrides the
    forward auto-choice for hardware A/B runs that must isolate one
    variable.  A forced preset builds
    with ``tuned_ok=False``: an A/B run must get exactly the geometry it
    named even when the tuned tier disagrees (round 12)."""
    import os
    from roc_tpu.ops.pallas.binned import (GEOM_PRESETS, Geometry,
                                           _default_geom,
                                           build_binned_plan,
                                           choose_geometry, split_hub_edges)
    # Geometry is itself a NamedTuple: only a PLAIN pair is (fwd, bwd)
    if isinstance(geom, tuple) and not isinstance(geom, Geometry):
        fwd_spec, bwd_spec = geom
    else:
        fwd_spec, bwd_spec = geom, geom

    def pick(spec, src, dst, n, t, forced=""):
        if spec != "auto":
            return spec
        if forced:
            return GEOM_PRESETS[forced]
        with _obs_span("choose_geometry", edges=len(src)):
            g, _ = choose_geometry(src, dst, n, t, force=True,
                                   storage_dtype=storage_dtype)
        return g or _default_geom()

    forced_env = os.environ.get("ROC_BINNED_GEOM", "")
    fwd_geom = pick(fwd_spec, edge_src, edge_dst, num_rows, table_rows,
                    forced=forced_env)
    es, ed = np.asarray(edge_src), np.asarray(edge_dst)
    mm = None
    if getattr(fwd_geom, "hub_minc", 0):
        keep = split_hub_edges(es, ed, fwd_geom)
        if keep.any() and not keep.all():
            ts, td = es[~keep], ed[~keep]
            o = np.argsort(td, kind="stable")   # chunk plans want dst-sorted
            mm = build_aggregate_plans(ts[o], td[o], num_rows, table_rows)
            es, ed = es[keep], ed[keep]
    bwd_geom = pick(bwd_spec, ed, es, table_rows, num_rows,
                    forced=forced_env)
    if getattr(bwd_geom, "hub_minc", 0):
        # the split happened (once) on the forward cells; the bwd binned
        # plan covers exactly the transposed dense edges
        bwd_geom = bwd_geom._replace(hub_minc=0)
    tuned_ok = not forced_env
    return BinnedPlans(
        fwd=build_binned_plan(es, ed, num_rows, table_rows, geom=fwd_geom,
                              tuned_ok=tuned_ok),
        bwd=build_binned_plan(ed, es, table_rows, num_rows, geom=bwd_geom,
                              tuned_ok=tuned_ok),
        mm=mm)


def matmul_precision(aggregate_precision: str) -> str:
    """Map the config-level precision name to the dot_general precision,
    rejecting anything but the two supported spellings (a silent fallthrough
    to the fast path would drop the fp32-exact guarantee)."""
    if aggregate_precision == "exact":
        return "highest"
    if aggregate_precision == "fast":
        return "default"
    raise ValueError(f"aggregate_precision={aggregate_precision!r}: "
                     f"must be 'exact' or 'fast'")


def pad_binned_plans(plans: "list[BinnedPlans]", min_fwd=(0, 0),
                     min_bwd=(0, 0)) -> BinnedPlans:
    """Stack per-shard binned plans to common chunk counts (shard_map
    needs one static program) — the binned analog of :func:`pad_plans`.
    All shards share (G, bins_per_group, num_rows, table_rows) by
    construction: those derive only from the padded shard shapes, which
    are equal across shards.  ``min_fwd``/``min_bwd`` are (C1, C2) floors
    — the per-host loader passes allgathered global maxima."""
    from roc_tpu.ops.pallas.binned import pad_binned_plan
    assert all(b.mm is None for b in plans), \
        "hybrid (binned+matmul) plans are single-device only"

    def stack(side, floors):
        from roc_tpu.ops.pallas.binned import _PLAN_DATA_FIELDS
        ps = [getattr(b, side) for b in plans]
        meta = {(p.num_rows, p.table_rows, p.bins_per_group,
                 p.p1_blk.shape[0], p.geom) for p in ps}
        assert len(meta) == 1, f"shards disagree on plan geometry: {meta}"
        C1 = max(max(p.p1_blk.shape[1] for p in ps), floors[0])
        C2 = max(max(p.p2_obi.shape[1] for p in ps), floors[1])
        padded = [pad_binned_plan(p, C1, C2) for p in ps]
        import dataclasses as _dc
        # The fused (f_*) schedules are a single-device fast path: their
        # step lists bake in the per-shard chunk counts, which diverge
        # under shard_map's one static program — strip them so the
        # stacked plans take the two-pass scan uniformly.
        arrays = {}
        for f in _PLAN_DATA_FIELDS:
            vals = [getattr(p, f) for p in padded]
            if f.startswith("f_"):
                arrays[f] = None
                continue
            present = [v is not None for v in vals]
            assert all(present) or not any(present), \
                f"shards disagree on plan field {f}"
            arrays[f] = jnp.stack(vals) if all(present) else None
        return _dc.replace(padded[0], **arrays)

    return BinnedPlans(fwd=stack("fwd", min_fwd), bwd=stack("bwd", min_bwd))


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def scatter_gather_binned(x, plans: BinnedPlans, interpret: bool = False,
                          precision: str = "fast"):
    """Sum-aggregation via the binned two-phase kernels.  precision
    "fast": one bf16 rounding of features, fp32 accumulation; "exact":
    fp32 staging + 3-way bf16 split dots — fp32-exact like the matmul
    backend, at the binned kernels' memory schedule (the round-3 answer
    to "the fp32-exact path loses to the reference figure").
    Differentiable w.r.t. x.

    A hybrid plan (plans.mm set) adds the thin-cell edges' one-hot matmul
    aggregation: A = A_dense + A_thin."""
    from roc_tpu.ops.pallas.binned import run_binned
    # the pass; run_binned names its kernels p1 / p1_flat / p2 / fused
    with scopes.scope("fwd"):
        out = run_binned(x, plans.fwd, interpret, precision)
        if plans.mm is not None:
            with scopes.scope("mm"):
                out = out + _matmul_run(
                    x, plans.mm.fwd_obi, plans.mm.fwd_edst,
                    plans.mm.fwd_esrc, plans.fwd.num_rows,
                    matmul_precision(precision))
    return out


def _bn_fwd(x, plans, interpret, precision):
    return scatter_gather_binned(x, plans, interpret, precision), plans


def _bn_bwd(interpret, precision, plans, g):
    from roc_tpu.ops.pallas.binned import run_binned
    with scopes.scope("bwd"):
        gx = run_binned(g, plans.bwd, interpret, precision)
        if plans.mm is not None:
            with scopes.scope("mm"):
                gx = gx + _matmul_run(
                    g, plans.mm.bwd_obi, plans.mm.bwd_edst,
                    plans.mm.bwd_esrc, plans.bwd.num_rows,
                    matmul_precision(precision))
    zero = jax.tree.map(
        lambda a: np.zeros(a.shape, dtype=jax.dtypes.float0), plans)
    return gx, zero


scatter_gather_binned.defvjp(_bn_fwd, _bn_bwd)
