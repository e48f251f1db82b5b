"""Op-graph model builder (the reference's Model class, gnn.h:162-203).

The reference builds a list of GnnOp objects via Model::dropout /
::linear / ::indegree_norm / ::scatter_gather / ::relu / ::add /
::softmax_cross_entropy (gnn.cc:75-92), then drives forward / backward /
update over Legion index launches.  Here the same builder API produces a tiny
op IR; `apply` folds it into one pure function, and backward is `jax.grad`
of the masked-CE loss — there are no per-op backward tasks to write, and the
reference's reset-vs-accumulate gradient bookkeeping (resetInputGrads,
gnn.cc:702-716) is exactly what reverse-mode AD does automatically.

Distribution boundary: ops are local to a vertex shard except aggregation,
which needs remote rows.  `apply` therefore takes a :class:`GraphCtx` whose
``aggregate(x)`` closure hides the data movement — dense segment-sum on one
device, all_gather/halo-exchange + segment-sum inside `shard_map` (see
roc_tpu/parallel) — so the same model IR runs single-chip or pod-wide.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from roc_tpu import ops

try:
    from jax.ad_checkpoint import checkpoint_name as _checkpoint_name
except ImportError:  # pragma: no cover - ancient jax: tags degrade to id
    def _checkpoint_name(x, name):
        return x

# Op kinds whose outputs a kept layer saves under an active memory plan
# (roc_tpu/memory): expensive to recompute.  Elementwise outputs (dropout /
# norm / activation / add) are never saved — recomputing them is
# bandwidth-cheap (the per-tensor half of the planner's granularity
# decision; see roc_tpu/memory/estimator.py).
CKPT_SAVE_KINDS = frozenset({"linear", "aggregate", "gat"})


class GraphCtx(NamedTuple):
    """Everything an op needs to know about the (shard of the) graph."""
    aggregate: Callable[[jnp.ndarray, str], jnp.ndarray]  # x, aggr_type -> out
    in_degree: jnp.ndarray  # [N_local] float32, >= 1
    # attention aggregation: (h [N,K,F], a_src [K,F], a_dst [K,F], slope,
    # drop) -> [N, K, F]; built by the same driver/spmd code that builds
    # ``aggregate`` (it owns the halo/all_gather exchange).  ``drop`` is
    # None (evaluation, or no attention dropout) or (key, rate): the
    # normalised coefficients are dropped per edge and head
    # (ops.edge.attention_keep).
    attend: Optional[Callable] = None
    # whole-layer megakernel hook:
    # (x, w, activation, aggr, fold) -> out or None.
    # When set, `apply` offers each `mega_matches`-eligible chain to it —
    # aggregate→linear(→relu) directly, or the norm-folded GCN shape when
    # fold=True (the hook owns the D^-1/2 pre/post scales); a None return
    # means "not fusable here" (VMEM gate, hybrid plan, kill switch) and
    # the unfused op sequence runs unchanged.  Default None keeps every
    # existing program byte-identical — the HLO budget audit pins that.
    fuse_linear: Optional[Callable] = None
    # cross-layer fusion-region hook (round 16):
    # (x, ws, activations, fold) -> out or None.
    # When set AND fusion_depth != 1, `apply` offers each
    # `mega_regions`-eligible multi-layer chain (the region's weight and
    # activation tuples, head to tail) to it before the per-layer
    # fuse_linear pass; a None return declines the whole region and the
    # per-layer matches run unchanged — byte-identical to fusion_depth=1.
    fuse_region: Optional[Callable] = None
    # static region-length cap keying the step cache: 1 = off (default,
    # byte-identical to pre-round-16 programs), 2 = chains of exactly two
    # layers, 0 = unlimited ("full").
    fusion_depth: int = 1


@dataclasses.dataclass(frozen=True)
class TensorRef:
    """Symbolic handle returned by builder methods (the reference's Tensor)."""
    id: int
    dim: int


@dataclasses.dataclass(frozen=True)
class OpNode:
    kind: str                 # dropout|linear|norm|aggregate|activation|add
    inputs: tuple             # input tensor ids
    out: int                  # output tensor id
    attrs: dict               # op-specific attributes


def mega_matches(model: "Model") -> Dict[int, dict]:
    """Find megakernel-eligible layer chains in the static op IR.

    Two shapes match.  The direct ``aggregate → linear (→ relu)`` chain
    (GIN/SAGE) is keyed by the AGGREGATE's op index.  The GCN chain
    ``linear → norm → aggregate → norm (→ relu)`` is keyed by the
    LINEAR's op index and carries ``fold=True`` (round 12, norm-folding):
    since ``indegree_norm`` is a positive diagonal row-scale,
    D^-½ A D^-½ (xW) = D^-½ · A · ((D^-½ x) W) — the hook pre-scales the
    layer input, runs the same fused aggregate→linear kernel, and
    post-scales; relu commutes with the positive scale, so the in-kernel
    epilogue still applies (bitwise: relu(c·v) = c·relu(v) picks the
    identical product).  Note the folded forward reassociates the scale
    through the GEMM — logits parity vs unfused is ≤1e-3-tight, not
    bitwise (tests/test_mega_bwd.py pins 3-epoch parity).

    Each record carries the matched ``aggregate``/``linear`` nodes, the
    resolved activation ("none"/"relu"), ``final`` (the node whose output
    tensor and ckpt tag the fused op takes over), the op indices to
    ``skip`` when fusion succeeds, ``fold``, and ``gone`` — the output
    tensor ids that never materialize under fusion (the memory
    estimator's accounting input).  Folded ``gone`` excludes the first
    norm's output deliberately: the hook materializes the pre-scaled
    input z = D^-½ x at exactly that shape, so dropping it would
    overstate the win.

    Eligibility — all structural: every intermediate feeds exactly one
    op, the whole chain sits in one builder layer (fusion never crosses
    an ``end_layer`` checkpoint boundary), the aggregate is sum or avg,
    the linear's own activation is none or relu (none for the folded
    shape — GCN's recipe never fuses one), a trailing single-consumer
    relu folds into the epilogue, and no interior intermediate is the
    logits tensor.
    """
    consumers: Dict[int, List[int]] = {}
    for i, op in enumerate(model.ops):
        for t in op.inputs:
            consumers.setdefault(t, []).append(i)
    logits_id = model.logits.id if model.logits is not None else -1

    def sole(out_id, layer):
        """The single same-layer consumer of tensor ``out_id``, or None."""
        cons = consumers.get(out_id, [])
        if len(cons) != 1:
            return None, -1
        nxt = model.ops[cons[0]]
        if nxt.attrs.get("layer") != layer:
            return None, -1
        return nxt, cons[0]

    found: Dict[int, dict] = {}
    for i, op in enumerate(model.ops):
        if op.kind != "aggregate" or op.attrs.get("aggr") not in ("sum",
                                                                  "avg"):
            continue
        if op.out == logits_id:
            continue
        layer = op.attrs.get("layer")
        lin, li = sole(op.out, layer)
        if (lin is None or lin.kind != "linear"
                or lin.attrs.get("activation") not in ("none", "relu")):
            continue
        activation, skip, final = lin.attrs["activation"], [li], lin
        if activation == "none" and lin.out != logits_id:
            nxt, ni = sole(lin.out, layer)
            if (nxt is not None and nxt.kind == "activation"
                    and nxt.attrs.get("mode") == "relu"):
                activation, final = "relu", nxt
                skip.append(ni)
        found[i] = {"aggregate": op, "linear": lin,
                    "activation": activation, "final": final,
                    "skip": tuple(skip), "fold": False,
                    "gone": (op.out,) + ((lin.out,)
                                         if final is not lin else ())}
    for i, op in enumerate(model.ops):
        if (op.kind != "linear" or op.attrs.get("activation") != "none"
                or op.out == logits_id):
            continue
        layer = op.attrs.get("layer")
        n1, i1 = sole(op.out, layer)
        if n1 is None or n1.kind != "norm" or n1.out == logits_id:
            continue
        agg, ia = sole(n1.out, layer)
        if (agg is None or agg.kind != "aggregate"
                or agg.attrs.get("aggr") not in ("sum", "avg")
                or agg.out == logits_id):
            continue
        n2, i2 = sole(agg.out, layer)
        if n2 is None or n2.kind != "norm":
            continue
        activation, skip, final = "none", [i1, ia, i2], n2
        if n2.out != logits_id:
            nxt, ni = sole(n2.out, layer)
            if (nxt is not None and nxt.kind == "activation"
                    and nxt.attrs.get("mode") == "relu"):
                activation, final = "relu", nxt
                skip.append(ni)
        found[i] = {"aggregate": agg, "linear": op,
                    "activation": activation, "final": final,
                    "skip": tuple(skip), "fold": True,
                    "gone": (op.out, agg.out) + ((n2.out,)
                                                 if final is not n2 else ())}
    return found


def attention_drop(op: "OpNode", key, train: bool):
    """The ``drop`` argument of ``GraphCtx.attend`` for one gat op in one
    step: (the step's key folded with the op's dropout slot, the rate) in
    training when the op drops coefficients, else None.  The one place the
    key is derived, so a test can ask ops.edge.attention_keep for the very
    mask a step used."""
    rate = op.attrs.get("attn_drop", 0.0)
    if not (train and rate):
        return None
    assert key is not None, "training attention dropout needs a PRNG key"
    return jax.random.fold_in(key, op.attrs["slot"]), rate


def gat_matches(model: "Model") -> Dict[int, dict]:
    """``gat`` ops by op index — the round-19 fused-attention accounting
    map (ops/pallas/gat.py).

    Deliberately SEPARATE from ``mega_matches``: those records feed
    ``fuse_linear`` dispatch and ``mega_bwd_cotangent_drop``, and each
    carries an ``aggregate``+``linear`` pair — a gat record has neither,
    so joining the same dict would crash every consumer.  The attention
    megakernel also declines to chain into the trailing concat→linear:
    the fused grid emits the gat output as head-stacked lane planes
    ``[rows, heads·head_dim]`` while the next layer's linear consumes
    row-major feature tiles, so an in-VMEM hand-off would need a
    cross-lane transpose pass costing more than the HBM round trip it
    saves.  Fusion dispatch happens inside the ``gat_attend_binned``
    custom_vjp instead (trace-time decline ladder, ops/edge.py); this map
    only drives the memory estimator's residual pricing.
    """
    found: Dict[int, dict] = {}
    for i, op in enumerate(model.ops):
        if op.kind == "gat":
            found[i] = {"gat": op, "heads": int(op.attrs["heads"]),
                        "head_dim": int(op.attrs["head_dim"])}
    return found


def mega_regions(model: "Model", max_depth: int = 0,
                 train: bool = False) -> Dict[int, dict]:
    """Chain ``mega_matches`` records into multi-layer fusion regions
    (round 16): aggregate→linear(→relu)→aggregate→linear…, keyed by the
    FIRST member's head-op index (the same index `apply` dispatches on,
    so a declined region falls through to that member's per-layer match
    byte-identically).

    A chain link exists when member l's ``final`` output reaches member
    l+1's head op through identity interstitials only — each hop single-
    consumer, and the only interstitial kind admitted is a dropout that
    is the identity (rate == 0.0, or eval mode).  Eligibility beyond the
    per-member ``mega_matches`` gates: every member aggregates with
    ``sum`` (avg's divide-by-degree runs outside the kernel and would
    break the in-VMEM hand-off), ``fold`` is uniform across members (the
    kernel applies one boundary epilogue shape), and no member's
    ``final`` output is the logits tensor — the classifier layer never
    fuses into a region, because its output must exist in HBM for the
    loss anyway, so fusing it saves nothing and would force the region
    backward to start from a softmax cotangent the kernel cannot see.

    ``max_depth`` is the static region-length cap from
    ``GraphCtx.fusion_depth``: 1 disables chaining entirely (returns {}),
    2 caps chains at two members, 0 means unlimited.  Chains are maximal
    under the cap and greedy from the earliest head, so the partition of
    matches into regions is deterministic — tools/preflight.sh pins the
    region plan JSON byte-identical across runs.

    Each record carries ``members`` (the ordered per-layer match
    records), ``final`` (the last member's final node, whose output
    tensor and ckpt tag the fused region takes over), ``skip`` (every op
    index the region replaces except the dispatch head), ``fold``, and
    ``gone`` — the members' per-layer ``gone`` tensors plus the interior
    members' final outputs and interstitial outputs, i.e. exactly the
    inter-layer boundaries that never materialize in HBM (the memory
    estimator's kept/dropped input; the region INPUT and OUTPUT survive).
    """
    if max_depth == 1:
        return {}
    matches = mega_matches(model)
    if not matches:
        return {}
    consumers: Dict[int, List[int]] = {}
    for i, op in enumerate(model.ops):
        for t in op.inputs:
            consumers.setdefault(t, []).append(i)
    logits_id = model.logits.id if model.logits is not None else -1

    def eligible(m):
        return (m["aggregate"].attrs.get("aggr") == "sum"
                and m["final"].out != logits_id)

    # next-link map: match head index -> (next head index, interstitial
    # op indices, interstitial output tensor ids)
    nxt: Dict[int, tuple] = {}
    for i, m in matches.items():
        if not eligible(m):
            continue
        tid, inter_ops, inter_outs = m["final"].out, [], []
        while True:
            cons = consumers.get(tid, [])
            if len(cons) != 1:
                break
            ci = cons[0]
            op = model.ops[ci]
            if op.inputs[0] != tid:
                break
            if ci in matches and eligible(matches[ci]):
                nxt[i] = (ci, tuple(inter_ops), tuple(inter_outs))
                break
            if op.kind == "dropout" and (op.attrs.get("rate") == 0.0
                                         or not train):
                inter_ops.append(ci)
                inter_outs.append(op.out)
                tid = op.out
                continue
            break

    # greedy maximal chains in ascending head order: links only run
    # forward in the (topologically ordered) op list, so by the time a
    # head is visited its predecessor — if any — has been consumed, and
    # a capped chain's tail starts its own region deterministically
    preds: Dict[int, int] = {}
    for i, (j, _, _) in nxt.items():
        preds[j] = i
    found: Dict[int, dict] = {}
    used: set = set()
    for h in sorted(set(nxt) | set(preds)):
        if h in used:
            continue
        p = preds.get(h)
        if p is not None and p not in used:
            continue
        fold = matches[h]["fold"]
        chain, i = [h], h
        while i in nxt and (max_depth == 0 or len(chain) < max_depth):
            j, _, _ = nxt[i]
            if j in used or matches[j]["fold"] != fold:
                break
            chain.append(j)
            i = j
        used.update(chain)
        if len(chain) < 2:
            continue
        members = tuple(matches[k] for k in chain)
        skip: List[int] = list(members[0]["skip"])
        gone: List[int] = list(members[0]["gone"])
        for k_prev, k in zip(chain, chain[1:]):
            _, inter_ops, inter_outs = nxt[k_prev]
            skip.extend(inter_ops)
            gone.extend(inter_outs)
            gone.append(matches[k_prev]["final"].out)
            skip.append(k)
            skip.extend(matches[k]["skip"])
            gone.extend(matches[k]["gone"])
        found[h] = {"members": members, "final": members[-1]["final"],
                    "fold": fold, "skip": tuple(skip),
                    "gone": tuple(dict.fromkeys(gone))}
    return found


class Model:
    """Builder + applier for a GNN op graph over node tensors."""

    def __init__(self, in_dim: int):
        self._next_id = 1
        self.input = TensorRef(0, in_dim)
        self.ops: List[OpNode] = []
        self.logits: Optional[TensorRef] = None
        self.num_linear = 0
        self.num_dropout = 0
        self._cur_layer = 0

    # -- builder API (names mirror the reference's Model methods) ---------
    def _new(self, dim: int) -> TensorRef:
        t = TensorRef(self._next_id, dim)
        self._next_id += 1
        return t

    def _emit(self, op: OpNode) -> None:
        """Append ``op``, stamping the memory planner's attrs: the current
        layer index and a stable checkpoint name (derived from the op IR,
        so a given builder config always yields the same name set)."""
        op.attrs["layer"] = self._cur_layer
        op.attrs["ckpt"] = f"L{self._cur_layer}.{op.kind}{op.out}"
        op.attrs["ckpt_save"] = op.kind in CKPT_SAVE_KINDS
        self.ops.append(op)

    def end_layer(self) -> None:
        """Close the current GNN layer: marks the last emitted op as the
        layer boundary (always saved under an active plan — it is the next
        layer's input) and starts the next layer index."""
        if self.ops and self.ops[-1].attrs["layer"] == self._cur_layer:
            self.ops[-1].attrs["ckpt_boundary"] = True
            self.ops[-1].attrs["ckpt_save"] = True
        self._cur_layer += 1

    @property
    def num_layers(self) -> int:
        """Number of closed layers (builders call end_layer per GNN layer)."""
        return max(self._cur_layer, 1)

    def dropout(self, t: TensorRef, rate: float) -> TensorRef:
        out = self._new(t.dim)
        self._emit(OpNode("dropout", (t.id,), out.id,
                          {"rate": rate, "slot": self.num_dropout}))
        self.num_dropout += 1
        return out

    def linear(self, t: TensorRef, out_dim: int,
               activation: str = "none") -> TensorRef:
        out = self._new(out_dim)
        self._emit(OpNode("linear", (t.id,), out.id,
                          {"in_dim": t.dim, "out_dim": out_dim,
                           "activation": activation,
                           "param": f"linear_{self.num_linear}"}))
        self.num_linear += 1
        return out

    def indegree_norm(self, t: TensorRef) -> TensorRef:
        out = self._new(t.dim)
        self._emit(OpNode("norm", (t.id,), out.id, {}))
        return out

    def scatter_gather(self, t: TensorRef, aggr: str = "sum") -> TensorRef:
        out = self._new(t.dim)
        self._emit(OpNode("aggregate", (t.id,), out.id, {"aggr": aggr}))
        return out

    def gat(self, t: TensorRef, head_dim: int, heads: int = 1,
            slope: float = 0.2, attn_drop: float = 0.0) -> TensorRef:
        """Multi-head graph-attention layer (W-projection + attention
        aggregation, heads concatenated).  Exercises the edge-tensor path
        the reference left latent (create_edge_tensor, gnn.cc:534-589).
        ``attn_drop``: dropout rate on the normalised attention
        coefficients in training (Velickovic et al. section 3.3); it takes
        a dropout slot of its own, so its mask is independent of every
        input dropout's."""
        out = self._new(head_dim * heads)
        attrs = {"in_dim": t.dim, "head_dim": head_dim,
                 "heads": heads, "slope": slope, "attn_drop": attn_drop,
                 "param": f"gat_{self.num_linear}"}
        if attn_drop:
            attrs["slot"] = self.num_dropout
            self.num_dropout += 1
        self._emit(OpNode("gat", (t.id,), out.id, attrs))
        self.num_linear += 1
        return out

    def relu(self, t: TensorRef) -> TensorRef:
        return self._activation(t, "relu")

    def sigmoid(self, t: TensorRef) -> TensorRef:
        return self._activation(t, "sigmoid")

    def elu(self, t: TensorRef) -> TensorRef:
        return self._activation(t, "elu")

    def _activation(self, t: TensorRef, mode: str) -> TensorRef:
        out = self._new(t.dim)
        self._emit(OpNode("activation", (t.id,), out.id, {"mode": mode}))
        return out

    def add(self, a: TensorRef, b: TensorRef) -> TensorRef:
        assert a.dim == b.dim
        out = self._new(a.dim)
        self._emit(OpNode("add", (a.id, b.id), out.id, {}))
        return out

    def softmax_cross_entropy(self, t: TensorRef) -> TensorRef:
        """Marks ``t`` as the logits tensor.  Loss/metrics themselves live in
        roc_tpu.ops.softmax (the reference's fwd is a no-op in train mode
        too, softmax.cc:45-55)."""
        self.logits = t
        return t

    # -- parameters -------------------------------------------------------
    def init_params(self, key) -> Dict[str, jnp.ndarray]:
        """Glorot-uniform per linear op, one fold_in per parameter —
        mirroring the driver's one-srand-seed-many-draws structure
        (initializer.cc:38)."""
        params = {}
        i = 0
        for op in self.ops:
            if op.kind == "linear":
                k = jax.random.fold_in(key, i)
                params[op.attrs["param"]] = ops.glorot_uniform(
                    k, op.attrs["in_dim"], op.attrs["out_dim"])
                i += 1
            elif op.kind == "gat":
                name = op.attrs["param"]
                kk, fd = op.attrs["heads"], op.attrs["head_dim"]
                k = jax.random.fold_in(key, i)
                params[name + "_w"] = ops.glorot_uniform(
                    k, op.attrs["in_dim"], kk * fd)
                for j, suff in enumerate(("_asrc", "_adst")):
                    ka = jax.random.fold_in(k, j + 1)
                    params[name + suff] = ops.glorot_uniform(
                        ka, kk * fd, 1).reshape(kk, fd)
                i += 1
        return params

    def keep_masks(self, key, num_nodes: int, num_edges: int) -> dict:
        """The keep masks a training step with ``key`` draws on one device,
        by op index: [N, d] bool for a dropout op, [K, E] bool (in-edges in
        CSR order) for a gat op that drops coefficients.  Drawn through the
        very functions the step calls (ops.dropout_keep,
        ops.edge.attention_keep) from the same folded keys: a test compares
        training-mode arithmetic with a reference that is GIVEN the masks."""
        from roc_tpu.memory.estimator import _op_out_dims
        from roc_tpu.ops.dropout import dropout_keep
        from roc_tpu.ops.edge import attention_keep
        dims, masks = _op_out_dims(self), {}
        for idx, op in enumerate(self.ops):
            if op.kind == "gat":
                drop = attention_drop(op, key, True)
                if drop is not None:
                    masks[idx] = attention_keep(
                        drop[0], drop[1], op.attrs["heads"], num_edges)
            elif op.kind == "dropout" and op.attrs["rate"]:
                masks[idx] = dropout_keep(
                    jax.random.fold_in(key, op.attrs["slot"]),
                    op.attrs["rate"], (num_nodes, dims[op.inputs[0]]))
        return masks

    # -- execution --------------------------------------------------------
    def apply(self, params: Dict[str, Any], x: jnp.ndarray, gctx: GraphCtx,
              key=None, train: bool = False,
              ckpt_names: bool = False) -> jnp.ndarray:
        """Run the op list; returns logits ([N_local, C]).

        ``ckpt_names=True`` tags every op output with its stable
        ``checkpoint_name`` so a surrounding ``jax.checkpoint`` with a
        ``save_only_these_names`` policy (roc_tpu/memory/policy.py) can pick
        residuals.  Off by default: untagged programs are byte-identical to
        the pre-planner ones, which the HLO budget audit pins."""
        vals: Dict[int, jnp.ndarray] = {0: x}
        matches = mega_matches(self) if gctx.fuse_linear is not None else {}
        regions = (mega_regions(self, gctx.fusion_depth, train)
                   if gctx.fuse_region is not None
                   and gctx.fusion_depth != 1 else {})
        skipped: set = set()
        for idx, op in enumerate(self.ops):
            if idx in skipped:
                continue
            a = vals[op.inputs[0]]
            if idx in regions:
                r = regions[idx]
                fused = gctx.fuse_region(
                    a, tuple(params[m["linear"].attrs["param"]]
                             for m in r["members"]),
                    tuple(m["activation"] for m in r["members"]),
                    r["fold"])
                if fused is not None:
                    if ckpt_names:
                        fused = _checkpoint_name(fused,
                                                 r["final"].attrs["ckpt"])
                    vals[r["final"].out] = fused
                    skipped.update(r["skip"])
                    continue
                # declined region: fall through to the per-layer match at
                # this same index — byte-identical to fusion_depth=1
            if idx in matches:
                m = matches[idx]
                fused = gctx.fuse_linear(
                    a, params[m["linear"].attrs["param"]],
                    m["activation"], m["aggregate"].attrs["aggr"],
                    m["fold"])
                if fused is not None:
                    if ckpt_names:
                        fused = _checkpoint_name(fused,
                                                 m["final"].attrs["ckpt"])
                    vals[m["final"].out] = fused
                    skipped.update(m["skip"])
                    continue
            if op.kind == "dropout":
                if train:
                    assert key is not None, "training dropout needs a PRNG key"
                    k = jax.random.fold_in(key, op.attrs["slot"])
                else:
                    k = None
                out = ops.dropout(k, a, op.attrs["rate"], train)
            elif op.kind == "linear":
                out = ops.linear(a, params[op.attrs["param"]],
                                 op.attrs["activation"])
            elif op.kind == "norm":
                out = ops.indegree_norm(a, gctx.in_degree)
            elif op.kind == "aggregate":
                out = gctx.aggregate(a, op.attrs["aggr"])
            elif op.kind == "gat":
                assert gctx.attend is not None, \
                    "this GraphCtx was built without attention support"
                name = op.attrs["param"]
                kk, fd = op.attrs["heads"], op.attrs["head_dim"]
                h = ops.linear(a, params[name + "_w"]).reshape(-1, kk, fd)
                out = gctx.attend(h, params[name + "_asrc"],
                                  params[name + "_adst"], op.attrs["slope"],
                                  attention_drop(op, key, train)
                                  ).reshape(-1, kk * fd)
            elif op.kind == "activation":
                out = ops.apply_activation(a, op.attrs["mode"])
            elif op.kind == "add":
                out = ops.add(a, vals[op.inputs[1]])
            else:
                raise ValueError(f"unknown op kind {op.kind!r}")
            if ckpt_names:
                out = _checkpoint_name(out, op.attrs["ckpt"])
            vals[op.out] = out
        assert self.logits is not None, "call softmax_cross_entropy() last"
        return vals[self.logits.id]

    def loss(self, params, x, labels, mask, gctx, key=None,
             train: bool = True):
        logits = self.apply(params, x, gctx, key=key, train=train)
        return ops.masked_softmax_cross_entropy(logits, labels, mask)
