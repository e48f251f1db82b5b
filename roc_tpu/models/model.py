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
from roc_tpu.obs import scopes

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
    # attention whose score reads both rows at every edge (a gat op whose
    # score is "dot" or "dynamic"): (score, tables, drop, **attrs) -> [N,
    # K, F], ``drop`` as above; "dot": tables (q, k, v) [N,K,F] each;
    # "dynamic": (xl [N,K,F], xr [N,K,F], a [K,F]) and ``slope``.  Only the
    # one-chip trainer builds it; the other roads refuse such an op.
    attend_pair: Optional[Callable] = None


@dataclasses.dataclass(frozen=True)
class TensorRef:
    """Symbolic handle returned by builder methods (the reference's Tensor)."""
    id: int
    dim: int


@dataclasses.dataclass(frozen=True)
class OpNode:
    kind: str                 # dropout|linear|norm|aggregate|gat|layernorm|
                              # activation|add
    inputs: tuple             # input tensor ids
    out: int                  # output tensor id
    attrs: dict               # op-specific attributes


def attention_score(op: "OpNode") -> Optional[str]:
    """How an op scores an in-edge: "additive" (GAT's rank-one ``a_dst . h_i
    + a_src . h_j``, ``GraphCtx.attend``), "dot" (the graph transformer's
    ``q_i . k_j / sqrt(d)``) or "dynamic" (GATv2's ``a . LeakyReLU(xr_i +
    xl_j)``), both ``GraphCtx.attend_pair``; None for an op that is no
    attention.  All are ops of kind "gat"; only the one-chip trainer
    carries a score but "additive"."""
    return op.attrs.get("score", "additive") if op.kind == "gat" else None


def attention_heads(op: "OpNode") -> int:
    """Attention heads of a gat op, the rows of its ``[K, E]`` arrays: its
    ``heads`` output groups times the ``mean_heads`` averaged in each."""
    return int(op.attrs["heads"]) * int(op.attrs.get("mean_heads", 1))


# the gat ops only the one-chip Trainer carries, by score: (-model, what,
# the plan road's rule in ops.edge)
PAIR_SCORES = {"dot": ("tconv", "dot-product attention", "tconv_attend_plan"),
               "dynamic": ("gatv2", "dynamic attention",
                           "gatv2_attend_plan")}


def refuse_pair_attention(model: "Model", road: str) -> None:
    """Only the one-chip Trainer carries a gat op whose score is not
    additive (``-model tconv``, ``-model gatv2``): every other road says so
    by name, with the score, when it is built, where running on would treat
    it as the additive op it is not."""
    for op in model.ops:
        score = attention_score(op)
        if score in PAIR_SCORES:
            name, what, rule = PAIR_SCORES[score]
            raise ValueError(
                f"-model {name}: the {name} op ({what}, score {score!r}, "
                f"ops.edge.{rule}) is not carried by {road}; it trains on "
                f"the one-chip Trainer (-parts 1, no -stream)")


def linear_bias(op: "OpNode", params):
    """The bias row of a linear op that has one (``Model.linear(...,
    bias=True)``: parameter ``<param>_bias``), else None."""
    return params[op.attrs["param"] + "_bias"] if op.attrs.get("bias") \
        else None


def attention_drop(op: "OpNode", key, train: bool):
    """The ``drop`` argument of ``GraphCtx.attend`` / ``attend_pair`` for one
    gat op (any score) in one
    step: (the step's key folded with the op's dropout slot, the rate) in
    training when the op drops coefficients, else None.  The one place the
    key is derived, so a test can ask ops.edge.attention_keep for the very
    mask a step used."""
    rate = op.attrs.get("attn_drop", 0.0)
    if not (train and rate):
        return None
    assert key is not None, "training attention dropout needs a PRNG key"
    return jax.random.fold_in(key, op.attrs["slot"]), rate


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
               activation: str = "none", bias: bool = False) -> TensorRef:
        """``t W`` (+ activation).  ``bias``: add a learned ``[out_dim]``
        row, parameter ``<param>_bias``, zero at the start; off by default
        (the reference has none, linear.cc:39-44)."""
        out = self._new(out_dim)
        attrs = {"in_dim": t.dim, "out_dim": out_dim,
                 "activation": activation,
                 "param": f"linear_{self.num_linear}"}
        if bias:
            attrs["bias"] = True
        self._emit(OpNode("linear", (t.id,), out.id, attrs))
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

    def tconv(self, t: TensorRef, head_dim: int, heads: int = 1,
              mean_heads: int = 1, attn_drop: float = 0.0) -> TensorRef:
        """Graph Transformer operator with gated residual (Shi et al.,
        UniMP, arXiv:2009.03509 eqs 3-5; PyG ``TransformerConv(heads,
        concat, beta=True, dropout)``): biased projections to queries, keys
        and values, multi-head dot-product attention over in-edges, and a
        learned per-node gate between the attention output and a biased
        linear skip of the input.  A gat op whose ``score`` is "dot"
        (:func:`attention_score`).  The output concatenates ``heads`` groups
        of width ``head_dim``, each the MEAN of ``mean_heads`` attention
        heads: PyG's ``concat=True`` is (heads, 1), ``concat=False`` (1,
        heads).  ``attn_drop`` as for :meth:`gat`."""
        out = self._new(head_dim * heads)
        attrs = {"in_dim": t.dim, "head_dim": head_dim, "heads": heads,
                 "mean_heads": mean_heads, "score": "dot",
                 "attn_drop": attn_drop,
                 "param": f"tconv_{self.num_linear}"}
        if attn_drop:
            attrs["slot"] = self.num_dropout
            self.num_dropout += 1
        self._emit(OpNode("gat", (t.id,), out.id, attrs))
        self.num_linear += 1
        return out

    def gatv2(self, t: TensorRef, head_dim: int, heads: int = 1,
              slope: float = 0.2, attn_drop: float = 0.0) -> TensorRef:
        """GATv2's dynamic attention (Brody, Alon, Yahav, ICLR 2022,
        arXiv:2105.14491 eq 7; PyG ``GATv2Conv(heads, concat=True,
        share_weights=False, bias=False)``): xl = t Wl for sources and
        messages, xr = t Wr for targets, the score of j -> i is
        ``a . LeakyReLU(xr_i + xl_j)`` per head, and the heads' weighted
        sums of xl rows are concatenated.  A gat op whose ``score`` is
        "dynamic" (:func:`attention_score`); ``attn_drop`` as for
        :meth:`gat`."""
        out = self._new(head_dim * heads)
        attrs = {"in_dim": t.dim, "head_dim": head_dim, "heads": heads,
                 "slope": slope, "score": "dynamic", "attn_drop": attn_drop,
                 "param": f"gatv2_{self.num_linear}"}
        if attn_drop:
            attrs["slot"] = self.num_dropout
            self.num_dropout += 1
        self._emit(OpNode("gat", (t.id,), out.id, attrs))
        self.num_linear += 1
        return out

    def layer_norm(self, t: TensorRef) -> TensorRef:
        """Row LayerNorm with gain and bias (ops.layer_norm)."""
        out = self._new(t.dim)
        n = sum(op.kind == "layernorm" for op in self.ops)
        self._emit(OpNode("layernorm", (t.id,), out.id,
                          {"dim": t.dim, "param": f"ln_{n}"}))
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

    def add(self, a: TensorRef, b: TensorRef, wa: Optional[float] = None,
            wb: Optional[float] = None) -> TensorRef:
        """``a + b``, or ``wa * a + wb * b`` with scalar weights fixed at
        build time (a weight left out is 1; without any the op is the
        reference's ADD and its attrs stay empty)."""
        assert a.dim == b.dim
        out = self._new(a.dim)
        attrs = {k: float(w) for k, w in (("wa", wa), ("wb", wb))
                 if w is not None}
        self._emit(OpNode("add", (a.id, b.id), out.id, attrs))
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
                if op.attrs.get("bias"):
                    params[op.attrs["param"] + "_bias"] = jnp.zeros(
                        (op.attrs["out_dim"],), jnp.float32)
                i += 1
            elif attention_score(op) == "dot":
                # Glorot weights, zero biases (the paper states neither)
                name, k = op.attrs["param"], jax.random.fold_in(key, i)
                out = op.attrs["heads"] * op.attrs["head_dim"]
                proj = attention_heads(op) * op.attrs["head_dim"]
                widths = {"q": proj, "k": proj, "v": proj, "r": out}
                for j, (s, width) in enumerate(widths.items()):
                    params[f"{name}_w{s}"] = ops.glorot_uniform(
                        jax.random.fold_in(k, j + 1), op.attrs["in_dim"],
                        width)
                    params[f"{name}_b{s}"] = jnp.zeros((width,), jnp.float32)
                params[name + "_wg"] = ops.glorot_uniform(
                    jax.random.fold_in(k, 5), 3 * out, 1)[:, 0]
                i += 1
            elif attention_score(op) == "dynamic":
                # Glorot Wl, Wr and a, no bias (the paper's equations)
                name, k = op.attrs["param"], jax.random.fold_in(key, i)
                kk, fd = op.attrs["heads"], op.attrs["head_dim"]
                for j, s in enumerate(("wl", "wr")):
                    params[f"{name}_{s}"] = ops.glorot_uniform(
                        jax.random.fold_in(k, j + 1), op.attrs["in_dim"],
                        kk * fd)
                params[name + "_a"] = ops.glorot_uniform(
                    jax.random.fold_in(k, 3), kk * fd, 1).reshape(kk, fd)
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
            elif op.kind == "layernorm":
                name = op.attrs["param"]
                params[name + "_gain"] = jnp.ones((op.attrs["dim"],),
                                                  jnp.float32)
                params[name + "_bias"] = jnp.zeros((op.attrs["dim"],),
                                                   jnp.float32)
        return params

    def keep_masks(self, key, num_nodes: int, num_edges: int) -> dict:
        """The keep masks a training step with ``key`` draws on one device,
        by op index: [N, d] bool for a dropout op, [K, E] bool (in-edges in
        CSR order) for a gat op (either score) that drops coefficients.  Drawn
        through the very functions the step calls (ops.dropout_keep,
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
                        drop[0], drop[1], attention_heads(op), num_edges)
            elif op.kind == "dropout" and op.attrs["rate"]:
                masks[idx] = dropout_keep(
                    jax.random.fold_in(key, op.attrs["slot"]),
                    op.attrs["rate"], (num_nodes, dims[op.inputs[0]]))
        return masks

    def layer_segments(self) -> List[tuple]:
        """The closed layers as segments of the op list, in order: (layer,
        op indices, ins, outs).  ``ins``: the tensors the layer's ops read
        that an earlier layer (or the model's input) made: its boundary
        and any FAR input, a tensor read by layers beyond the next, as
        GCNII's ``H0`` is by every layer.  ``outs``: what it makes that a
        later layer, or the loss, reads.  The memory plan checkpoints the
        forward pass a segment at a time (roc_tpu/memory/policy.py): a
        segment's ``ins`` are live from forward to backward whatever the
        plan decides, so no segment recomputes another's output."""
        by_layer: Dict[int, List[int]] = {}
        for index, op in enumerate(self.ops):
            by_layer.setdefault(op.attrs.get("layer", 0), []).append(index)
        last_read = {self.logits.id: len(self.ops)} if self.logits else {}
        for index, op in enumerate(self.ops):
            for t in op.inputs:
                last_read[t] = max(last_read.get(t, -1), index)
        segments = []
        for layer in sorted(by_layer):
            indices = by_layer[layer]
            made = {self.ops[i].out for i in indices}
            ins = tuple(dict.fromkeys(
                t for i in indices for t in self.ops[i].inputs
                if t not in made))
            outs = tuple(self.ops[i].out for i in indices
                         if last_read.get(self.ops[i].out, -1) > indices[-1])
            segments.append((layer, tuple(indices), ins, outs))
        return segments

    def pinned_outputs(self) -> set:
        """Ids of the tensors a later layer (or the loss) reads: every
        segment's ``outs``, live from forward to backward under every
        memory plan because they are a later segment's inputs."""
        return {t for _, _, _, outs in self.layer_segments() for t in outs}

    def far_outputs(self) -> Dict[int, int]:
        """{tensor id: its producer's layer} of every FAR input
        (:meth:`layer_segments`): read by a layer beyond the one after its
        producer's, so live across more than one boundary."""
        layer_of = {op.out: op.attrs.get("layer", 0) for op in self.ops}
        return {t: layer_of[t] for layer, _, ins, _ in self.layer_segments()
                for t in ins if t in layer_of and layer > layer_of[t] + 1}

    # -- execution --------------------------------------------------------
    def apply(self, params: Dict[str, Any], x: jnp.ndarray, gctx: GraphCtx,
              key=None, train: bool = False, ckpt_names: bool = False,
              wrap_layer: Optional[Callable] = None) -> jnp.ndarray:
        """Run the op list; returns logits ([N_local, C]).

        ``ckpt_names=True`` tags every op output with its stable
        ``checkpoint_name`` so a ``jax.checkpoint`` with a
        ``save_only_these_names`` policy (roc_tpu/memory/policy.py) can pick
        residuals.  Off by default: untagged programs are byte-identical to
        the pre-planner ones, which the HLO budget audit pins.

        ``wrap_layer(layer, fn) -> fn`` runs the ops a closed layer at a
        time (:meth:`layer_segments`), each layer's function ``fn(params,
        *ins) -> outs`` handed through it first: how an active memory plan
        puts its checkpoint around every layer.  None: one flat loop."""
        vals: Dict[int, jnp.ndarray] = {0: x}

        def run_ops(indices, p, vals):
            for index in indices:
                op = self.ops[index]
                # the op's device scope, set here and nowhere else
                with scopes.scope(scopes.op_scope(index, op.kind)):
                    vals[op.out] = self._apply_op(op, p, vals, gctx, key,
                                                  train, ckpt_names)

        if wrap_layer is None:
            run_ops(range(len(self.ops)), params, vals)
        else:
            for layer, indices, ins, outs in self.layer_segments():
                def segment(p, *args, indices=indices, ins=ins, outs=outs):
                    local = dict(zip(ins, args))
                    run_ops(indices, p, local)
                    return tuple(local[t] for t in outs)

                got = wrap_layer(layer, segment)(
                    params, *(vals[t] for t in ins))
                vals.update(zip(outs, got))
        assert self.logits is not None, "call softmax_cross_entropy() last"
        return vals[self.logits.id]

    def _apply_op(self, op: OpNode, params, vals, gctx: GraphCtx, key,
                  train: bool, ckpt_names: bool):
        """One op of the list: its output from the values so far."""
        a = vals[op.inputs[0]]
        if op.kind == "dropout":
            if train:
                assert key is not None, "training dropout needs a PRNG key"
                k = jax.random.fold_in(key, op.attrs["slot"])
            else:
                k = None
            out = ops.dropout(k, a, op.attrs["rate"], train)
        elif op.kind == "linear":
            out = ops.linear(a, params[op.attrs["param"]],
                             op.attrs["activation"], linear_bias(op, params))
        elif op.kind == "norm":
            out = ops.indegree_norm(a, gctx.in_degree)
        elif op.kind == "aggregate":
            out = gctx.aggregate(a, op.attrs["aggr"])
        elif attention_score(op) == "dot":
            out = self._apply_tconv(op, params, a, gctx,
                                    attention_drop(op, key, train))
        elif attention_score(op) == "dynamic":
            assert gctx.attend_pair is not None, \
                "this GraphCtx was built without dynamic attention support"
            name = op.attrs["param"]
            kk, fd = op.attrs["heads"], op.attrs["head_dim"]
            xl, xr = (ops.linear(a, params[f"{name}_{s}"]).reshape(-1, kk, fd)
                      for s in ("wl", "wr"))
            out = gctx.attend_pair("dynamic", (xl, xr, params[name + "_a"]),
                                   attention_drop(op, key, train),
                                   slope=op.attrs["slope"]).reshape(-1,
                                                                    kk * fd)
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
        elif op.kind == "layernorm":
            name = op.attrs["param"]
            out = ops.layer_norm(a, params[name + "_gain"],
                                 params[name + "_bias"])
        elif op.kind == "activation":
            out = ops.apply_activation(a, op.attrs["mode"])
        elif op.kind == "add":
            out = ops.add(a, vals[op.inputs[1]], op.attrs.get("wa"),
                          op.attrs.get("wb"))
        else:
            raise ValueError(f"unknown op kind {op.kind!r}")
        if ckpt_names:
            out = _checkpoint_name(out, op.attrs["ckpt"])
        return out

    @staticmethod
    def _apply_tconv(op: OpNode, params, x, gctx: GraphCtx, drop):
        """One dot-score gat op (builder docstring: :meth:`tconv`): m =
        attention over in-edges of the projected rows; r = x Wr + br; b =
        sigmoid(wg . [m ; r ; m - r]); out = (1 - b) m + b r.  The gate's
        products are float32 at "highest", like the scores."""
        assert gctx.attend_pair is not None, \
            "this GraphCtx was built without dot-product attention support"
        name = op.attrs["param"]
        kk, fd = attention_heads(op), op.attrs["head_dim"]
        groups, per = op.attrs["heads"], op.attrs.get("mean_heads", 1)

        def proj(s):
            return ops.linear(x, params[f"{name}_w{s}"]) \
                + params[f"{name}_b{s}"].astype(x.dtype)

        m = gctx.attend_pair("dot", tuple(proj(s).reshape(-1, kk, fd)
                                          for s in "qkv"), drop)
        if per > 1:     # average within a group, then concatenate groups
            m = jnp.mean(m.reshape(-1, groups, per, fd), axis=2)
        m = m.reshape(-1, groups * fd)
        r = proj("r")
        wm, wr, wd = jnp.split(params[name + "_wg"].astype(jnp.float32), 3)
        m32, r32 = m.astype(jnp.float32), r.astype(jnp.float32)
        gate = jax.nn.sigmoid(
            jnp.dot(m32, wm, precision="highest")
            + jnp.dot(r32, wr, precision="highest")
            + jnp.dot(m32 - r32, wd, precision="highest"))[:, None]
        return ((1.0 - gate) * m32 + gate * r32).astype(x.dtype)

    def loss(self, params, x, labels, mask, gctx, key=None,
             train: bool = True):
        logits = self.apply(params, x, gctx, key=key, train=train)
        return ops.masked_softmax_cross_entropy(logits, labels, mask)
