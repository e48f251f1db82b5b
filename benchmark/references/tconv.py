"""Plain reference for the graph-transformer family (`-model tconv`): float32
`jax.numpy`, `segment_max`, `segment_sum`, `jnp.take`, matmuls at `highest`
precision, no kernels, no plans, no [K, E] layouts.

Written from Shi, Huang, Feng, Zhong, Wang, Sun, "Masked Label Prediction:
Unified Message Passing Model for Semi-Supervised Classification" (UniMP),
IJCAI 2021, arXiv:2009.03509, equations 3-5 (the Graph Transformer operator
with gated residual; PyTorch Geometric's `TransformerConv(heads, concat,
beta=True, dropout)`), independent of `roc_tpu/ops` and `roc_tpu/models`.
For C heads of width d, D = C d, and N(i) the in-neighbours of i (self-edge
included, as the graph carries it), hidden layer l computes

    x      = dropout(h, p)
    q_c,i  = x_i Wq_c + bq_c     k_c,j = x_j Wk_c + bk_c     v_c,j = x_j Wv_c + bv_c
    s_c,ij = q_c,i . k_c,j / sqrt(d)                          j in N(i)
    a_c,ij = exp(s_c,ij - m_c,i) / sum_u exp(s_c,iu - m_c,i),  m_c,i = max_j s_c,ij
    a~_c,ij = a_c,ij * keep_c,ij / (1 - p)        (training; not renormalised)
    m_i    = concat_c sum_j a~_c,ij v_c,j                     [D]
    r_i    = x_i Wr + br                                      [D]
    b_i    = sigmoid(wg . [m_i ; r_i ; m_i - r_i])            wg in R^{3D}
    h'_i   = ReLU(LayerNorm((1 - b_i) m_i + b_i r_i))         eps 1e-5

and the output layer AVERAGES its C heads, each as wide as the classes
(m_i = 1/C sum_c ...), has r_i and wg at that width, and gives the logits
(1 - b) m + b r with no LayerNorm and no ReLU.  The loss is the unreduced
sum of softmax cross-entropy over the train rows, as for every model of the
program.  Evaluation drops nothing.

Departures from the paper, all shared with the program under test:
  * no masked-label input (the paper adds a label embedding to the features
    of a random share of the training nodes): a product in front of the
    model, not a mechanism of the layer;
  * the loss is summed, not averaged, over the train rows;
  * weight decay is the optimiser's, not part of this loss.

Parameters arrive as the trainer's dict: `tconv_<i>_w{q,k,v,r}` [d_in, *],
`tconv_<i>_b{q,k,v,r}`, `tconv_<i>_wg` [3 x out], in recipe order by <i>,
and `ln_<j>_gain`, `ln_<j>_bias` for hidden layer j.  The head count is read
from the output layer's shapes (its projections are C times as wide as its
skip) and the layers' own (`layers`: a hidden entry is the concatenated
width).

The edge list is walked in fixed blocks of destination rows (in-edge CSR
order: a block of rows owns a contiguous run of edges, so every softmax is
whole inside its block), each padded to the longest block's edge count, so
the gathered [block edges, C, d] rows of q, k and v are the largest
temporaries: whole, [E, 4, 32] float32 at the Reddit shape is 12 GB each.
The block body is rematerialised under differentiation for the same reason.

Training-mode arithmetic can be compared too: `loss_and_grads` takes the
keep masks (per-edge-and-head for the coefficients, per-feature for the
inputs) that the program drew, and applies them as the equations say.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

# the in-edge CSR in blocks of destination rows, as the GAT reference walks it
from benchmark.references.gat import MASK_TRAIN, ROW_BLOCK, edge_blocks

LN_EPS = 1e-5
PARTS = ("wq", "bq", "wk", "bk", "wv", "bv", "wr", "br", "wg")


def layer_names(params: dict) -> list:
    """The layers' parameter prefixes (`tconv_0`, `tconv_1`, ...) in recipe
    order; any other name but a LayerNorm's is an error."""
    found = set()
    for name in params:
        m = re.fullmatch(r"(tconv_(\d+))_(%s)" % "|".join(PARTS), name)
        if m is not None:
            found.add((int(m.group(2)), m.group(1)))
        elif re.fullmatch(r"ln_\d+_(gain|bias)", name) is None:
            raise ValueError(
                f"the graph-transformer reference knows no parameter {name!r}")
    return [name for _, name in sorted(found)]


def ordered_weights(params: dict) -> list:
    """One dict a layer, float32: the nine arrays of `PARTS`, and `gain`,
    `bias` on hidden layers (LayerNorm j belongs to hidden layer j)."""
    names = layer_names(params)
    out = []
    for j, n in enumerate(names):
        layer = {p: jnp.asarray(params[f"{n}_{p}"], jnp.float32)
                 for p in PARTS}
        if j != len(names) - 1:
            layer["gain"] = jnp.asarray(params[f"ln_{j}_gain"], jnp.float32)
            layer["bias"] = jnp.asarray(params[f"ln_{j}_bias"], jnp.float32)
        out.append(layer)
    return out


def head_count(weights: list) -> int:
    """C: the output layer's projections are C heads as wide as its skip."""
    last = weights[-1]
    return int(last["wq"].shape[1]) // int(last["wr"].shape[1])


def attend(q, k, v, src, dst_local, edge_start, row_block: int,
           edge_keep=None, rate: float = 0.0):
    """Equations 3-4 for one layer: q, k, v [N, C, d] -> [N, C, d].
    ``edge_keep``: [C, E] bool keep mask of the coefficients, or None."""
    n, c, d = q.shape
    blocks, longest = src.shape
    rows_padded = blocks * row_block
    q = jnp.pad(q, ((0, rows_padded - n), (0, 0), (0, 0)))
    scale = 1.0 / np.sqrt(d)
    if edge_keep is not None:
        # [E, C] float multiplier, padded so every block slices in bounds
        mult = jnp.pad(edge_keep.T.astype(jnp.float32) / (1.0 - rate),
                       ((0, longest), (0, 0)))

    @jax.checkpoint
    def block(b):
        s_ids, d_loc, e0 = src[b], dst_local[b], edge_start[b]
        d_in = jnp.minimum(d_loc, row_block - 1)      # pads read a live row
        qe = jnp.take(q, b * row_block + d_in, axis=0)            # [L, C, d]
        s = jnp.sum(qe * jnp.take(k, s_ids, axis=0), axis=-1) * scale
        m = jax.ops.segment_max(s, d_loc, num_segments=row_block,
                                indices_are_sorted=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)        # rows with no in-edge
        e = jnp.exp(s - jnp.take(m, d_in, axis=0))
        z = jax.ops.segment_sum(e, d_loc, num_segments=row_block,
                                indices_are_sorted=True)
        alpha = e / jnp.take(jnp.where(z > 0, z, 1.0), d_in, axis=0)
        if edge_keep is not None:
            alpha = alpha * jax.lax.dynamic_slice(mult, (e0, 0),
                                                  (longest, c))
        return jax.ops.segment_sum(
            alpha[:, :, None] * jnp.take(v, s_ids, axis=0), d_loc,
            num_segments=row_block, indices_are_sorted=True)

    out = jax.lax.map(block, jnp.arange(blocks))      # [B, row_block, C, d]
    return out.reshape(rows_padded, c, d)[:n]


def layer_norm(t, gain, bias):
    mean = jnp.mean(t, axis=-1, keepdims=True)
    var = jnp.mean((t - mean) ** 2, axis=-1, keepdims=True)
    return (t - mean) / jnp.sqrt(var + LN_EPS) * gain + bias


@functools.partial(jax.jit, static_argnames=("heads", "row_block", "rate"))
def logits(weights, x, src, dst_local, edge_start, heads: int,
           row_block: int = ROW_BLOCK, input_keep=None, edge_keep=None,
           rate: float = 0.0):
    """Logits [N, classes].  Evaluation mode unless keep masks are given:
    ``input_keep[l]`` [N, d_l] and ``edge_keep[l]`` [C, E] bool per layer,
    applied at rate ``rate``."""
    t = x.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        for i, w in enumerate(weights):
            last = i == len(weights) - 1
            if input_keep is not None:
                t = jnp.where(input_keep[i], t / (1.0 - rate), 0.0)
            q, k, v = ((t @ w["w" + s] + w["b" + s]).reshape(
                t.shape[0], heads, -1) for s in "qkv")
            m = attend(q, k, v, src, dst_local, edge_start, row_block,
                       None if edge_keep is None else edge_keep[i], rate)
            m = jnp.mean(m, axis=1) if last else m.reshape(t.shape[0], -1)
            r = t @ w["wr"] + w["br"]
            b = jax.nn.sigmoid(
                jnp.concatenate([m, r, m - r], axis=-1) @ w["wg"])[:, None]
            t = (1.0 - b) * m + b * r
            if not last:
                t = jnp.maximum(layer_norm(t, w["gain"], w["bias"]), 0.0)
    return t


def loss(weights, x, src, dst_local, edge_start, label_ids, mask, heads: int,
         row_block: int = ROW_BLOCK, input_keep=None, edge_keep=None,
         rate: float = 0.0):
    """Sum of cross-entropy over train rows (softmax_kernel.cu:19-33 gives
    softmax - onehot, masked, unnormalised: the gradient of this sum)."""
    z = logits(weights, x, src, dst_local, edge_start, heads=heads,
               row_block=row_block, input_keep=input_keep,
               edge_keep=edge_keep, rate=rate)
    logp = jax.nn.log_softmax(z, axis=-1)
    ce = -jnp.take_along_axis(logp, label_ids[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(mask == MASK_TRAIN, ce, 0.0))


def loss_and_grads(params: dict, dataset, layers, row_block: int = ROW_BLOCK,
                   edge_keep=None, input_keep=None, rate: float = 0.0):
    """(loss, {name: gradient}) of the reference on ``dataset`` at the
    trainer's ``params``.  Dropout off unless the program's own keep masks
    are handed in: ``edge_keep`` a list, per layer, of [C, E] bool (the
    coefficients kept, per head and in-edge in CSR order) and
    ``input_keep`` of [N, d] bool (the layer inputs kept), both applied at
    ``rate``; either may be None."""
    names = layer_names(params)
    weights = ordered_weights(params)
    src, dst_local, e0 = edge_blocks(dataset.graph, row_block)
    val, grads = jax.value_and_grad(loss)(
        weights, jnp.asarray(dataset.features), src, dst_local, e0,
        jnp.asarray(dataset.label_ids, jnp.int32),
        jnp.asarray(dataset.mask, jnp.int32), head_count(weights), row_block,
        input_keep, edge_keep, rate)
    out = {}
    for j, (name, g) in enumerate(zip(names, grads)):
        out.update({f"{name}_{p}": g[p] for p in PARTS})
        if "gain" in g:
            out.update({f"ln_{j}_gain": g["gain"], f"ln_{j}_bias": g["bias"]})
    return val, out


def reference_logits(params: dict, dataset, layers, device=None,
                     row_block: int = ROW_BLOCK) -> np.ndarray:
    """Host copy of the reference's evaluation-mode logits for the
    trainer's ``params``."""
    src, dst_local, e0 = edge_blocks(dataset.graph, row_block)
    put = functools.partial(jax.device_put, device=device)
    weights = ordered_weights(params)
    out = logits([{n: put(a) for n, a in layer.items()} for layer in weights],
                 put(dataset.features), put(src), put(dst_local), put(e0),
                 heads=head_count(weights), row_block=row_block)
    return np.asarray(out)
