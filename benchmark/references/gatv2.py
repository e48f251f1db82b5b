"""Plain reference for GATv2 (`-model gatv2`): float32 `jax.numpy`,
`segment_max`, `segment_sum`, `jnp.take`, matmuls at `highest` precision, no
kernels, no plans, no [K, E] layouts.

Written from Brody, Alon, Yahav, "How Attentive are Graph Attention
Networks?", ICLR 2022, arXiv:2105.14491, equation 7 (PyTorch Geometric's
`GATv2Conv(heads, concat=True, negative_slope=0.2, dropout=p,
share_weights=False, bias=False)`), inside the transductive recipe of
Velickovic et al., ICLR 2018, section 3.3, independent of `roc_tpu/ops` and
`roc_tpu/models`.  For K heads of width F and N(i) the in-neighbours of i
(self-edge included, as the graph carries it), layer l computes

    x        = dropout(h, p)
    xl_j     = x_j W_l      xr_i = x_i W_r          W_l, W_r: [d_in, K F]
    s_k,ij   = sum_f a_k,f LeakyReLU_0.2(xr_i,k,f + xl_j,k,f)     j in N(i)
    alpha_ij = exp(s_ij - m_i) / sum_j' exp(s_ij' - m_i),  m_i = max_j s_ij
    alpha~   = alpha * keep / (1 - p)         (training; not renormalised)
    h'_i,k   = sum_j alpha~_k,ij xl_j,k

hidden layers concatenate the K heads and apply ELU; the output layer has
one head of C features and no activation.  LeakyReLU is `where(p >= 0, p,
0.2 p)`, so its derivative at 0 is 1.  The loss is the unreduced sum of
softmax cross-entropy over the train rows, as for every model of the
program.  Evaluation drops nothing.

Departures from the paper, all shared with the program under test:
  * no bias (the paper's equations have none; PyG's default adds one);
  * one p for the input and the coefficient dropout;
  * the loss is summed, not averaged, over the train rows;
  * weight decay is the optimiser's, not part of this loss.

Parameters arrive as the trainer's dict: `gatv2_<i>_wl`, `gatv2_<i>_wr`
[d_in, K*F] and `gatv2_<i>_a` [K, F], in recipe order by <i>; the head count
is read from `a`'s shape.

The edge list is walked in fixed blocks of destination rows (in-edge CSR
order: a block of rows owns a contiguous run of edges, so every softmax is
whole inside its block), each padded to the longest block's edge count, so
the gathered [block edges, K, F] rows and their pre-activation are the
largest temporaries: whole, [E, 8, 8] float32 at the Reddit shape is 6.0 GB
each.  The block body is rematerialised under differentiation for the same
reason.

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
from benchmark.references.gat import MASK_TRAIN, ROW_BLOCK, SLOPE, edge_blocks

PARTS = ("wl", "wr", "a")


def layer_names(params: dict) -> list:
    """The layers' parameter prefixes (`gatv2_0`, `gatv2_1`, ...) in recipe
    order."""
    found = set()
    for name in params:
        m = re.fullmatch(r"(gatv2_(\d+))_(%s)" % "|".join(PARTS), name)
        if m is None:
            raise ValueError(
                f"the GATv2 reference knows no parameter {name!r}")
        found.add((int(m.group(2)), m.group(1)))
    return [name for _, name in sorted(found)]


def ordered_weights(params: dict) -> list:
    """[(W_l, W_r, a)] per layer, float32."""
    return [tuple(jnp.asarray(params[f"{n}_{p}"], jnp.float32)
                  for p in PARTS) for n in layer_names(params)]


def attend(xl, xr, a, src, dst_local, edge_start, row_block: int,
           edge_keep=None, rate: float = 0.0):
    """Equation 7 and the attention of one layer: xl, xr [N, K, F] ->
    [N, K, F].  ``edge_keep``: [K, E] bool keep mask of the coefficients,
    or None."""
    n, k, f = xl.shape
    blocks, longest = src.shape
    rows_padded = blocks * row_block
    xr = jnp.pad(xr, ((0, rows_padded - n), (0, 0), (0, 0)))
    if edge_keep is not None:
        # [E, K] float multiplier, padded so every block slices in bounds
        mult = jnp.pad(edge_keep.T.astype(jnp.float32) / (1.0 - rate),
                       ((0, longest), (0, 0)))

    @jax.checkpoint
    def block(b):
        s_ids, d_loc, e0 = src[b], dst_local[b], edge_start[b]
        d_in = jnp.minimum(d_loc, row_block - 1)      # pads read a live row
        g = jnp.take(xl, s_ids, axis=0)                           # [L, K, F]
        p = jnp.take(xr, b * row_block + d_in, axis=0) + g
        s = jnp.sum(jnp.where(p >= 0, p, SLOPE * p) * a, axis=-1)  # [L, K]
        m = jax.ops.segment_max(s, d_loc, num_segments=row_block,
                                indices_are_sorted=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)        # rows with no in-edge
        e = jnp.exp(s - jnp.take(m, d_in, axis=0))
        z = jax.ops.segment_sum(e, d_loc, num_segments=row_block,
                                indices_are_sorted=True)
        alpha = e / jnp.take(jnp.where(z > 0, z, 1.0), d_in, axis=0)
        if edge_keep is not None:
            alpha = alpha * jax.lax.dynamic_slice(mult, (e0, 0),
                                                  (longest, k))
        return jax.ops.segment_sum(alpha[:, :, None] * g, d_loc,
                                   num_segments=row_block,
                                   indices_are_sorted=True)

    out = jax.lax.map(block, jnp.arange(blocks))      # [B, row_block, K, F]
    return out.reshape(rows_padded, k, f)[:n]


@functools.partial(jax.jit, static_argnames=("row_block", "rate"))
def logits(weights, x, src, dst_local, edge_start, row_block: int = ROW_BLOCK,
           input_keep=None, edge_keep=None, rate: float = 0.0):
    """Logits [N, classes].  Evaluation mode unless keep masks are given:
    ``input_keep[l]`` [N, d_l] and ``edge_keep[l]`` [K_l, E] bool per
    layer, applied at rate ``rate``."""
    t = x.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        for i, (wl, wr, a) in enumerate(weights):
            if input_keep is not None:
                t = jnp.where(input_keep[i], t / (1.0 - rate), 0.0)
            k, f = a.shape
            out = attend((t @ wl).reshape(-1, k, f),
                         (t @ wr).reshape(-1, k, f), a, src, dst_local,
                         edge_start, row_block,
                         None if edge_keep is None else edge_keep[i], rate)
            t = out.reshape(-1, k * f)
            if i != len(weights) - 1:
                t = jnp.where(t > 0, t, jnp.expm1(jnp.minimum(t, 0.0)))  # ELU
    return t


def loss(weights, x, src, dst_local, edge_start, label_ids, mask,
         row_block: int = ROW_BLOCK, input_keep=None, edge_keep=None,
         rate: float = 0.0):
    """Sum of cross-entropy over train rows (softmax_kernel.cu:19-33 gives
    softmax - onehot, masked, unnormalised: the gradient of this sum)."""
    z = logits(weights, x, src, dst_local, edge_start, row_block=row_block,
               input_keep=input_keep, edge_keep=edge_keep, rate=rate)
    logp = jax.nn.log_softmax(z, axis=-1)
    ce = -jnp.take_along_axis(logp, label_ids[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(mask == MASK_TRAIN, ce, 0.0))


def loss_and_grads(params: dict, dataset, layers, row_block: int = ROW_BLOCK,
                   edge_keep=None, input_keep=None, rate: float = 0.0):
    """(loss, {name: gradient}) of the reference on ``dataset`` at the
    trainer's ``params``.  Dropout off unless the program's own keep masks
    are handed in: ``edge_keep`` a list, per layer, of [K, E] bool (the
    coefficients kept, per head and in-edge in CSR order) and
    ``input_keep`` of [N, d] bool (the layer inputs kept), both applied at
    ``rate``; either may be None."""
    names = layer_names(params)
    src, dst_local, e0 = edge_blocks(dataset.graph, row_block)
    val, grads = jax.value_and_grad(loss)(
        ordered_weights(params), jnp.asarray(dataset.features), src,
        dst_local, e0, jnp.asarray(dataset.label_ids, jnp.int32),
        jnp.asarray(dataset.mask, jnp.int32), row_block,
        input_keep, edge_keep, rate)
    out = {}
    for name, g in zip(names, grads):
        out.update({f"{name}_{p}": gp for p, gp in zip(PARTS, g)})
    return val, out


def reference_logits(params: dict, dataset, layers, device=None,
                     row_block: int = ROW_BLOCK) -> np.ndarray:
    """Host copy of the reference's evaluation-mode logits for the
    trainer's ``params``."""
    src, dst_local, e0 = edge_blocks(dataset.graph, row_block)
    put = functools.partial(jax.device_put, device=device)
    out = logits([tuple(put(a) for a in layer)
                  for layer in ordered_weights(params)],
                 put(dataset.features), put(src), put(dst_local), put(e0),
                 row_block=row_block)
    return np.asarray(out)
