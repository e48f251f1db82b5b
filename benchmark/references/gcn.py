"""Plain reference for the GCN family: float32 `jax.numpy`, `segment_sum`,
matmuls at `highest` precision, no kernels, no plans, no padding.

Written from the reference program's recipe (gnn.cc:75-92), independent of
`roc_tpu/ops` and `roc_tpu/models`.  For layers = [d0, d1, ..., dL], with A
the in-edge adjacency (self-edges included) and D its in-degree, layer i is

    t   = dropout(t)                      (identity here: evaluation mode)
    u   = t @ W_i                         (no bias anywhere)
    u   = D^-1/2 . A . D^-1/2 . u         (norm, sum over in-edges, norm)
    u   = relu(u)           unless i == L
    t   = u + t_in @ P_i    only when the spec has more than three entries
                            (gnn.cc:87-88: the residual is always projected)

and the loss is the unreduced sum of softmax cross-entropy over the train
rows.  Parameters arrive as the trainer's dict; the recipe's linear ops in
order are linear_0, linear_1, ... (W_1, P_1, W_2, P_2, ... with the
residual, W_1, W_2, ... without).

The aggregation walks the edge list in fixed blocks, so the gathered
[block, width] rows are the largest temporary: the products shape
(1.25e8 edges at width 256) would otherwise need 128 GB for one gather.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

MASK_TRAIN = 0          # gnn.h:98-103
EDGE_BLOCK = 1 << 21    # edges gathered at a time (2 GiB at width 256)


def edge_arrays(graph, edge_block: int = EDGE_BLOCK):
    """(src, dst, in_degree) of an in-edge CSR, the edge list padded to a
    whole number of blocks with edges that point outside the graph (dropped
    by segment_sum)."""
    n, e = graph.num_nodes, graph.num_edges
    deg = np.diff(graph.row_ptr)
    pad = (-e) % edge_block
    src = np.concatenate([graph.col_idx.astype(np.int32),
                          np.zeros(pad, np.int32)])
    dst = np.concatenate([np.repeat(np.arange(n, dtype=np.int32), deg),
                          np.full(pad, n, np.int32)])
    return src, dst, deg.astype(np.float32)


def ordered_names(params: dict) -> list:
    """The trainer's linear parameter names in recipe order."""
    def index(name):
        m = re.fullmatch(r"linear_(\d+)", name)
        if m is None:
            raise ValueError(f"the GCN reference knows no parameter {name!r}")
        return int(m.group(1))
    return sorted(params, key=index)


def ordered_weights(params: dict) -> list:
    return [jnp.asarray(params[k], jnp.float32)
            for k in ordered_names(params)]


def aggregate(x, src, dst, edge_block: int):
    """out[v] = sum of x[u] over in-edges (u, v), in blocks of edges."""
    n = x.shape[0]
    blocks = src.shape[0] // edge_block

    def body(out, sd):
        s, d = sd
        return out + jax.ops.segment_sum(x[s], d, num_segments=n,
                                         indices_are_sorted=True), None

    out, _ = jax.lax.scan(body, jnp.zeros_like(x),
                          (src.reshape(blocks, edge_block),
                           dst.reshape(blocks, edge_block)))
    return out


@functools.partial(jax.jit, static_argnames=("residual", "edge_block"))
def logits(weights, x, src, dst, in_degree, residual: bool,
           edge_block: int = EDGE_BLOCK):
    """Evaluation-mode logits [N, classes].  ``residual``: the layer spec
    has more than three entries (`has_residual`)."""
    mains = weights[0::2] if residual else weights
    projs = weights[1::2] if residual else [None] * len(weights)
    norm = jax.lax.rsqrt(in_degree)[:, None]
    t = x.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        for i, (w, p) in enumerate(zip(mains, projs)):
            u = (t @ w) * norm
            u = aggregate(u, src, dst, edge_block) * norm
            if i != len(mains) - 1:
                u = jnp.maximum(u, 0.0)
            t = u + t @ p if p is not None else u
    return t


def has_residual(layers) -> bool:
    """gnn.cc:86: `if (layers.size() > 3)` builds the projected residual."""
    return len(layers) > 3


def loss(weights, x, src, dst, in_degree, label_ids, mask, residual: bool,
         edge_block: int = EDGE_BLOCK):
    """Sum of cross-entropy over train rows (softmax_kernel.cu:19-33 gives
    softmax - onehot, masked, unnormalised: the gradient of this sum)."""
    z = logits(weights, x, src, dst, in_degree, residual=residual,
               edge_block=edge_block)
    logp = jax.nn.log_softmax(z, axis=-1)
    ce = -jnp.take_along_axis(logp, label_ids[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(mask == MASK_TRAIN, ce, 0.0))


def loss_and_grads(params: dict, dataset, layers,
                   edge_block: int = EDGE_BLOCK):
    """(loss, {name: gradient}) of the reference on ``dataset`` at the
    trainer's ``params`` (dropout off)."""
    names = ordered_names(params)
    src, dst, deg = edge_arrays(dataset.graph, edge_block)
    val, grads = jax.value_and_grad(loss)(
        ordered_weights(params), jnp.asarray(dataset.features), src, dst,
        deg, jnp.asarray(dataset.label_ids, jnp.int32),
        jnp.asarray(dataset.mask, jnp.int32), has_residual(layers),
        edge_block)
    return val, dict(zip(names, grads))


def reference_logits(params: dict, dataset, layers, device=None,
                     edge_block: int = EDGE_BLOCK) -> np.ndarray:
    """Host copy of the reference's logits for the trainer's ``params``."""
    src, dst, deg = edge_arrays(dataset.graph, edge_block)
    put = functools.partial(jax.device_put, device=device)
    out = logits([put(w) for w in ordered_weights(params)],
                 put(dataset.features), put(src), put(dst), put(deg),
                 residual=has_residual(layers), edge_block=edge_block)
    return np.asarray(out)
