"""Plain reference for GCNII: float32 `jax.numpy`, `segment_sum`, matmuls at
`highest` precision, no kernels, no plans, no padding.

Written from the paper (Chen, Wei, Huang, Ding, Li, "Simple and Deep Graph
Convolutional Networks", ICML 2020, arXiv:2007.02133, equation 5; the
authors' `GraphConvolution` with `variant=False`, PyTorch Geometric's
`GCN2Conv(shared_weights=True)`), independent of `roc_tpu/ops` and
`roc_tpu/models`.  For layers = [d_in, D, ..., D, classes] with L hidden
entries, A the in-edge adjacency (self-edges included), D its in-degree and
P = D^-1/2 A D^-1/2:

    H0      = relu(dropout(X) W_in + b_in)
    for l = 1..L:    beta_l = log(LAMDA / l + 1)
        s   = (1 - ALPHA) P dropout(H(l-1)) + ALPHA H0
        H(l)= relu((1 - beta_l) s + beta_l (s W_l))
    logits  = dropout(H(L)) W_out + b_out

(dropout is the identity in evaluation mode), and the loss is the
unreduced sum of softmax cross-entropy over the train rows.  ALPHA and LAMDA
are the semi-supervised table's Pubmed row (16 layers, hidden 256): the
configuration states them under `assumed`, and the program's builder has
them as its defaults because the harness hands `build_model` neither.

Parameters arrive as the trainer's dict: `linear_0` / `linear_0_bias` (W_in,
b_in), `linear_1` .. `linear_L` (W_l, no bias), `linear_<L+1>` and its
`_bias` (W_out, b_out).

The aggregation walks the edge list in fixed blocks, so the gathered
[block, D] rows are the largest temporary (2 GiB at D = 256) beside the
[N, D] tables.
"""

from __future__ import annotations

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

ALPHA = 0.1             # initial-residual weight
LAMDA = 0.4             # identity-mapping decay (the authors' spelling)
MASK_TRAIN = 0          # gnn.h:98-103
EDGE_BLOCK = 1 << 21    # edges gathered at a time


def beta(layer: int) -> float:
    """``beta_l`` of GCNII layer ``l`` = 1..L."""
    return math.log(LAMDA / layer + 1.0)


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
    """The trainer's parameter names in the order `ordered_weights` lists
    them: W_in, b_in, W_1 .. W_L, W_out, b_out."""
    def index(name):
        m = re.fullmatch(r"linear_(\d+)(_bias)?", name)
        if m is None:
            raise ValueError(
                f"the GCNII reference knows no parameter {name!r}")
        return int(m.group(1)), m.group(2) is not None
    names = sorted(params, key=index)
    last = index(names[-1])[0]
    want = ["linear_0", "linear_0_bias",
            *(f"linear_{i}" for i in range(1, last + 1)),
            f"linear_{last}_bias"]
    if names != want:
        raise ValueError(f"GCNII's parameters are {want}, not {names}")
    return names


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


@functools.partial(jax.jit, static_argnames=("edge_block", "rate"))
def logits(weights, x, src, dst, in_degree, edge_block: int = EDGE_BLOCK,
           keep=None, rate: float = 0.0):
    """Logits [N, classes].  Evaluation mode unless ``keep`` is given: the
    L + 2 keep masks of the dropouts in order (on X, on H(0) .. H(L-1), on
    H(L)), applied at ``rate``."""
    w_in, b_in, *hidden, w_out, b_out = weights

    def drop(t, i):
        return t if keep is None else \
            jnp.where(keep[i], t / (1.0 - rate), 0.0)

    norm = jax.lax.rsqrt(in_degree)[:, None]

    # checkpointed a layer (as gat.py and tconv.py checkpoint a row block):
    # the same arithmetic, and `loss_and_grads` then holds one [N, D] table
    # a layer where plain autodiff holds several and sixteen layers of the
    # Reddit shape do not fit a 16 GB chip (17.4 GB; chip, PR 37)
    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def gcnii_layer(h, h0, w, layer):
        px = aggregate(drop(h, layer) * norm, src, dst, edge_block) * norm
        s = (1.0 - ALPHA) * px + ALPHA * h0
        b = beta(layer)
        return jnp.maximum((1.0 - b) * s + b * (s @ w), 0.0)

    with jax.default_matmul_precision("highest"):
        h = h0 = jnp.maximum(
            drop(x.astype(jnp.float32), 0) @ w_in + b_in, 0.0)
        for layer, w in enumerate(hidden, start=1):
            h = gcnii_layer(h, h0, w, layer)
        return drop(h, len(hidden) + 1) @ w_out + b_out


def loss(weights, x, src, dst, in_degree, label_ids, mask,
         edge_block: int = EDGE_BLOCK, keep=None, rate: float = 0.0):
    """Sum of cross-entropy over train rows (softmax_kernel.cu:19-33 gives
    softmax - onehot, masked, unnormalised: the gradient of this sum)."""
    z = logits(weights, x, src, dst, in_degree, edge_block=edge_block,
               keep=keep, rate=rate)
    logp = jax.nn.log_softmax(z, axis=-1)
    ce = -jnp.take_along_axis(logp, label_ids[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(mask == MASK_TRAIN, ce, 0.0))


def loss_and_grads(params: dict, dataset, layers,
                   edge_block: int = EDGE_BLOCK, keep=None,
                   rate: float = 0.0):
    """(loss, {name: gradient}) of the reference on ``dataset`` at the
    trainer's ``params``.  Dropout off unless the program's own keep masks
    are handed in (``keep``, as `logits` takes them, at ``rate``)."""
    names = ordered_names(params)
    src, dst, deg = edge_arrays(dataset.graph, edge_block)
    val, grads = jax.value_and_grad(loss)(
        ordered_weights(params), jnp.asarray(dataset.features), src, dst,
        deg, jnp.asarray(dataset.label_ids, jnp.int32),
        jnp.asarray(dataset.mask, jnp.int32), edge_block, keep, rate)
    return val, dict(zip(names, grads))


def reference_logits(params: dict, dataset, layers, device=None,
                     edge_block: int = EDGE_BLOCK) -> np.ndarray:
    """Host copy of the reference's evaluation-mode logits for the
    trainer's ``params``."""
    src, dst, deg = edge_arrays(dataset.graph, edge_block)
    put = functools.partial(jax.device_put, device=device)
    out = logits([put(w) for w in ordered_weights(params)],
                 put(dataset.features), put(src), put(dst), put(deg),
                 edge_block=edge_block)
    return np.asarray(out)
