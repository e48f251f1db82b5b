"""GCNII (Chen, Wei, Huang, Ding, Li, "Simple and Deep Graph Convolutional
Networks", ICML 2020, arXiv:2007.02133, equation 5) on the op IR: the deep
full-batch GCN, PyTorch Geometric's ``GCN2Conv(channels, alpha, theta,
layer, shared_weights=True)``, the authors' ``GraphConvolution`` with
``variant=False``.

With P = D^-1/2 A D^-1/2 over in-edges (the self-edge is the input
contract's), L layers of one width D, ``beta_l = log(lamda / l + 1)``:

    H0     = ReLU(dropout(X, p) W_in + b_in)
    for l = 1..L:
        x    = dropout(H(l-1), p)
        s    = (1 - alpha) P x + alpha H0        # initial residual
        H(l) = ReLU((1 - beta_l) s + beta_l (s W_l))   # identity mapping
    logits = dropout(H(L), p) W_out + b_out

``H0`` enters EVERY layer: a tensor of layer 0 read by the ops of layers
1..L (``Model.far_outputs``).  ``W_l`` is square and has no bias; the two
dense layers have one.  Every op is the IR's own: the two sums are ``add``
with scalar weights.
"""

from __future__ import annotations

import math
from typing import Sequence

from roc_tpu.models.model import Model

ALPHA = 0.1     # the paper's semi-supervised table, Pubmed row
LAMDA = 0.4     # the authors' spelling (train.py --lamda)


def gcnii_beta(lamda: float, layer: int) -> float:
    """``beta_l`` of layer ``l`` = 1..L."""
    return math.log(lamda / layer + 1.0)


def build_gcnii(layers: Sequence[int], dropout_rate: float = 0.5,
                alpha: float = ALPHA, lamda: float = LAMDA) -> Model:
    """layers = [in_dim, D, ..., D, num_classes]: every hidden entry is one
    GCNII layer of width D (all equal: W_l is square), between the in_dim
    -> D and D -> num_classes dense layers."""
    if len(layers) < 3:
        raise ValueError("gcnii needs -layers in-D-...-D-classes: at least "
                         "one hidden entry (one GCNII layer)")
    width = layers[1]
    if any(d != width for d in layers[1:-1]):
        raise ValueError(
            f"gcnii hidden widths {list(layers[1:-1])} differ: every hidden "
            f"entry of -layers is one GCNII layer and W_l is square, so all "
            f"must equal the first ({width})")
    model = Model(in_dim=layers[0])
    t = model.dropout(model.input, dropout_rate)
    h0 = t = model.relu(model.linear(t, width, bias=True))
    model.end_layer()
    for layer in range(1, len(layers) - 1):
        beta = gcnii_beta(lamda, layer)
        t = model.dropout(t, dropout_rate)
        t = model.indegree_norm(t)
        t = model.scatter_gather(t, "sum")
        t = model.indegree_norm(t)
        s = model.add(t, h0, 1.0 - alpha, alpha)
        t = model.relu(model.add(s, model.linear(s, width),
                                 1.0 - beta, beta))
        model.end_layer()
    t = model.dropout(t, dropout_rate)
    t = model.linear(t, layers[-1], bias=True)
    model.end_layer()
    model.softmax_cross_entropy(t)
    return model
