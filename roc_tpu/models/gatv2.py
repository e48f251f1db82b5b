"""GATv2 (Brody, Alon, Yahav, "How Attentive are Graph Attention Networks?",
ICLR 2022, arXiv:2105.14491) on the op IR: GAT with the "dynamic" score,
PyTorch Geometric's ``GATv2Conv(heads, concat=True, negative_slope=0.2,
dropout=p, share_weights=False, bias=False)``.

Layer l (equation 7; K heads of width F; N(i) the in-neighbours of i, its
self-edge included by the input contract):

    x        = dropout(h, p)
    xl_j     = x_j W_l      xr_i = x_i W_r                  no bias
    s_k,ij   = sum_f a_k,f LeakyReLU(xr_i,k,f + xl_j,k,f; 0.2)
    alpha    = softmax over j in N(i) of s_k,ij             per head
    alpha~   = dropout(alpha, p)                            not renormalised
    h'_i,k   = sum_j alpha~_k,ij xl_j,k                     heads concatenated
    h'       = ELU(h')                                      hidden layers

The output layer has one head as wide as the classes and no ELU.  The
recipe around the operator is ``build_gat``'s (Velickovic et al. section
3.3: one p for both dropouts), so a gat and a gatv2 model of the same
-layers and -heads differ in the score alone.  Where GAT's score splits
into two node scalars a head, this one passes both rows through a LeakyReLU
at every channel before ``a`` (ops/edge.py, ``gatv2_attend_plan``).
"""

from __future__ import annotations

from typing import Sequence

from roc_tpu.models.model import Model


def build_gatv2(layers: Sequence[int], dropout_rate: float = 0.5,
                heads: int = 8, slope: float = 0.2) -> Model:
    """layers = [in_dim, hidden..., num_classes]; hidden widths are per
    head, as ``build_gat`` reads them (602-8-41 at 8 heads: 64
    concatenated, then one head of 41)."""
    assert len(layers) >= 2
    model = Model(in_dim=layers[0])
    t = model.input
    for i in range(1, len(layers)):
        last = i == len(layers) - 1
        t = model.dropout(t, dropout_rate)
        t = model.gatv2(t, layers[i], heads=1 if last else heads,
                        slope=slope, attn_drop=dropout_rate)
        if not last:
            t = model.elu(t)
        model.end_layer()
    model.softmax_cross_entropy(t)
    return model
