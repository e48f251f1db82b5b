"""Graph transformer (Shi et al., "Masked Label Prediction: Unified Message
Passing Model for Semi-Supervised Classification", UniMP, IJCAI 2021,
arXiv:2009.03509) on the op IR: the attention GNN of the OGB leaderboards,
PyTorch Geometric's ``TransformerConv(heads, concat, beta=True, dropout)``.

Hidden layer l (equations 3-5; C heads of width d, D = C d; N(i) the
in-neighbours of i, its self-edge included by the input contract):

    x      = dropout(h, p)
    q_c,i  = x_i Wq_c + bq_c    k_c,j = x_j Wk_c + bk_c    v_c,j = x_j Wv_c + bv_c
    a_c,ij = softmax over j in N(i) of  q_c,i . k_c,j / sqrt(d)
    a~     = dropout(a, p)              # not renormalised; training only
    m_i    = concat_c sum_j a~_c,ij v_c,j
    r_i    = x_i Wr + br
    b_i    = sigmoid(wg . [m_i ; r_i ; m_i - r_i])         # wg in R^{3D}
    h'_i   = ReLU(LayerNorm((1 - b_i) m_i + b_i r_i))

Output layer: the C heads, each as wide as the classes, are AVERAGED, r_i
and wg are at that width, and the logits are (1 - b) m + b r: no LayerNorm,
no ReLU.  Where GAT's score is additive and rank one, this one is a dot
product of two projected rows at every edge (ops/edge.py,
``tconv_attend_plan``).  Left out: the paper's masked-label input (a label
embedding added to the features), which is a product in front of the model
and no mechanism of the layer.
"""

from __future__ import annotations

from typing import Sequence

from roc_tpu.models.model import Model


def build_tconv(layers: Sequence[int], dropout_rate: float = 0.3,
                heads: int = 4) -> Model:
    """layers = [in_dim, hidden..., num_classes]; a hidden entry is the
    CONCATENATED width (heads x head width, the transformer convention:
    128 = 4 x 32), the last entry the width of each averaged head."""
    assert len(layers) >= 2
    model = Model(in_dim=layers[0])
    t = model.input
    for i in range(1, len(layers)):
        last = i == len(layers) - 1
        if not last and layers[i] % heads:
            raise ValueError(
                f"tconv hidden width {layers[i]} is not a multiple of "
                f"heads={heads} (a hidden entry of -layers is the "
                f"concatenated width)")
        t = model.dropout(t, dropout_rate)
        # hidden: `heads` heads side by side; output: their mean
        t = model.tconv(t, layers[i] if last else layers[i] // heads,
                        heads=1 if last else heads,
                        mean_heads=heads if last else 1,
                        attn_drop=dropout_rate)
        if not last:
            t = model.relu(model.layer_norm(t))
        model.end_layer()
    model.softmax_cross_entropy(t)
    return model
