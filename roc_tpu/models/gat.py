"""Graph Attention Network (Velickovic et al., ICLR'18) on the op IR.

The reference has no attention model, but it reserves the machinery one
needs: edge tensors partitioned by the edge coloring (create_edge_tensor,
gnn.cc:534-589) with EDGE_TENSOR input paths through linear / activation /
dropout (linear.cc:73-77, activation.cc:48-52, dropout.cc:42-46).  This
model exercises the TPU realization of that latent capability
(roc_tpu/ops/edge.py): per-edge attention scores, per-destination edge
softmax, attention-weighted aggregation — all sharded over the same vertex
partition, with the halo/all_gather exchange reused for the source table.

Recipe per hidden layer (paper sections 2.2 and 3.3):
    t = dropout(t, p)
    t = gat(t, head_dim, heads, attn_drop=p)   # multi-head, concatenated;
                                  # the normalised attention coefficients
                                  # are dropped per edge and head at the
                                  # same rate p, not renormalised
    t = elu(t)                    # not on the output layer
Output layer: single head sized to num_classes, then softmax CE.  The paper
has one p (0.6) for both dropouts, and so has ``build_gat``: its one
``dropout_rate`` is the rate of the inputs and of the coefficients.
Evaluation drops nothing.  No bias, as in the paper's equations.
"""

from __future__ import annotations

from typing import Sequence

from roc_tpu.models.model import Model


def build_gat(layers: Sequence[int], dropout_rate: float = 0.5,
              heads: int = 8, slope: float = 0.2) -> Model:
    """layers = [in_dim, hidden..., num_classes]; hidden widths are per-head
    (layer output is heads*width, matching the paper's K=8, F'=8 -> 64)."""
    assert len(layers) >= 2
    model = Model(in_dim=layers[0])
    t = model.input
    for i in range(1, len(layers)):
        last = i == len(layers) - 1
        t = model.dropout(t, dropout_rate)
        t = model.gat(t, layers[i], heads=1 if last else heads, slope=slope,
                      attn_drop=dropout_rate)
        if not last:
            t = model.elu(t)
        model.end_layer()
    model.softmax_cross_entropy(t)
    return model
