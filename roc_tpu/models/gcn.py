"""The reference's built-in GCN program (top_level_task, gnn.cc:75-92).

Per hidden layer i = 1..L-1:
    t = dropout(t, rate)
    input = t
    t = linear(t, layers[i])            # no fused activation in the recipe
    t = indegree_norm(t)
    t = scatter_gather(t)               # sum over in-edges
    t = indegree_norm(t)                # → symmetric D^-1/2 A D^-1/2
    if not last: t = relu(t)
    if len(layers) > 3:                 # residual path for deep GCNs
        input = linear(input, t.dim)    # always projected, gnn.cc:87-88
        t = add(t, input)
final: softmax_cross_entropy(t, label, mask)
"""

from __future__ import annotations

from typing import Sequence

from roc_tpu.models.model import Model


def build_gcn(layers: Sequence[int], dropout_rate: float = 0.5,
              aggr: str = "sum") -> Model:
    """layers = [in_dim, hidden..., num_classes] — the CLI's `-layers` spec."""
    assert len(layers) >= 2
    model = Model(in_dim=layers[0])
    t = model.input
    for i in range(1, len(layers)):
        t = model.dropout(t, dropout_rate)
        residual_in = t
        t = model.linear(t, layers[i])
        t = model.indegree_norm(t)
        t = model.scatter_gather(t, aggr)
        t = model.indegree_norm(t)
        if i != len(layers) - 1:
            t = model.relu(t)
        if len(layers) > 3:
            proj = model.linear(residual_in, t.dim)
            t = model.add(t, proj)
        model.end_layer()
    model.softmax_cross_entropy(t)
    return model
