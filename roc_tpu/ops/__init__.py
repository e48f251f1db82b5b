from roc_tpu.ops.aggregate import (
    AggregatePlans, BinnedPlans, build_aggregate_plans, build_binned_plans,
    divide_by_degree, matmul_precision, pad_binned_plans, pad_plans,
    scatter_gather, scatter_gather_binned, scatter_gather_matmul)
from roc_tpu.ops.edge import (GatPlans, build_gat_plans, edge_softmax,
                              gat_attend, gat_attend_plan, gatv2_attend,
                              gatv2_attend_plan, pad_gat_plans,
                              tconv_attend, tconv_attend_plan)
from roc_tpu.ops.norm import indegree_norm, layer_norm
from roc_tpu.ops.linear import linear
from roc_tpu.ops.activation import apply_activation, elu, relu, sigmoid
from roc_tpu.ops.element import add, mul
from roc_tpu.ops.dropout import dropout
from roc_tpu.ops.softmax import (
    PerfMetrics, masked_softmax_cross_entropy, perf_metrics)
from roc_tpu.ops.init import glorot_uniform

__all__ = [
    "scatter_gather", "scatter_gather_matmul",
    "scatter_gather_binned",
    "BinnedPlans", "build_binned_plans",
    "pad_binned_plans", "matmul_precision", "divide_by_degree",
    "edge_softmax", "gat_attend", "gat_attend_plan",
    "tconv_attend", "tconv_attend_plan", "gatv2_attend", "gatv2_attend_plan",
    "GatPlans", "build_gat_plans", "pad_gat_plans",
    "indegree_norm", "layer_norm", "linear", "relu", "sigmoid", "elu",
    "apply_activation", "add",
    "mul", "dropout", "PerfMetrics", "masked_softmax_cross_entropy",
    "perf_metrics", "glorot_uniform",
]
