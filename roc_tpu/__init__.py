"""roc_tpu — a TPU-native framework for distributed full-graph GNN training.

A ground-up JAX/XLA/Pallas re-design of the capabilities of the ROC system
(MLSys'20, reference: /root/reference — C++/CUDA on the Legion runtime):
edge-balanced graph partitioning, CSR scatter-gather aggregation, GCN-family
models, masked softmax cross-entropy with train/val/test metrics, Adam with
ROC's exact weight-decay formulation, and multi-chip SPMD execution over a
`jax.sharding.Mesh` (ICI collectives instead of Legion's implicit zero-copy
region coherence).

Layer map (the TPU-native analog of SURVEY.md §1):

  L0  XLA / TPU runtime            (external)
  L1  parallel/   mesh + shardings + halo exchange  (replaces GnnMapper,
                  ResourceManager, zero-copy staging — none of which exist
                  on TPU: HBM residency + sharding specs do their jobs)
  L2  graph/      CSR core, .lux IO, edge-balanced partitioner, datasets
  L3  ops/        pure-function ops with custom VJPs where sparsity needs it
  L4  models/     op-graph builder + model zoo (GCN, SAGE, GIN, GAT,
                  residual deep GCN)
  L5  train/      config, driver epoch loop, metrics, checkpointing, CLI
"""

__version__ = "0.2.0"

from roc_tpu.graph.csr import Csr  # noqa: F401
