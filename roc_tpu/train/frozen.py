"""Frozen-parameter loading, shared by eval and serve.

Before this module, checkpoint loading was duplicated per driver mode:
`BaseTrainer.restore` rebuilt params *and* optimizer state through a full
trainer, the `-stream` path grew its own gdata-less restore, and anything
that only wanted a forward pass (eval tooling, now the serving engine)
had to construct a throwaway trainer to get one.  `load_frozen` is the
one entry point: checkpoint + plan cache in, a `FrozenBundle` out —
params restored (weights only, no optimizer arrays), graph data built
through the SAME backend-resolution policy as training
(`driver.effective_backend`), plans pulled from the content-keyed plan
cache (a warm cache means ZERO plan rebuilds — the serve cold-start
contract, pinned in tests/test_serve.py).

Graphs that don't fit in-core keep working: under `config.stream` the
bundle wraps the streaming executor's slot machinery instead of a
resident DenseGraphData, and `predict_logits` sweeps shards through the
frozen padded slots exactly as streamed eval does.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from roc_tpu.graph.datasets import Dataset
from roc_tpu.models.model import Model, refuse_pair_attention
from roc_tpu.train import checkpoint
from roc_tpu.train.config import Config


@dataclasses.dataclass
class FrozenBundle:
    """Everything a forward-only consumer needs, loaded exactly once.

    ``gdata`` is a resident DenseGraphData on the in-core path and None
    under streaming, where ``stream_trainer`` holds the slot machinery
    instead.  ``params`` are device-resident (placed via device_put at
    load) and never updated — the serving engine treats them as frozen
    donated buffers for the lifetime of the process.
    """

    config: Config
    dataset: Dataset
    model: Model
    params: object
    x: Optional[jnp.ndarray]
    gdata: object
    num_nodes: int
    stream_trainer: object = None
    _logits_jit: object = dataclasses.field(default=None, repr=False)

    def predict_logits(self):
        """Full-graph logits [N, C] in global node order — the parity
        oracle served queries are gated against (tests/test_serve.py).
        Jitted with the same program as the trainer's logits_step, so
        eval and serve run byte-identical forwards."""
        if self.stream_trainer is not None:
            tr = self.stream_trainer
            padded = tr.predict_logits()
            import numpy as np
            return jnp.asarray(tr._meta.unpad_nodes(np.asarray(padded)))
        if self._logits_jit is None:
            from roc_tpu.analysis import retrace as _retrace
            from roc_tpu.train.driver import make_gctx
            model, n = self.model, self.num_nodes

            @jax.jit
            def frozen_logits(params, x, gdata):
                _retrace.note_trace("frozen_logits")
                return model.apply(params, x, make_gctx(gdata, n),
                                   train=False)

            self._logits_jit = frozen_logits
        return self._logits_jit(self.params, self.x, self.gdata)


def load_frozen(config: Config, dataset: Dataset, model: Model,
                checkpoint_path: Optional[str] = None) -> FrozenBundle:
    """Load a checkpoint + the plan cache into a forward-only bundle.

    With ``checkpoint_path`` (or ``config.checkpoint_path``) the weights
    are restored via `checkpoint.load_params` — optimizer state is never
    materialized.  Without one, Glorot-init params are returned (tests
    and selftests exercise parity without a training run).  Plan builds
    go through the same content-keyed disk cache as training
    (ops/pallas/binned.py): when the training run already built this
    graph's plans, loading here is a cache read, not a rebuild.
    """
    from roc_tpu import obs

    path = checkpoint_path or config.checkpoint_path
    refuse_pair_attention(model, "the frozen loader behind serve/ and "
                                "fleet/ (train/frozen.py load_frozen)")
    with obs.span("load_frozen", stream=bool(config.stream)):
        if config.stream:
            from roc_tpu.stream.executor import StreamTrainer
            tr = StreamTrainer(config, dataset, model)
            if path:
                tr.params = checkpoint.load_params(path, tr.params)
            return FrozenBundle(
                config=config, dataset=dataset, model=model,
                params=tr.params, x=None, gdata=None,
                num_nodes=dataset.graph.num_nodes, stream_trainer=tr)
        from roc_tpu.train.driver import (dense_graph_data,
                                          effective_backend,
                                          effective_gat_backend)
        backend = effective_backend(config, dataset, model)
        gdata = dense_graph_data(
            dataset.graph, backend, config.aggregate_precision,
            gat_backend=effective_gat_backend(config, dataset, model),
            storage_dtype="bf16" if config.bf16_storage else "fp32")
        dtype = jnp.bfloat16 if config.use_bf16 else jnp.float32
        x = jnp.asarray(dataset.features, dtype)
        params = model.init_params(jax.random.PRNGKey(config.seed))
        if path:
            params = checkpoint.load_params(path, params)
        params = jax.device_put(params)
        return FrozenBundle(
            config=config, dataset=dataset, model=model, params=params,
            x=x, gdata=gdata, num_nodes=dataset.graph.num_nodes)
