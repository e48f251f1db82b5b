"""Loud canaries for load-bearing workarounds (VERDICT round-1 weak #7):
each of these encodes an assumption about jax internals or shard_map vma
semantics that a jax upgrade could silently break.  If one of these fails,
find the matching workaround and revisit it — do not just delete the test.
"""

import jax
import numpy as np
import pytest

from roc_tpu.graph import datasets
from roc_tpu.models import build_gcn
from roc_tpu.train.config import Config


def test_platform_pinning_contract():
    """jax.config.update('jax_platforms', ...) must remain readable back —
    _pin_cpu_platform relies on config-level pinning beating env vars."""
    assert jax.config.jax_platforms == "cpu"  # set by conftest


@pytest.mark.parametrize("backend", ["xla", "matmul"])
def test_vma_checking_stays_on_for_xla_and_matmul(backend, monkeypatch):
    """spmd.py disables shard_map's check_vma ONLY for the pallas backend
    (pallas_call can't annotate vma yet); the xla and matmul backends must
    keep compiling WITH vma checking — including the `+ 0 * x[:1, :1]`
    device-varying-carry hack in ops/aggregate.py:_matmul_run, which this
    exercises end-to-end.  If this fails after a jax upgrade, the vma
    annotation rules changed."""
    from jax import shard_map as real_shard_map
    from roc_tpu.parallel import spmd

    seen = []

    def spy_shard_map(*a, **kw):
        seen.append(kw.get("check_vma"))
        return real_shard_map(*a, **kw)

    monkeypatch.setattr(spmd.jax, "shard_map", spy_shard_map)
    ds = datasets.synthetic("vma", 256, 4.0, 8, 4, n_train=64, n_val=64,
                            n_test=64, seed=0)
    cfg = Config(layers=[8, 8, 4], num_epochs=1, dropout_rate=0.0,
                 num_parts=4, halo=True, aggregate_backend=backend,
                 eval_every=10**9)
    tr = spmd.SpmdTrainer(cfg, ds, build_gcn(cfg.layers, 0.0))
    assert seen and all(v is True for v in seen), (
        f"check_vma must stay True for backend={backend}, saw {seen}")
    loss = tr.run_epoch()            # compiles + runs under vma checking
    assert np.isfinite(float(np.asarray(loss)))
