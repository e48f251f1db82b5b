"""Golden accuracy curves (docs/GOLDEN.md): fixed-seed end-to-end training
must reproduce the recorded curve within cross-platform float tolerance.
This is the framework's version of the reference's de-facto oracle
(SURVEY §4: correctness regression == accuracy divergence)."""

import jax
import pytest

from roc_tpu.graph import datasets
from roc_tpu.models import build_gcn
from roc_tpu.train.config import Config
from roc_tpu.train.driver import Trainer


def _run(name, layers, wd, epochs, seed=1):
    ds = datasets.get(name, seed=seed)
    cfg = Config(layers=layers, num_epochs=epochs, learning_rate=0.01,
                 weight_decay=wd, dropout_rate=0.5, seed=seed,
                 eval_every=10**9)
    tr = Trainer(cfg, ds, build_gcn(layers, cfg.dropout_rate))
    curve = {}
    for epoch in range(epochs + 1):
        if epoch in (5, 10, 20):
            curve[epoch] = jax.device_get(tr.evaluate())
        if epoch < epochs:
            tr.run_epoch()
    return curve


@pytest.mark.slow
def test_golden_cora_curve():
    curve = _run("cora", [1433, 16, 7], 5e-4, 20)
    # GOLDEN.md: 96.40 / 98.20 / 97.80 @ epochs 5/10/20 (loss 0.67 @ 20)
    assert curve[5].val_correct / curve[5].val_all >= 0.94
    assert curve[20].val_correct / curve[20].val_all >= 0.965
    assert float(curve[20].train_loss) <= 1.5


@pytest.mark.slow
def test_golden_reddit_small_curve():
    curve = _run("reddit-small", [602, 128, 41], 1e-4, 10)
    # GOLDEN.md: saturates by epoch 5; epoch-10 pin with headroom
    assert curve[10].val_correct / curve[10].val_all >= 0.995
    assert float(curve[10].train_loss) <= 1.0


@pytest.mark.slow
def test_golden_cora_curve_binned_backend():
    """The binned backend's designed bf16 rounding must not move the golden
    curve (docs/GOLDEN.md records the full metric lines: accuracy counts
    agree with fp32 to within +-1 sample at every checkpoint)."""
    ds = datasets.get("cora", seed=1)
    cfg = Config(layers=[1433, 16, 7], num_epochs=20, learning_rate=0.01,
                 weight_decay=5e-4, dropout_rate=0.5, seed=1,
                 eval_every=10**9, aggregate_backend="binned")
    tr = Trainer(cfg, ds, build_gcn(cfg.layers, cfg.dropout_rate))
    for _ in range(20):
        tr.run_epoch()
    m = jax.device_get(tr.evaluate())
    assert m.val_correct / m.val_all >= 0.965
    assert float(m.train_loss) <= 1.5


@pytest.mark.slow
@pytest.mark.parametrize("name,pins", [
    # (epoch, min val accuracy); final (epoch, max loss) — docs/GOLDEN.md
    ("sage", {5: 0.96, 20: 0.975, "loss20": 0.1}),
    ("gin", {20: 0.78, "loss20": 33.0}),
    # PR 25: the GAT recipe drops the attention coefficients too (paper
    # section 3.3), so 20 epochs leave a higher and noisier train loss
    # (1.6 to 1.8 measured, was 0.0000); the accuracy pin is unchanged
    ("gat", {20: 0.955, "loss20": 4.0}),
])
def test_golden_zoo_curves(name, pins):
    """Fixed-seed accuracy pins for the model zoo (docs/GOLDEN.md) — the
    zoo's version of the reference's accuracy oracle.  Conservative
    thresholds leave cross-platform float headroom."""
    from roc_tpu.models import build_model

    ds = datasets.get("cora", seed=1)
    cfg = Config(layers=[1433, 16, 7], num_epochs=20, learning_rate=0.01,
                 weight_decay=5e-4, dropout_rate=0.5, seed=1,
                 eval_every=10**9)
    tr = Trainer(cfg, ds, build_model(name, cfg.layers, cfg.dropout_rate))
    for epoch in range(20):
        if epoch in pins:
            m = jax.device_get(tr.evaluate())
            assert m.val_correct / m.val_all >= pins[epoch], (name, epoch)
        tr.run_epoch()
    m = jax.device_get(tr.evaluate())
    if 20 in pins:
        assert m.val_correct / m.val_all >= pins[20], name
    assert float(m.train_loss) <= pins["loss20"], name
