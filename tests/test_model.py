"""Model builder + single-device end-to-end training tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.graph import datasets
from roc_tpu.models import build_gcn
from roc_tpu.train.config import Config, parse_args
from roc_tpu.train.driver import Trainer, dense_graph_data, make_gctx


def small_ds(seed=21, n=300, in_dim=16, classes=4):
    return datasets.synthetic("t", n, 3.0, in_dim, classes, n_train=60,
                              n_val=60, n_test=60, seed=seed)


def test_gcn_op_graph_structure():
    m = build_gcn([16, 8, 4], 0.5)
    kinds = [op.kind for op in m.ops]
    # two layers of: dropout linear norm aggregate norm (+relu on first)
    assert kinds == ["dropout", "linear", "norm", "aggregate", "norm",
                     "activation",
                     "dropout", "linear", "norm", "aggregate", "norm"]
    assert m.num_linear == 2
    assert m.logits is not None and m.logits.dim == 4


@pytest.mark.parametrize("name,adds,linears,weights", [
    # >3 entries in -layers adds a projected residual per layer
    # (gnn.cc:86-90): the reference's ADD, no weights, no bias
    ("gcn", 3, 6, None),
    # two weighted sums a GCNII layer (initial residual, identity mapping)
    ("gcnii", 4, 4, ("wa", "wb")),
])
def test_deep_models_add_structure(name, adds, linears, weights):
    from roc_tpu.models import build_model
    m = build_model(name, [16, 8, 8, 4], 0.5)
    found = [op for op in m.ops if op.kind == "add"]
    assert len(found) == adds
    assert m.num_linear == linears
    stamps = {"layer", "ckpt", "ckpt_save", "ckpt_boundary"}
    for op in found:
        own = {k: v for k, v in op.attrs.items() if k not in stamps}
        assert tuple(own) == (weights or ())
        assert not weights or own["wa"] + own["wb"] == pytest.approx(1.0)
    biased = [op for op in m.ops if op.kind == "linear"
              and op.attrs.get("bias")]
    assert len(biased) == (2 if name == "gcnii" else 0)


def test_add_without_weights_lowers_as_before():
    """`ops.add(a, b)` and a `Model.add` without weights are the
    reference's ADD: one `add` and no multiply in the jaxpr; a weight
    brings its multiply."""
    import jax
    from roc_tpu import ops
    x = jnp.ones((4, 3))
    plain = jax.make_jaxpr(lambda a, b: ops.add(a, b))(x, x)
    assert [e.primitive.name for e in plain.eqns] == ["add"]
    mixed = jax.make_jaxpr(lambda a, b: ops.add(a, b, 0.9, 0.1))(x, x)
    assert [e.primitive.name for e in mixed.eqns] == ["mul", "mul", "add"]
    np.testing.assert_allclose(ops.add(x, 2 * x, 0.9, 0.1), 1.1 * x,
                               rtol=1e-6)


def test_gcn_apply_shapes_and_pad_zero_preservation():
    ds = small_ds()
    model = build_gcn([ds.in_dim, 8, ds.num_classes], 0.0)
    params = model.init_params(jax.random.PRNGKey(0))
    gdata = dense_graph_data(ds.graph)
    gctx = make_gctx(gdata, ds.graph.num_nodes)
    logits = model.apply(params, jnp.asarray(ds.features), gctx, train=False)
    assert logits.shape == (ds.graph.num_nodes, ds.num_classes)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_training_learns_on_synthetic_graph():
    # The reference's de-facto oracle: accuracy on a known workload
    # (SURVEY.md §4).  SBM graph + informative features → a 2-layer GCN
    # must beat chance by a wide margin within 100 epochs.
    ds = small_ds()
    cfg = Config(layers=[ds.in_dim, 16, ds.num_classes], num_epochs=100,
                 learning_rate=0.01, weight_decay=5e-4, dropout_rate=0.2,
                 eval_every=1000)
    model = build_gcn(cfg.layers, cfg.dropout_rate)
    tr = Trainer(cfg, ds, model)
    m0 = jax.device_get(tr.evaluate())
    for _ in range(cfg.num_epochs):
        tr.run_epoch()
    m1 = jax.device_get(tr.evaluate())
    acc0 = m0.val_correct / max(m0.val_all, 1)
    acc1 = m1.val_correct / max(m1.val_all, 1)
    assert acc1 > max(2.0 / ds.num_classes, acc0), (acc0, acc1)
    assert acc1 > 0.55
    assert m1.train_loss < m0.train_loss


def test_lr_decay_applied_like_reference():
    ds = small_ds(n=50)
    cfg = Config(layers=[ds.in_dim, 4, ds.num_classes], num_epochs=1,
                 decay_steps=2, decay_rate=0.5)
    tr = Trainer(cfg, ds, build_gcn(cfg.layers, 0.0))
    lrs = []
    for _ in range(5):
        tr.run_epoch()
        lrs.append(tr.optimizer.alpha)
    # decay at epochs 2 and 4 (not epoch 0) — gnn.cc:100-101
    np.testing.assert_allclose(lrs, [0.01, 0.01, 0.005, 0.005, 0.0025])


def test_parse_args_reference_flags():
    cfg = parse_args(["-file", "dataset/reddit-dgl", "-e", "3000",
                      "-lr", "0.01", "-decay", "0.0001", "-dropout", "0.5",
                      "-layers", "602-256-41", "-decay-rate", "0.97"])
    assert cfg.filename == "dataset/reddit-dgl"
    assert cfg.num_epochs == 3000
    assert cfg.layers == [602, 256, 41]
    assert cfg.weight_decay == 0.0001
    assert cfg.decay_rate == 0.97
    assert cfg.dropout_rate == 0.5
    # defaults mirror gnn.cc:31-40
    d = parse_args([])
    assert (d.num_epochs, d.learning_rate, d.weight_decay, d.dropout_rate,
            d.decay_rate, d.decay_steps, d.seed) == (1, 0.01, 0.05, 0.5, 1.0,
                                                     100, 1)


@pytest.mark.parametrize("backend", [
    "xla", "matmul",
    # binned x bf16 compiles the full kernel pair (13 s on the 1-core
    # box); exactness of the bf16 degenerate case is pinned fast by
    # test_binned_exact_degrades_to_fast_for_bf16_input
    pytest.param("binned", marks=pytest.mark.slow),
])
def test_bf16_training_all_backends(backend):
    """-bf16 (activation bf16, fp32 accumulation) must train on every
    aggregation backend and reach sane accuracy."""
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gcn
    from roc_tpu.train.config import Config
    from roc_tpu.train.driver import Trainer

    ds = datasets.synthetic("bf16", 500, 5.0, 16, 4, n_train=120,
                            n_val=120, n_test=120, seed=2)
    layers = [16, 16, 4]
    cfg = Config(layers=layers, num_epochs=40, learning_rate=0.01,
                 weight_decay=5e-4, dropout_rate=0.1, eval_every=10**9,
                 aggregate_backend=backend, use_bf16=True, seed=3)
    tr = Trainer(cfg, ds, build_gcn(layers, cfg.dropout_rate))
    assert tr.x.dtype == jnp.bfloat16
    for _ in range(cfg.num_epochs):
        loss = tr.run_epoch()
    assert np.isfinite(float(loss))
    m = jax.device_get(tr.evaluate())
    assert m.val_correct / m.val_all > 0.6, backend


def test_bf16_sharded_smoke():
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gcn
    from roc_tpu.parallel.spmd import SpmdTrainer
    from roc_tpu.train.config import Config

    ds = datasets.synthetic("bf16s", 260, 4.0, 8, 4, n_train=50, n_val=50,
                            n_test=50, seed=4)
    layers = [8, 8, 4]
    cfg = Config(layers=layers, num_epochs=2, dropout_rate=0.0,
                 eval_every=10**9, num_parts=4, use_bf16=True,
                 edge_shard="off")
    tr = SpmdTrainer(cfg, ds, build_gcn(layers, 0.0))
    assert np.isfinite(float(tr.run_epoch()))


def test_cli_round2_flags_parse():
    """Round-2 CLI flags parse to the expected Config fields."""
    from roc_tpu.train.config import parse_args

    cfg = parse_args(["-file", "x", "-layers", "8-4",
                      "-aggr-backend", "binned", "-aggr-precision", "fast",
                      "-exchange", "ring", "-edge-shard", "off"])
    assert cfg.aggregate_backend == "binned"
    assert cfg.aggregate_precision == "fast"
    assert cfg.exchange == "ring" and cfg.exchange_mode() == "ring"
    assert cfg.edge_shard == "off"
    # bare -edge-shard means "on"; default is auto; -no-halo maps exchange
    cfg2 = parse_args(["-file", "x", "-layers", "8-4", "-edge-shard"])
    assert cfg2.edge_shard == "on"
    cfg3 = parse_args(["-file", "x", "-layers", "8-4"])
    assert cfg3.edge_shard == "auto" and cfg3.exchange_mode() == "halo"
    cfg4 = parse_args(["-file", "x", "-layers", "8-4", "-no-halo"])
    assert cfg4.exchange_mode() == "allgather"


def test_profile_flag_writes_trace(tmp_path):
    """-profile must produce a jax.profiler trace of epochs 3-5 (SURVEY
    §5.1: profiling is a first-class aux system here, absent upstream)."""
    import os

    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gcn
    from roc_tpu.train.config import Config
    from roc_tpu.train.driver import Trainer

    ds = datasets.synthetic("prof", 120, 3.0, 8, 3, n_train=30, n_val=30,
                            n_test=30, seed=6)
    cfg = Config(layers=[8, 8, 3], num_epochs=6, dropout_rate=0.0,
                 eval_every=10**9, profile_dir=str(tmp_path / "tr"))
    Trainer(cfg, ds, build_gcn(cfg.layers, 0.0)).train(
        print_fn=lambda *_: None)
    files = [os.path.join(r, f)
             for r, _, fs in os.walk(tmp_path / "tr") for f in fs]
    assert any("xplane" in f or "trace" in f for f in files), files


@pytest.mark.parametrize("flag", [["-megafuse"], ["-fusion-depth", "2"]])
def test_the_fusion_flags_are_refused(flag, capsys):
    """The whole-layer and cross-layer fused kernels left with their
    options (PR 27): the parser names the flag it does not know."""
    with pytest.raises(SystemExit) as e:
        parse_args(["-file", "x", "-layers", "8-4"] + flag)
    assert e.value.code == 2
    assert flag[0] in capsys.readouterr().err


def test_the_fusion_switches_are_named_nowhere():
    """None of the eight environment names that selected or killed a fused
    path is read by the program, the second harness or the tools."""
    import dataclasses
    import os
    import re
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    gone = re.compile("|".join((
        "ROC_MEGAFUSE", "ROC_FUSION_DEPTH", "ROC_NO_MEGAFUSE",
        "ROC_MEGA_BWD", "ROC_XLAYER", "ROC_NO_GATFUSE", "ROC_GAT_BWD",
        "ROC_GAT_HEADGROUPS")))
    paths = [os.path.join(root, "bench.py")]
    for top in ("roc_tpu", "tools"):
        for d, _, files in os.walk(os.path.join(root, top)):
            paths += [os.path.join(d, f) for f in files
                      if f.endswith((".py", ".sh", ".json", ".cc", ".h"))]
    assert len(paths) > 100
    for p in paths:
        with open(p, encoding="utf-8") as f:
            assert not gone.search(f.read()), p
    fields = {f.name for f in dataclasses.fields(Config)}
    assert not fields & {"megafuse", "fusion_depth"}
