"""GCNII (`-model gcnii`, models/gcnii.py) on the op IR: the program's
logits, loss and every gradient against the plain reference
(`benchmark/references/gcnii.py`) in evaluation mode and, GIVEN the masks
`Model.keep_masks` draws, in training mode, on the `xla` and the `binned`
(interpreted) backends; the identity-mapping weights number by number; the
builder's defaults against the configuration's file; what the builder
refuses; and the roads that carry the weighted `add` and the far input."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, graphgen
from benchmark.references import gcnii as ref
from roc_tpu.models import build_gcnii, build_model
from roc_tpu.models.gcnii import ALPHA, LAMDA, gcnii_beta
from roc_tpu.train.config import Config, parse_args
from roc_tpu.train.driver import dense_graph_data, make_gctx, make_trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = [24, 16, 16, 16, 16, 5]        # four GCNII layers of width 16
EDGE_BLOCK = 1 << 12                    # several blocks over ~10,000 edges
BACKENDS = ("xla", "binned")
# float32 against float32, sums in another order, six products deep
TOL = 2e-5
GRAD_TOL = 2e-4


def _dataset(seed=1):
    recipe = graphgen.load_recipe(os.path.join(
        ROOT, "benchmark", "rehearsal", "traffic", "tiny-regular.json"))
    return graphgen.generate(recipe, LAYERS[0], LAYERS[-1], seed)


def _params(model, seed=7):
    """Glorot weights from the program's own initialiser; the biases, which
    start at zero and would hide their gradient's path, small and random."""
    params = model.init_params(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)
    return {name: value if value.ndim == 2 else value + 0.3 *
            jax.random.normal(jax.random.fold_in(key, i), value.shape)
            for i, (name, value) in enumerate(sorted(params.items()))}


def _program(ds, backend, rate=0.0):
    model = build_model("gcnii", LAYERS, rate)
    gd = dense_graph_data(ds.graph, backend, "exact")
    assert gd.backend == backend
    return model, make_gctx(gd, ds.graph.num_nodes), _params(model)


def _inputs(ds):
    return (jnp.asarray(ds.features), jnp.asarray(ds.onehot_labels()),
            jnp.asarray(ds.mask))


@pytest.mark.parametrize("backend", BACKENDS)
def test_forward_agrees_with_the_reference(backend):
    ds = _dataset()
    model, gctx, params = _program(ds, backend)
    got = np.asarray(model.apply(params, jnp.asarray(ds.features), gctx,
                                 train=False))
    want = ref.reference_logits(params, ds, LAYERS, edge_block=EDGE_BLOCK)
    assert want.shape == (ds.graph.num_nodes, LAYERS[-1])
    assert checks.rel_fro(got, want) < TOL


@pytest.mark.parametrize("train", [False, True], ids=["eval", "train"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_loss_and_gradients_agree_with_the_reference(backend, train):
    """Evaluation mode with dropout off; training mode at rate 0.5 with the
    reference handed the very masks the step draws from its key."""
    ds = _dataset()
    rate = 0.5 if train else 0.0
    model, gctx, params = _program(ds, backend, rate)
    x, labels, mask = _inputs(ds)
    key = jax.random.PRNGKey(11) if train else None
    val, grads = jax.value_and_grad(model.loss)(
        params, x, labels, mask, gctx, key=key, train=train)
    keep = None
    if train:
        masks = model.keep_masks(key, ds.graph.num_nodes,
                                 ds.graph.num_edges)
        keep = [masks[i] for i in sorted(masks)]    # dropouts in op order
        assert len(keep) == len(LAYERS)             # X, H0..H3, H4
    rval, rgrads = ref.loss_and_grads(params, ds, LAYERS,
                                      edge_block=EDGE_BLOCK, keep=keep,
                                      rate=rate)
    assert abs(float(val) - float(rval)) <= 1e-5 * abs(float(rval))
    assert set(grads) == set(rgrads) == set(params)
    for name in sorted(grads):
        assert float(jnp.linalg.norm(rgrads[name])) > 0, name
        assert checks.rel_fro(grads[name], rgrads[name]) < GRAD_TOL, name


def test_beta_number_by_number():
    model = build_gcnii([8] + [4] * 16 + [3], 0.5)
    mixes = [op for op in model.ops if op.kind == "add"]
    assert len(mixes) == 32
    for layer in range(1, 17):
        want = math.log(0.4 / layer + 1.0)
        assert gcnii_beta(LAMDA, layer) == ref.beta(layer) == want
        residual, identity = mixes[2 * layer - 2: 2 * layer]
        assert residual.attrs["wa"] == pytest.approx(0.9, abs=1e-15)
        assert residual.attrs["wb"] == 0.1
        assert identity.attrs["wa"] == 1.0 - want
        assert identity.attrs["wb"] == want
    assert gcnii_beta(LAMDA, 1) == pytest.approx(0.33647223662121289)
    assert gcnii_beta(LAMDA, 16) == pytest.approx(0.024692612590371501)


def test_defaults_are_what_the_configuration_states():
    """`benchmark/run.py` hands `build_model` neither alpha nor lambda: the
    builder's defaults ARE the cell's, and the file says which."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "gcnii-reddit.json"), encoding="utf-8") as f:
        conf = json.load(f)
    assumed = conf["assumed"]
    assert (assumed["alpha"]["value"], assumed["lambda"]["value"]) \
        == (ALPHA, LAMDA) == (ref.ALPHA, ref.LAMDA) == (0.1, 0.4)
    a = build_model(conf["model"], conf["layers"], conf["dropout"])
    b = build_gcnii(conf["layers"], conf["dropout"], alpha=0.1, lamda=0.4)
    assert [op.attrs for op in a.ops] == [op.attrs for op in b.ops]
    assert a.num_layers == 18 and a.num_linear == 18
    assert a.far_outputs() == {3: 0}        # H0, made by layer 0


def test_structure_parameters_and_what_the_builder_refuses():
    model = build_gcnii([602] + [256] * 16 + [41], 0.5)
    kinds = [op.kind for op in model.ops]
    assert kinds[:3] == ["dropout", "linear", "activation"]
    assert kinds[3:11] == ["dropout", "norm", "aggregate", "norm", "add",
                           "linear", "add", "activation"]
    assert kinds[-2:] == ["dropout", "linear"]
    assert set(kinds) == {"dropout", "linear", "activation", "norm",
                          "aggregate", "add"}       # no new op kind
    params = model.init_params(jax.random.PRNGKey(0))
    assert sorted(params) == sorted(
        [f"linear_{i}" for i in range(18)]
        + ["linear_0_bias", "linear_17_bias"])
    assert params["linear_0"].shape == (602, 256)
    assert params["linear_9"].shape == (256, 256)
    assert params["linear_17_bias"].shape == (41,)
    assert not np.asarray(params["linear_0_bias"]).any()
    with pytest.raises(ValueError, match=r"hidden widths \[256, 128\] differ"):
        build_gcnii([602, 256, 128, 41])
    with pytest.raises(ValueError, match="at least one hidden entry"):
        build_gcnii([602, 41])
    with pytest.raises(ValueError, match="gcnii is defined on sum"):
        build_model("gcnii", [8, 4, 3], aggr="avg")
    assert parse_args(["-model", "gcnii", "-layers", "8-4-4-3"]).model \
        == "gcnii"


@pytest.mark.parametrize("road", ["spmd", "stream", "frozen"])
def test_the_other_roads_carry_the_model(road):
    """The weighted `add`, the biases and the far input cost the other
    roads nothing (they run `Model.apply`, the streamed executor its own
    copy of the elementwise ops): four parts, streamed or not, train to the
    one-chip losses, and the frozen loader's logits are the trainer's."""
    from roc_tpu.graph import datasets
    ds = datasets.get("roc-audit", seed=1)
    layers = [ds.in_dim, 16, 16, 16, ds.num_classes]

    def trainer(**kw):
        cfg = Config(layers=layers, num_epochs=2, dropout_rate=0.0,
                     eval_every=10**9, model="gcnii", **kw)
        return make_trainer(cfg, ds, build_model("gcnii", layers, 0.0))

    one = trainer()
    if road == "frozen":
        from roc_tpu.train.frozen import load_frozen
        bundle = load_frozen(one.config, ds, one.model)
        got = np.asarray(bundle.predict_logits())
        np.testing.assert_allclose(got, np.asarray(one.predict_logits()),
                                   rtol=1e-5, atol=1e-6)
        return
    many = trainer(num_parts=4, stream=road == "stream")
    for _ in range(2):
        assert abs(float(many.run_epoch()) - float(one.run_epoch())) <= 1e-3
