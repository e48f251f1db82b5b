"""The plain reference against the program, at small size on the CPU:
evaluation-mode logits and, with dropout off, the loss and its gradients;
and that the tolerance the benchmark holds the chip to catches a bf16
accumulate in the cells' own kernels."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, graphgen
from benchmark import manifest as mf
from benchmark.references import gcn as ref
from roc_tpu.models import build_model
from roc_tpu.train.config import Config
from roc_tpu.train.driver import dense_graph_data, make_gctx, make_trainer

REHEARSAL = os.path.join(mf.ROOT, "benchmark", "rehearsal")
CASES = {
    "two_layers": ([24, 16, 5], "tiny-regular"),
    "two_layers_skewed": ([24, 16, 5], "tiny-skewed"),
    "three_layers_residual": ([12, 16, 16, 7], "tiny-local-p4"),
}


def _dataset(layers, traffic, seed=1):
    recipe = graphgen.load_recipe(
        os.path.join(REHEARSAL, "traffic", traffic + ".json"))
    return graphgen.generate(recipe, layers[0], layers[-1], seed)


def _program(layers, ds, backend="xla", precision="exact"):
    model = build_model("gcn", layers, 0.0)
    gd = dense_graph_data(ds.graph, backend, precision)
    gctx = make_gctx(gd, ds.graph.num_nodes)
    params = model.init_params(jax.random.PRNGKey(7))
    return model, gctx, params


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_agrees_with_the_program(case):
    layers, traffic = CASES[case]
    ds = _dataset(layers, traffic)
    model, gctx, params = _program(layers, ds)
    got = np.asarray(model.apply(params, jnp.asarray(ds.features), gctx,
                                 train=False))
    want = ref.reference_logits(params, ds, layers, edge_block=4096)
    # float32 against float32, sums in another order: 1e-5 is rounding
    assert checks.rel_fro(got, want) < 1e-5
    assert want.shape == (ds.graph.num_nodes, layers[-1])


@pytest.mark.parametrize("case", sorted(CASES))
def test_loss_and_gradients_agree_with_the_program(case):
    layers, traffic = CASES[case]
    ds = _dataset(layers, traffic)
    model, gctx, params = _program(layers, ds)
    x = jnp.asarray(ds.features)
    labels = jnp.asarray(ds.onehot_labels())
    mask = jnp.asarray(ds.mask)
    val, grads = jax.value_and_grad(model.loss)(
        params, x, labels, mask, gctx, key=None, train=False)
    rval, rgrads = ref.loss_and_grads(params, ds, layers, edge_block=4096)
    assert float(val) == pytest.approx(float(rval), rel=1e-5)
    assert set(grads) == set(rgrads)
    for name in grads:
        assert checks.rel_fro(grads[name], rgrads[name]) < 1e-4, name


def test_edge_blocks_do_not_change_the_sum():
    layers, traffic = CASES["three_layers_residual"]
    ds = _dataset(layers, traffic)
    _, _, params = _program(layers, ds)
    a = ref.reference_logits(params, ds, layers, edge_block=512)
    b = ref.reference_logits(params, ds, layers, edge_block=1 << 16)
    assert checks.rel_fro(a, b) < 1e-6


def test_sharded_program_logits_come_back_in_graph_order():
    """benchmark/run.py reads a sharded trainer's padded logits back
    through the partition; on four virtual devices they must match the
    reference row for row."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    from benchmark import run as bench_run
    layers, traffic = CASES["three_layers_residual"]
    ds = _dataset(layers, traffic)
    cfg = Config(layers=layers, num_parts=4, dropout_rate=0.5, seed=3,
                 weight_decay=0.0)
    trainer = make_trainer(cfg, ds, build_model("gcn", layers, 0.5))
    params = jax.device_get(trainer.params)
    got = bench_run.program_logits(trainer, params)
    want = ref.reference_logits(params, ds, layers, edge_block=4096)
    assert got.shape == want.shape
    assert checks.rel_fro(got, want) < 1e-5


def _degree_50(traffic):
    """A rehearsal recipe at the cells' density: in-degree about 90."""
    recipe = graphgen.load_recipe(
        os.path.join(REHEARSAL, "traffic", traffic + ".json"))
    return graphgen.generate(dict(recipe, avg_degree=50), 24, 5, 1)


def _binned_fast_error(ds, layers=(24, 16, 5)):
    layers = list(layers)
    model, gctx, params = _program(layers, ds, "binned", "fast")
    got = np.asarray(jax.jit(
        lambda p, x: model.apply(p, x, gctx, train=False))(
            params, jnp.asarray(ds.features)))
    want = ref.reference_logits(params, ds, layers, edge_block=4096)
    return checks.rel_fro(got, want)


@pytest.mark.parametrize("traffic", ["tiny-regular", "tiny-skewed"])
def test_binned_fast_kernels_are_inside_the_tolerance(traffic):
    """The kernels the Reddit cells run (Pallas interpreter here), `fast`
    precision, Glorot parameters, against the float32 reference: inside the
    chip's bound for initial parameters, at the error the chip shows
    (1.9e-4 to 2.9e-4 there)."""
    err = _binned_fast_error(_degree_50(traffic))
    assert 1e-4 < err < checks.logits_tol("binned", "initial")


@pytest.mark.parametrize("traffic", ["tiny-regular", "tiny-skewed"])
def test_bf16_accumulate_in_the_binned_kernels_fails(traffic, monkeypatch):
    """Through the real kernels: every one-hot contraction of phase 1 and
    phase 2 goes through `binned._onehot_dot`; with its result rounded to
    bf16 (what `preferred_element_type=bfloat16` would do, and the least a
    bf16 accumulate does: the sum across chunks stays float32 here) the
    initial logits land outside the bound the same kernels are inside of
    above.  The final-parameter bound alone would let this pass."""
    from roc_tpu.ops.pallas import binned
    exact_sum = binned._onehot_dot

    def rounded(t, xv, dims, exact):
        return exact_sum(t, xv, dims, exact).astype(jnp.bfloat16).astype(
            jnp.float32)

    monkeypatch.setattr(binned, "_onehot_dot", rounded)
    jax.clear_caches()          # the kernels above were traced unrounded
    try:
        err = _binned_fast_error(_degree_50(traffic))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert err > 1.4 * checks.logits_tol("binned", "initial")
    assert err < checks.logits_tol("binned", "final")


def test_the_tolerance_follows_the_backend_and_the_parameters():
    assert checks.logits_tol("binned", "initial") == 6e-4
    assert checks.logits_tol("binned", "final") == 2e-3
    # matmul measured 0.9e-3 to 1.8e-3 on the chip; xla and the rest as it
    for backend in ("matmul", "xla", "anything-new"):
        assert checks.logits_tol(backend, "initial") == 4e-3
        assert checks.logits_tol(backend, "final") == 4e-3
