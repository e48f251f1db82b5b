"""The plain graph-transformer reference (`benchmark/references/tconv.py`)
against the program, at small size on the CPU, on seeded random weights
(biases and the LayerNorm's parameters random too: their initial values,
zeros and ones, would hide a gradient): evaluation-mode logits and, with
dropout off, the loss and every parameter gradient, on both roads the
driver can resolve (`xla`, `plan`), with one, two and four heads, on a
regular graph and on a hub graph; the same in training mode with the
program's own keep masks handed to the reference; and the control: a bf16
accumulate in the plan road's sums fails the bound `tconv-reddit.json`
brings."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, graphgen
from benchmark import manifest as mf
from benchmark.references import tconv as ref
from roc_tpu.models import build_model
from roc_tpu.train.driver import dense_graph_data, make_gctx

# the regular graph and the one with a hub and a one-edge row, as the GAT
# reference's tests draw them
from test_benchmark_gat_reference import (GRAPHS, REHEARSAL, ROW_BLOCK,
                                          _hub, _regular)

LAYERS = [24, 16, 16, 5]
PART_NAMES = {f"tconv_{i}_{p}" for i in range(3) for p in ref.PARTS} | {
    f"ln_{j}_{p}" for j in range(2) for p in ("gain", "bias")}


ROADS = ("xla", "plan")
# float32 against float32, sums in another order: three layers deep, each
# with a softmax and a LayerNorm (which divides by a row's own deviation),
# read 2e-7 to 2e-6 here; 1e-5 is rounding and nothing else.  `fast` does
# not reach this op (every sum float32 at `highest` in both modes:
# ops.edge.tconv_attend_plan), so `fast` is held to the same bound; the
# chip's readings are PERF.md's (PR 33).
TOL_EXACT = 1e-5
GRAD_TOL = 1e-4         # hand-derived backward against autodiff, float32


def random_params(model, seed=7):
    """Glorot weights from the program's own initialiser, and small random
    values where it starts from zeros and ones."""
    params = model.init_params(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)
    out = {}
    for i, (name, value) in enumerate(sorted(params.items())):
        if value.ndim == 1 and not name.endswith("_wg"):
            noise = 0.3 * jax.random.normal(jax.random.fold_in(key, i),
                                            value.shape)
            value = value + noise
        out[name] = value
    return out


def _program(ds, layers, heads, road, precision="exact", rate=0.0):
    model = build_model("tconv", layers, rate, heads=heads)
    gd = dense_graph_data(ds.graph, "xla", precision,
                          gat_backend="plan" if road == "plan" else "xla",
                          attention="tconv")
    assert (gd.gat_plans is not None) == (road == "plan")
    return model, make_gctx(gd, ds.graph.num_nodes), random_params(model)


def _inputs(ds):
    return (jnp.asarray(ds.features), jnp.asarray(ds.onehot_labels()),
            jnp.asarray(ds.mask))


def _layers(heads):
    # a hidden entry is the concatenated width: a multiple of every count
    return [24, 16, 16, 5] if heads != 3 else [24, 12, 12, 5]


# precision reaches the plan road's dots only
@pytest.mark.parametrize("road,precision", [
    ("xla", "exact"), ("plan", "exact"), ("plan", "fast")])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("heads", [1, 2, 4])
def test_forward_agrees_with_the_program(heads, graph, road, precision):
    layers = _layers(heads)
    ds = GRAPHS[graph](layers)
    model, gctx, params = _program(ds, layers, heads, road, precision)
    got = np.asarray(model.apply(params, jnp.asarray(ds.features), gctx,
                                 train=False))
    want = ref.reference_logits(params, ds, layers, row_block=ROW_BLOCK)
    assert want.shape == (ds.graph.num_nodes, layers[-1])
    assert np.isfinite(want).all()
    assert ref.head_count(ref.ordered_weights(params)) == heads
    assert checks.rel_fro(got, want) < TOL_EXACT


@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("heads", [1, 4])
@pytest.mark.parametrize("road", ROADS)
def test_loss_and_every_gradient_agree_with_the_program(road, heads, graph):
    ds = GRAPHS[graph](LAYERS)
    model, gctx, params = _program(ds, LAYERS, heads, road)
    x, labels, mask = _inputs(ds)
    val, grads = jax.value_and_grad(model.loss)(
        params, x, labels, mask, gctx, key=None, train=False)
    rval, rgrads = ref.loss_and_grads(params, ds, LAYERS,
                                      row_block=ROW_BLOCK)
    assert float(val) == pytest.approx(float(rval), rel=1e-5)
    # Wq, bq, Wk, bk, Wv, bv, Wr, br, wg a layer; gain and bias a hidden one
    assert set(grads) == set(rgrads) == PART_NAMES
    _assert_gradients_agree(grads, rgrads)


def _assert_gradients_agree(grads, rgrads):
    """Every gradient within GRAD_TOL of the reference's, relative to its
    own norm; but the key bias: bk shifts every score of a destination by
    the same q_i . bk / sqrt(d), which the softmax over that destination's
    in-edges cancels, so its true gradient is ZERO and both sides read
    rounding (1e-6 of bq's).  It is held to that: under GRAD_TOL of the
    query bias's gradient, on both sides."""
    for name in grads:
        if name.endswith("_bk"):
            scale = GRAD_TOL * float(np.linalg.norm(rgrads[name[:-1] + "q"]))
            assert scale > 0
            assert float(np.linalg.norm(grads[name])) < scale, name
            assert float(np.linalg.norm(rgrads[name])) < scale, name
            continue
        assert np.linalg.norm(rgrads[name]) > 0, name
        assert checks.rel_fro(grads[name], rgrads[name]) < GRAD_TOL, name


def test_row_blocks_do_not_change_the_result():
    ds = _hub(LAYERS)
    _, _, params = _program(ds, LAYERS, 4, "xla")
    a = ref.reference_logits(params, ds, LAYERS, row_block=64)
    b = ref.reference_logits(params, ds, LAYERS, row_block=4096)
    assert checks.rel_fro(a, b) < 1e-6


@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("road", ROADS)
def test_training_mode_agrees_given_the_programs_masks(road, graph):
    """Dropout 0.3 on every layer's input and on the normalised attention
    coefficients: the program's loss and gradients for one key against the
    reference given the masks that key draws (`Model.keep_masks`, the
    step's own draw functions).  On the plan road the backward has no mask
    saved: it redraws it, and a different draw would show here."""
    heads, rate = 4, 0.3
    ds = GRAPHS[graph](LAYERS)
    model, gctx, params = _program(ds, LAYERS, heads, road, rate=rate)
    x, labels, mask = _inputs(ds)
    key = jax.random.PRNGKey(11)
    val, grads = jax.value_and_grad(model.loss)(
        params, x, labels, mask, gctx, key=key, train=True)
    masks = model.keep_masks(key, ds.graph.num_nodes, ds.graph.num_edges)
    by_kind = {kind: [masks[i] for i, op in enumerate(model.ops)
                      if op.kind == kind] for kind in ("dropout", "gat")}
    assert [m.shape for m in by_kind["gat"]] == [
        (heads, ds.graph.num_edges)] * 3
    rval, rgrads = ref.loss_and_grads(
        params, ds, LAYERS, row_block=ROW_BLOCK, rate=rate,
        edge_keep=by_kind["gat"], input_keep=by_kind["dropout"])
    assert float(val) == pytest.approx(float(rval), rel=1e-5)
    _assert_gradients_agree(grads, rgrads)
    # and the masks matter: the evaluation-mode loss is another number
    plain = model.loss(params, x, labels, mask, gctx, key=None, train=False)
    assert abs(float(plain) - float(val)) > 1e-3 * abs(float(val))


def test_the_reference_imports_nothing_of_the_programs_ops():
    with open(ref.__file__, encoding="utf-8") as f:
        source = f.read()
    assert "import roc_tpu" not in source and "from roc_tpu" not in source


def _plan_error(ds, layers, heads, monkeypatch, accumulate_bf16):
    """The plan road at `fast` against the reference; with
    ``accumulate_bf16`` every one-hot contraction's result is rounded to
    bf16 (the CPU's dot does not round at the MXU's default, so the
    rounding is made here), which is the least a bf16 accumulate does."""
    from roc_tpu.ops import aggregate
    real = aggregate._one_hot_dots

    def rounded(*args):
        return real(*args).astype(jnp.bfloat16).astype(jnp.float32)

    if accumulate_bf16:
        monkeypatch.setattr(aggregate, "_one_hot_dots", rounded)
    jax.clear_caches()
    try:
        model, gctx, params = _program(ds, layers, heads, "plan", "fast")
        got = np.asarray(jax.jit(
            lambda p, x: model.apply(p, x, gctx, train=False))(
                params, jnp.asarray(ds.features)))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    want = ref.reference_logits(params, ds, layers, row_block=1024)
    return checks.rel_fro(got, want)


def test_fast_is_float32_here_and_a_bf16_accumulate_fails_the_cell(
        monkeypatch):
    """At the cell's widths and in-degree (about 90): the program at `fast`
    reads float32 reassociation, far inside the bound `tconv-reddit.json`
    brings; a bf16 ACCUMULATE (every contraction's result rounded) comes
    out not correct by it, with room (the chip's control, the S2 combine at
    the MXU's default, reads the same order: PERF.md section 2)."""
    layers, heads = [602, 128, 128, 41], 4
    recipe = graphgen.load_recipe(
        os.path.join(REHEARSAL, "traffic", "tiny-skewed.json"))
    ds = graphgen.generate(dict(recipe, nodes=3000, avg_degree=50),
                           layers[0], layers[-1], 1)
    fast = _plan_error(ds, layers, heads, monkeypatch, False)
    accumulate = _plan_error(ds, layers, heads, monkeypatch, True)
    conf = mf.load(os.path.join(mf.ROOT, "benchmark", "configs",
                                "tconv-reddit.json"))
    for which in checks.WHICH:
        bound = checks.logits_tol(conf, "xla", which)
        assert bound < checks.logits_tol({}, "xla", which) == 4e-3
        assert fast < bound / 3 < bound * 3 < accumulate
