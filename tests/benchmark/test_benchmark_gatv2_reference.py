"""The plain GATv2 reference (`benchmark/references/gatv2.py`) against the
program, at small size on the CPU, on seeded random weights: evaluation-mode
logits and, with dropout off, the loss and every parameter gradient (`a`
included), on both roads the trainer can resolve (`xla`, `plan`), with one,
two and eight heads, on a regular graph and on a hub graph; the same in
training mode with the program's own keep masks handed to the reference;
the slope at zero, where every self-edge's pre-activation is an exact 0;
and the controls: one bf16 rounding of the value sum's products, or of the
score's operands, fails the bound `gatv2-reddit.json` brings."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, graphgen
from benchmark import manifest as mf
from benchmark.references import gatv2 as ref
from roc_tpu.models import build_model
from roc_tpu.train.driver import dense_graph_data, make_gctx

# the regular graph and the one with a hub and a one-edge row, as the GAT
# reference's tests draw them
from test_benchmark_gat_reference import (GRAPHS, REHEARSAL, ROW_BLOCK,
                                          _inputs)

LAYERS = [24, 8, 5]
PART_NAMES = {f"gatv2_{i}_{p}" for i in range(2) for p in ref.PARTS}
ROADS = ("xla", "plan")
# float32 against float32, sums in another order: two layers deep, each
# with a softmax, read 1e-7 to 1e-6 here; 1e-5 is rounding and nothing
# else.  `fast` does not reach this op (every sum float32 at `highest` in
# both modes: ops.edge.gatv2_attend_plan), so `fast` is held to the same
# bound; the chip's readings are PERF.md's (section 2).
TOL_EXACT = 1e-5
GRAD_TOL = 1e-4         # hand-derived backward against autodiff, float32


def _program(ds, layers, heads, road, precision="exact", rate=0.0,
             params=None):
    model = build_model("gatv2", layers, rate, heads=heads)
    gd = dense_graph_data(ds.graph, "xla", precision,
                          gat_backend="plan" if road == "plan" else "xla",
                          attention="gatv2")
    assert (gd.gat_plans is not None) == (road == "plan")
    if params is None:
        params = model.init_params(jax.random.PRNGKey(7))
    return model, make_gctx(gd, ds.graph.num_nodes), params


# precision reaches nothing of this op; both are run on the plan road
@pytest.mark.parametrize("road,precision", [
    ("xla", "exact"), ("plan", "exact"), ("plan", "fast")])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("heads", [1, 2, 8])
def test_forward_agrees_with_the_program(heads, graph, road, precision):
    ds = GRAPHS[graph](LAYERS)
    model, gctx, params = _program(ds, LAYERS, heads, road, precision)
    got = np.asarray(model.apply(params, jnp.asarray(ds.features), gctx,
                                 train=False))
    want = ref.reference_logits(params, ds, LAYERS, row_block=ROW_BLOCK)
    assert want.shape == (ds.graph.num_nodes, LAYERS[-1])
    assert np.isfinite(want).all()
    assert checks.rel_fro(got, want) < TOL_EXACT


def _assert_gradients_agree(grads, rgrads):
    assert set(grads) == set(rgrads) == PART_NAMES
    for name in grads:
        assert np.linalg.norm(rgrads[name]) > 0, name
        assert checks.rel_fro(grads[name], rgrads[name]) < GRAD_TOL, name


@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("heads", [1, 8])
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
    # W_l, W_r and a, every layer
    _assert_gradients_agree(grads, rgrads)


def test_row_blocks_do_not_change_the_result():
    ds = GRAPHS["hub"](LAYERS)
    _, _, params = _program(ds, LAYERS, 8, "xla")
    a = ref.reference_logits(params, ds, LAYERS, row_block=64)
    b = ref.reference_logits(params, ds, LAYERS, row_block=4096)
    assert checks.rel_fro(a, b) < 1e-6


@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("road", ROADS)
def test_training_mode_agrees_given_the_programs_masks(road, graph):
    """Dropout 0.6 on both layers' inputs and on the normalised attention
    coefficients: the program's loss and gradients for one key against the
    reference given the masks that key draws (`Model.keep_masks`, the
    step's own draw functions).  On the plan road the backward has no mask
    saved: it redraws it, and a different draw would show here."""
    heads, rate = 8, 0.6
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
        (heads, ds.graph.num_edges), (1, ds.graph.num_edges)]
    rval, rgrads = ref.loss_and_grads(
        params, ds, LAYERS, row_block=ROW_BLOCK, rate=rate,
        edge_keep=by_kind["gat"], input_keep=by_kind["dropout"])
    assert float(val) == pytest.approx(float(rval), rel=1e-5)
    _assert_gradients_agree(grads, rgrads)
    # and the masks matter: the evaluation-mode loss is another number
    plain = model.loss(params, x, labels, mask, gctx, key=None, train=False)
    assert abs(float(plain) - float(val)) > 1e-3 * abs(float(val))


@pytest.mark.parametrize("road", ROADS)
def test_the_slope_at_zero_agrees_with_the_reference(road):
    """W_r = -W_l in the first layer: every self-edge's pre-activation
    xr_i + xl_i is an exact 0 at every channel (the graph carries a
    self-edge a row), where LeakyReLU's derivative is a convention.  The
    reference's ``where(p >= 0, p, 0.2 p)`` and the program agree on it
    (1): loss and every gradient as anywhere else.  A slope of 0.2 there
    moves W_l's and W_r's gradients by far more than the bound."""
    ds = GRAPHS["regular"](LAYERS)
    model = build_model("gatv2", LAYERS, 0.0, heads=8)
    params = model.init_params(jax.random.PRNGKey(7))
    params = dict(params, gatv2_0_wr=-params["gatv2_0_wl"])
    model, gctx, params = _program(ds, LAYERS, 8, road, params=params)
    g = ds.graph
    self_edges = np.asarray(g.col_idx) == np.repeat(
        np.arange(g.num_nodes), np.diff(np.asarray(g.row_ptr)))
    assert self_edges.sum() == g.num_nodes
    x, labels, mask = _inputs(ds)
    val, grads = jax.value_and_grad(model.loss)(
        params, x, labels, mask, gctx, key=None, train=False)
    rval, rgrads = ref.loss_and_grads(params, ds, LAYERS,
                                      row_block=ROW_BLOCK)
    assert float(val) == pytest.approx(float(rval), rel=1e-5)
    _assert_gradients_agree(grads, rgrads)


def test_the_reference_imports_nothing_of_the_programs_ops():
    with open(ref.__file__, encoding="utf-8") as f:
        source = f.read()
    assert "import roc_tpu" not in source and "from roc_tpu" not in source


def _plan_error(ds, layers, heads, monkeypatch, control):
    """The plan road at `fast` against the reference, evaluation mode;
    ``control``: None, "u" (every product of a row sum rounded to bf16
    once, what the MXU's default precision computes: the CPU's dot does not
    round, so the rounding is made here) or "score" (the score's operands,
    xl and xr, rounded to bf16)."""
    from roc_tpu.ops import aggregate
    from roc_tpu.ops import edge as em
    if control == "u":
        real = aggregate._one_hot_dots

        def rounded(g, *args):
            return real(g.astype(jnp.bfloat16).astype(jnp.float32), *args)
        monkeypatch.setattr(aggregate, "_one_hot_dots", rounded)
    elif control == "score":
        real_score = em._dynamic_score

        def rounded_score(xl, xr, *args):
            def bf16(t):
                return jax.lax.reduce_precision(t, 8, 7)
            return real_score(bf16(xl), bf16(xr), *args)
        monkeypatch.setattr(em, "_dynamic_score", rounded_score)
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


def test_fast_is_float32_here_and_one_precision_lower_fails_the_cell(
        monkeypatch):
    """At the cell's widths and in-degree (about 90): the program at `fast`
    reads float32 reassociation, far inside the bound `gatv2-reddit.json`
    brings; one bf16 rounding of each product of the value sum (the MXU's
    default) or of the score's operands comes out not correct by it, with
    room (the chip's readings of the same controls: PERF.md section 2)."""
    layers, heads = [602, 8, 41], 8
    recipe = graphgen.load_recipe(
        os.path.join(REHEARSAL, "traffic", "tiny-skewed.json"))
    ds = graphgen.generate(dict(recipe, nodes=3000, avg_degree=50),
                           layers[0], layers[-1], 1)
    fast = _plan_error(ds, layers, heads, monkeypatch, None)
    conf = mf.load(os.path.join(mf.ROOT, "benchmark", "configs",
                                "gatv2-reddit.json"))
    for control in ("u", "score"):
        lower = _plan_error(ds, layers, heads, monkeypatch, control)
        for which in checks.WHICH:
            bound = checks.logits_tol(conf, "xla", which)
            assert bound < checks.logits_tol({}, "xla", which) == 4e-3
            assert fast < bound / 3 < bound * 3 < lower, (control, lower)
