"""The plain GAT reference (`benchmark/references/gat.py`) against the
program, at small size on the CPU: evaluation-mode logits and, with dropout
off, the loss and every weight gradient, on each attention path the driver
can resolve (`xla` dense, `xla` chunked, `plan`), with one head and with
eight, on a regular graph and on a hub graph; and the same comparison in
training mode, with the program's own keep masks handed to the reference."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, graphgen
from benchmark import manifest as mf
from benchmark.references import gat as ref
from roc_tpu.graph.csr import from_edges
from roc_tpu.models import build_model
from roc_tpu.ops import edge as edge_mod
from roc_tpu.train.driver import dense_graph_data, make_gctx

REHEARSAL = os.path.join(mf.ROOT, "benchmark", "rehearsal")
HUB_IN_EDGES = 1100
ROW_BLOCK = 256         # several blocks on 1,500 rows


def _regular(layers, seed=1):
    recipe = graphgen.load_recipe(
        os.path.join(REHEARSAL, "traffic", "tiny-regular.json"))
    return graphgen.generate(recipe, layers[0], layers[-1], seed)


def _hub(layers, seed=1):
    """The regular graph with vertex 0 made a hub (>= 1,000 in-edges: many
    plan chunks for one window) and vertex 1 left with its self-edge only
    (a softmax over one coefficient)."""
    ds = _regular(layers, seed)
    g = ds.graph
    src, dst = np.asarray(g.col_idx), np.asarray(g.dst_idx)
    keep = ~((dst == 1) & (src != 1))
    extra = np.arange(2, 2 + HUB_IN_EDGES)
    edges = np.unique(np.stack(
        [np.concatenate([src[keep], extra]),
         np.concatenate([dst[keep], np.zeros_like(extra)])], 1), axis=0)
    graph = from_edges(g.num_nodes, edges[:, 0], edges[:, 1])
    deg = np.diff(graph.row_ptr)
    assert deg[0] >= 1000 and deg[1] == 1 and deg.min() >= 1
    return dataclasses.replace(ds, graph=graph)


GRAPHS = {"regular": _regular, "hub": _hub}
PATHS = ("xla", "chunked", "plan")
# float32 against float32, sums in another order: 1e-5 is rounding.  `fast`
# feeds the two weighted feature sums (u, dtable) to the MXU at its default
# precision, which on the chip rounds each product e*h once to bf16
# (relative step 2^-9) before a float32 sum; scores, maxima and
# normalisers stay float32 in both modes.  One such rounding a term moves
# the logits by at most 2^-9 = 2e-3 and, averaged over a row's in-edges,
# by less: the bound is the harness's 4e-3 for this backend
# (checks.LOGITS_REL_FRO_TOL_OTHER).  The CPU's dot does not round at
# `default`, so here `fast` reads what `exact` reads; the chip's reading is
# PERF.md's (PR 25).
TOL = {"exact": 1e-5, "fast": checks.LOGITS_REL_FRO_TOL_OTHER["initial"]}


def _program(ds, layers, heads, path, precision="exact", rate=0.0,
             monkeypatch=None):
    model = build_model("gat", layers, rate, heads=heads)
    gd = dense_graph_data(ds.graph, "xla", precision,
                          gat_backend="plan" if path == "plan" else "xla")
    assert (gd.gat_plans is not None) == (path == "plan")
    if path == "chunked":
        # the memory-bounded scan, many steps (tests/test_gat.py's recipe)
        monkeypatch.setattr(edge_mod, "_GAT_CHUNK_THRESHOLD_ELEMS", 1)
        monkeypatch.setattr(edge_mod, "_GAT_CHUNK_TARGET_ELEMS",
                            512 * heads * layers[1])
        monkeypatch.setattr(edge_mod, "_GAT_CHUNK_MIN", 16)
    gctx = make_gctx(gd, ds.graph.num_nodes)
    params = model.init_params(jax.random.PRNGKey(7))
    return model, gctx, params


def _inputs(ds):
    return (jnp.asarray(ds.features), jnp.asarray(ds.onehot_labels()),
            jnp.asarray(ds.mask))


# precision reaches the plan path's dots only
@pytest.mark.parametrize("path,precision", [
    ("xla", "exact"), ("chunked", "exact"), ("plan", "exact"),
    ("plan", "fast")])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("heads", [1, 8])
def test_forward_agrees_with_the_program(heads, graph, path, precision,
                                         monkeypatch):
    layers = [24, 8, 5]
    ds = GRAPHS[graph](layers)
    model, gctx, params = _program(ds, layers, heads, path, precision,
                                   monkeypatch=monkeypatch)
    got = np.asarray(model.apply(params, jnp.asarray(ds.features), gctx,
                                 train=False))
    want = ref.reference_logits(params, ds, layers, row_block=ROW_BLOCK)
    assert want.shape == (ds.graph.num_nodes, layers[-1])
    assert np.isfinite(want).all()
    assert checks.rel_fro(got, want) < TOL[precision]


@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("heads", [1, 8])
@pytest.mark.parametrize("path", PATHS)
def test_loss_and_gradients_agree_with_the_program(path, heads, graph,
                                                   monkeypatch):
    layers = [24, 8, 5]
    ds = GRAPHS[graph](layers)
    model, gctx, params = _program(ds, layers, heads, path,
                                   monkeypatch=monkeypatch)
    x, labels, mask = _inputs(ds)
    val, grads = jax.value_and_grad(model.loss)(
        params, x, labels, mask, gctx, key=None, train=False)
    rval, rgrads = ref.loss_and_grads(params, ds, layers,
                                      row_block=ROW_BLOCK)
    assert float(val) == pytest.approx(float(rval), rel=1e-5)
    assert set(grads) == set(rgrads) == {
        f"gat_{i}_{s}" for i in (0, 1) for s in ("w", "asrc", "adst")}
    for name in grads:
        # the plan path's backward is hand-derived, the reference's is
        # autodiff of the equations: float32 both, 1e-4 as for the GCN
        assert checks.rel_fro(grads[name], rgrads[name]) < 1e-4, name


def test_row_blocks_do_not_change_the_result():
    layers = [24, 8, 5]
    ds = _hub(layers)
    _, _, params = _program(ds, layers, 8, "xla")
    a = ref.reference_logits(params, ds, layers, row_block=64)
    b = ref.reference_logits(params, ds, layers, row_block=4096)
    assert checks.rel_fro(a, b) < 1e-6


@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("path", PATHS)
def test_training_mode_agrees_given_the_programs_masks(path, graph,
                                                       monkeypatch):
    """Dropout 0.6 on both layers' inputs and on the normalised attention
    coefficients: the program's loss and gradients for one key against the
    reference given the masks that key draws (`Model.keep_masks`, the
    step's own draw functions).  On the plan path the backward has no mask
    saved: it redraws it, and a different draw would show here."""
    layers, heads, rate = [24, 8, 5], 8, 0.6
    ds = GRAPHS[graph](layers)
    model, gctx, params = _program(ds, layers, heads, path, rate=rate,
                                   monkeypatch=monkeypatch)
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
        params, ds, layers, row_block=ROW_BLOCK, rate=rate,
        edge_keep=by_kind["gat"], input_keep=by_kind["dropout"])
    assert float(val) == pytest.approx(float(rval), rel=1e-5)
    for name in grads:
        assert checks.rel_fro(grads[name], rgrads[name]) < 1e-4, name
    # and the masks matter: the evaluation-mode loss is another number
    plain = model.loss(params, x, labels, mask, gctx, key=None, train=False)
    assert abs(float(plain) - float(val)) > 1e-3 * abs(float(val))


def _plan_fast_error(ds, layers, heads, monkeypatch, round_result):
    """The plan path at `fast` with the MXU's default precision emulated
    on the CPU: the weighted sums' operand (the products e * h) rounded to
    bf16 once, and with ``round_result`` every contraction's result as
    well, which is the least a bf16 accumulate does."""
    from roc_tpu.ops import aggregate
    real = aggregate._one_hot_dots

    def emulated(g, ed, ob, cb, precision, combine_precision=None):
        if precision == "highest":
            return real(g, ed, ob, cb, precision, combine_precision)
        out = real(g.astype(jnp.bfloat16).astype(jnp.float32), ed, ob, cb,
                   precision, combine_precision)
        return out.astype(jnp.bfloat16).astype(jnp.float32) \
            if round_result else out

    monkeypatch.setattr(aggregate, "_one_hot_dots", emulated)
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


def test_fast_is_one_rounding_and_the_harness_bound_is_wide(monkeypatch):
    """At the cell's widths and in-degree (about 90): `fast`, as the MXU
    computes it, reads 2e-4 (one bf16 rounding of each product, float32
    sums; the chip reads the same, PERF.md PR 25).  A bf16 ACCUMULATE reads
    1.7e-3: eight times as much, and still inside the 4e-3 the harness
    gives every backend but `binned` (checks.LOGITS_REL_FRO_TOL_OTHER),
    which this PR may not edit.  So on this cell `correct` would let a
    bf16 accumulate through; PERF.md section 7 asks a `benchmark` issue for
    a bound of the cell's own, and until then this test holds the program
    to its one rounding."""
    layers, heads = [602, 8, 41], 8
    recipe = graphgen.load_recipe(
        os.path.join(REHEARSAL, "traffic", "tiny-skewed.json"))
    ds = graphgen.generate(dict(recipe, nodes=3000, avg_degree=50),
                           layers[0], layers[-1], 1)
    fast = _plan_fast_error(ds, layers, heads, monkeypatch, False)
    accumulate = _plan_fast_error(ds, layers, heads, monkeypatch, True)
    assert 0.5e-4 < fast < 6e-4
    assert accumulate > 3 * fast
    bound = checks.logits_tol("xla", "initial")
    assert bound == 4e-3
    assert accumulate < bound       # the finding: the bound does not catch it
