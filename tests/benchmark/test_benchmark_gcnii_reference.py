"""The plain GCNII reference (`benchmark/references/gcnii.py`) held to a
second evaluation of the paper's equations: NumPy, float64, one Python
loop over the edges, at a toy size; its training mode given keep masks;
its parameter order; and the control: an aggregate whose result is rounded
to bf16 (the least a bf16 accumulate does) fails the bound
`gcnii-reddit.json` brings, where the configuration's own `fast` rounding
passes it."""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, graphgen
from benchmark import manifest as mf
from benchmark.references import gcnii as ref

LAYERS = [10, 6, 6, 6, 4]       # three GCNII layers of width 6
REHEARSAL = os.path.join(mf.ROOT, "benchmark", "rehearsal")


def _toy(seed=3):
    recipe = dict(graphgen.load_recipe(
        os.path.join(REHEARSAL, "traffic", "tiny-skewed.json")), nodes=120,
        splits={"train": 60, "val": 20, "test": 20})
    return graphgen.generate(recipe, LAYERS[0], LAYERS[-1], seed)


def _params(seed=0):
    rng = np.random.default_rng(seed)
    width, depth = LAYERS[1], len(LAYERS) - 2
    dims = [(LAYERS[0], width)] + [(width, width)] * depth \
        + [(width, LAYERS[-1])]
    params = {f"linear_{i}": rng.standard_normal((a, b)).astype(np.float32)
              / math.sqrt(a) for i, (a, b) in enumerate(dims)}
    params["linear_0_bias"] = 0.2 * rng.standard_normal(
        LAYERS[1]).astype(np.float32)
    params[f"linear_{len(dims) - 1}_bias"] = 0.2 * rng.standard_normal(
        LAYERS[-1]).astype(np.float32)
    return params


def _by_hand(params, ds, keep=None, rate=0.0):
    """Equation 5 edge by edge in float64."""
    g = ds.graph
    n = g.num_nodes
    row_ptr, col = np.asarray(g.row_ptr), np.asarray(g.col_idx)
    deg = np.diff(row_ptr).astype(np.float64)
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    depth = len(LAYERS) - 2

    def drop(t, i):
        if keep is None:
            return t
        return np.where(np.asarray(keep[i]), t / (1.0 - rate), 0.0)

    x = drop(np.asarray(ds.features, np.float64), 0)
    h = h0 = np.maximum(x @ p["linear_0"] + p["linear_0_bias"], 0.0)
    for layer in range(1, depth + 1):
        xin = drop(h, layer)
        px = np.zeros_like(h)
        for v in range(n):
            for e in range(row_ptr[v], row_ptr[v + 1]):
                u = col[e]
                px[v] += xin[u] / math.sqrt(deg[u] * deg[v])
        s = 0.9 * px + 0.1 * h0
        beta = math.log(0.4 / layer + 1.0)
        h = np.maximum((1.0 - beta) * s + beta * (s @ p[f"linear_{layer}"]),
                       0.0)
    last = depth + 1
    return drop(h, last) @ p[f"linear_{last}"] + p[f"linear_{last}_bias"]


def test_the_reference_agrees_with_the_equations_edge_by_edge():
    ds, params = _toy(), _params()
    want = _by_hand(params, ds)
    got = ref.reference_logits(params, ds, LAYERS, edge_block=256)
    assert got.shape == want.shape == (120, 4)
    assert checks.rel_fro(got, want) < 1e-6
    # one block or many: the same sum
    whole = ref.reference_logits(params, ds, LAYERS, edge_block=1 << 14)
    assert checks.rel_fro(whole, got) < 1e-6


def test_training_mode_applies_the_masks_it_is_given():
    ds, params = _toy(), _params()
    rng = np.random.default_rng(5)
    widths = LAYERS[:-1] + [LAYERS[-2]]     # X, H0 .. H2, H3
    keep = [rng.random((120, w)) < 0.5 for w in widths]
    want = _by_hand(params, ds, keep, 0.5)
    src, dst, deg = ref.edge_arrays(ds.graph, 256)
    got = np.asarray(ref.logits(
        ref.ordered_weights(params), jnp.asarray(ds.features), src, dst, deg,
        edge_block=256, keep=[jnp.asarray(k) for k in keep], rate=0.5))
    assert checks.rel_fro(got, want) < 1e-6
    assert checks.rel_fro(got, _by_hand(params, ds)) > 0.1   # masks matter


def test_parameter_order_and_what_the_reference_refuses():
    params = _params()
    assert ref.ordered_names(params) == [
        "linear_0", "linear_0_bias", "linear_1", "linear_2", "linear_3",
        "linear_4", "linear_4_bias"]
    with pytest.raises(ValueError, match="knows no parameter 'gat_0_w'"):
        ref.ordered_names({**params, "gat_0_w": 1})
    missing = {k: v for k, v in params.items() if k != "linear_0_bias"}
    with pytest.raises(ValueError, match="GCNII's parameters are"):
        ref.ordered_names(missing)
    assert (ref.ALPHA, ref.LAMDA) == (0.1, 0.4)
    assert [ref.beta(l) for l in (1, 2, 16)] == [
        math.log(1.4), math.log(1.2), math.log(1.025)]


def test_loss_and_gradients_come_from_the_same_logits():
    ds, params = _toy(), _params()
    val, grads = ref.loss_and_grads(params, ds, LAYERS, edge_block=256)
    z = _by_hand(params, ds)
    logp = z - np.log(np.exp(z - z.max(1, keepdims=True)).sum(1,
                      keepdims=True)) - z.max(1, keepdims=True)
    train = np.asarray(ds.mask) == ref.MASK_TRAIN
    want = -logp[np.arange(120), np.asarray(ds.label_ids)][train].sum()
    assert abs(float(val) - want) < 1e-4 * abs(want)
    assert set(grads) == set(params)
    assert all(float(jnp.linalg.norm(g)) > 0 for g in grads.values())


@pytest.mark.parametrize("lower", [False, True],
                         ids=["fast", "result_rounded_to_bf16"])
def test_one_precision_lower_fails_the_configurations_bound(lower,
                                                            monkeypatch):
    """At the cell's degree (50 drawn edges a node) and sixteen layers deep: the
    configuration's `fast` (aggregation inputs rounded to bf16 once, sums
    float32) computed in the reference passes the bound the file brings
    for initial parameters; with each aggregate's result rounded to bf16
    as well it does not."""
    conf = mf.load(os.path.join(mf.ROOT, "benchmark", "configs",
                                "gcnii-reddit.json"))
    layers = [24] + [16] * 16 + [5]
    recipe = dict(graphgen.load_recipe(
        os.path.join(REHEARSAL, "traffic", "tiny-skewed.json")),
        avg_degree=50)
    ds = graphgen.generate(recipe, layers[0], layers[-1], 11)
    from roc_tpu.models import build_model
    params = jax.device_get(build_model("gcnii", layers, 0.5).init_params(
        jax.random.PRNGKey(11)))
    want = ref.reference_logits(params, ds, layers, edge_block=1 << 14)
    plain = ref.aggregate

    def bf16(x):    # an explicit rounding: a compiler may elide convert pairs
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def rounded(x, src, dst, edge_block):
        out = plain(bf16(x), src, dst, edge_block)
        return bf16(out) if lower else out

    monkeypatch.setattr(ref, "aggregate", rounded)
    jax.clear_caches()
    got = ref.reference_logits(params, ds, layers, edge_block=1 << 14)
    jax.clear_caches()
    err = checks.rel_fro(got, want)
    bound = conf["logits_tol"]["initial"]
    assert (err > bound) == lower, (err, bound)
