"""Attention dropout (Velickovic et al. section 3.3: dropout on the
normalised attention coefficients, per edge and head, in training) on every
attention path that accepts a gat model; that the plan path's hand-derived
backward redraws the forward's mask; that evaluation never sees it; and
that the plan path's per-edge arrays keep edges on the lane axis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu import ops
from roc_tpu.graph import datasets
from roc_tpu.models import build_gat
from roc_tpu.models.model import Model
from roc_tpu.ops import edge as edge_mod
from roc_tpu.parallel.spmd import SpmdTrainer
from roc_tpu.train.config import Config
from roc_tpu.train.driver import Trainer

RATE = 0.6


def _graph(n=150, degree=4.0, seed=3):
    ds = datasets.synthetic("t", n, degree, 8, 4, n_train=30, n_val=30,
                            n_test=30, seed=seed)
    return ds, ds.graph


def _rel_fro(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def _attention_inputs(g, K, F, seed=7):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(g.num_nodes, K, F)).astype(np.float32))
    a_src = jnp.asarray(rng.normal(size=(K, F)).astype(np.float32))
    a_dst = jnp.asarray(rng.normal(size=(K, F)).astype(np.float32))
    return h, a_src, a_dst


@pytest.mark.parametrize("heads", [1, 8])
def test_plan_backward_redraws_the_forwards_mask(heads):
    """gat_attend_plan saves no mask: its backward draws it again from the
    key.  Value and every gradient equal autodiff of the unfused dense
    gat_attend given the same (key, rate)."""
    _, g = _graph()
    N, F = g.num_nodes, 4
    h, a_src, a_dst = _attention_inputs(g, heads, F)
    es, ed = jnp.asarray(g.col_idx), jnp.asarray(g.dst_idx)
    plans = ops.build_gat_plans(g.col_idx, g.dst_idx, N, N)
    drop = (jax.random.PRNGKey(5), RATE)

    def dense(hh, tt, s, d):
        return jnp.sum(ops.gat_attend(hh, tt, es, ed, N, s, d, 0.2,
                                      drop) ** 2)

    def plan(hh, tt, s, d):
        return jnp.sum(ops.gat_attend_plan(hh, tt, s, d, plans, (es, ed),
                                           0.2, "highest", drop) ** 2)

    want = jax.value_and_grad(dense, argnums=(0, 1, 2, 3))(h, h, a_src, a_dst)
    got = jax.jit(jax.value_and_grad(plan, argnums=(0, 1, 2, 3)))(
        h, h, a_src, a_dst)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        assert _rel_fro(a, b) < 1e-5    # float32 sums in another order
    # the mask matters, and another key draws another one
    unmasked = jnp.sum(ops.gat_attend(h, h, es, ed, N, a_src, a_dst,
                                      0.2) ** 2)
    assert abs(float(unmasked) - float(want[0])) > 1e-3 * float(unmasked)
    other = jnp.sum(ops.gat_attend_plan(
        h, h, a_src, a_dst, plans, (es, ed), 0.2, "highest",
        (jax.random.PRNGKey(6), RATE)) ** 2)
    assert abs(float(other) - float(got[0])) > 1e-4 * float(other)


def test_chunked_scan_takes_the_same_mask(monkeypatch):
    _, g = _graph()
    N, K, F = g.num_nodes, 8, 4
    h, a_src, a_dst = _attention_inputs(g, K, F)
    args = (h, h, jnp.asarray(g.col_idx), jnp.asarray(g.dst_idx), N, a_src,
            a_dst, 0.2, (jax.random.PRNGKey(5), RATE))

    def loss(hh):
        return jnp.sum(ops.gat_attend(hh, *args[1:]) ** 2)

    dense, gd = jax.value_and_grad(loss)(h)
    monkeypatch.setattr(edge_mod, "_GAT_CHUNK_THRESHOLD_ELEMS", 1)
    monkeypatch.setattr(edge_mod, "_GAT_CHUNK_TARGET_ELEMS", 16 * K * F)
    monkeypatch.setattr(edge_mod, "_GAT_CHUNK_MIN", 16)
    chunked, gc = jax.value_and_grad(loss)(h)
    np.testing.assert_allclose(chunked, dense, rtol=1e-5)
    assert _rel_fro(gc, gd) < 1e-5


def test_keep_rate_is_one_minus_p():
    """Bernoulli(1 - p) per edge and head: 160,000 draws, 3 sigma."""
    K, E = 8, 20000
    keep = np.asarray(edge_mod.attention_keep(jax.random.PRNGKey(1), RATE,
                                              K, E))
    assert keep.shape == (K, E) and keep.dtype == np.bool_
    sigma = np.sqrt(RATE * (1 - RATE) / keep.size)
    assert abs(keep.mean() - (1 - RATE)) < 3 * sigma
    # per head too (20,000 draws each), and heads are not copies
    per_head = keep.mean(axis=1)
    assert np.all(np.abs(per_head - (1 - RATE))
                  < 4 * np.sqrt(RATE * (1 - RATE) / E))
    assert (keep[0] != keep[1]).mean() > 0.3


@pytest.mark.parametrize("score", ["gat", "tconv"])
def test_evaluation_is_untouched_by_the_rate(score):
    """Additive scores (gat) and dot-product scores (tconv) alike."""
    from roc_tpu.models import build_model
    ds, g = _graph()
    layers = [ds.in_dim, 4, ds.num_classes]
    cfg = dict(layers=layers, eval_every=10**9, model=score, heads=2,
               aggregate_backend="matmul", weight_decay=0.0)
    dropped = Trainer(Config(dropout_rate=RATE, **cfg), ds,
                      build_model(score, layers, RATE, heads=2))
    plain = Trainer(Config(dropout_rate=0.0, **cfg), ds,
                    build_model(score, layers, 0.0, heads=2))
    assert dropped.gdata.gat_plans is not None
    np.testing.assert_array_equal(np.asarray(dropped.predict_logits()),
                                  np.asarray(plain.predict_logits()))
    # training is not: same seed, same parameters, another loss
    assert abs(float(dropped.run_epoch()) - float(plain.run_epoch())) > 1e-3


def test_build_gat_drops_inputs_and_coefficients_at_one_rate():
    m = build_gat([8, 4, 3], RATE, heads=2)
    gats = [op for op in m.ops if op.kind == "gat"]
    drops = [op for op in m.ops if op.kind == "dropout"]
    assert [op.attrs["attn_drop"] for op in gats] == [RATE, RATE]
    assert [op.attrs["rate"] for op in drops] == [RATE, RATE]
    # four masks a step, four slots, no two ops share one
    slots = [op.attrs["slot"] for op in m.ops if "slot" in op.attrs]
    assert sorted(slots) == [0, 1, 2, 3] and m.num_dropout == 4
    # rate 0 takes no slot: older programs keep their keys
    m0 = build_gat([8, 4, 3], 0.0, heads=2)
    assert all("slot" not in op.attrs for op in m0.ops if op.kind == "gat")


SHARDED = {
    "halo-plan": dict(halo=True, aggregate_backend="matmul"),
    "allgather-xla": dict(halo=False, aggregate_backend="xla"),
    "ring": dict(exchange="ring"),
    "edge-shard-plan": dict(edge_shard="on", aggregate_backend="matmul"),
    "edge-shard-xla": dict(edge_shard="on", aggregate_backend="xla"),
    "overcommit": dict(num_parts=16, halo=True),
}


@pytest.mark.parametrize("mode", sorted(SHARDED))
def test_sharded_attention_paths_take_the_mask(mode, monkeypatch):
    """Every sharded attention path draws the coefficients' mask in a
    training step (through ops.edge.attention_keep, per shard, at the
    model's rate) and none in evaluation."""
    drawn = []
    real = edge_mod.attention_keep

    def recording(key, rate, heads, num_edges):
        drawn.append((rate, heads, num_edges))
        return real(key, rate, heads, num_edges)

    monkeypatch.setattr(edge_mod, "attention_keep", recording)
    ds, _ = _graph(n=220)
    layers = [ds.in_dim, 4, ds.num_classes]
    kw = dict(layers=layers, dropout_rate=RATE, eval_every=10**9,
              num_parts=4, model="gat", heads=2, weight_decay=0.0)
    kw.update(SHARDED[mode])
    tr = SpmdTrainer(Config(**kw), ds, build_gat(layers, RATE, heads=2))
    tr.evaluate()
    assert drawn == []
    loss = float(tr.run_epoch())
    assert np.isfinite(loss)
    assert {(r, h) for r, h, _ in drawn} == {(RATE, 2), (RATE, 1)}
    info = tr.attention_info()
    assert info["backend"] == ("plan" if mode.endswith("plan") else "xla")


def test_streamed_attention_takes_the_mask(monkeypatch):
    drawn = []
    real = edge_mod.attention_keep
    monkeypatch.setattr(
        edge_mod, "attention_keep",
        lambda key, rate, heads, n: drawn.append(rate) or real(
            key, rate, heads, n))
    from roc_tpu.models import build_model
    from roc_tpu.train.driver import make_trainer
    ds = datasets.get("roc-audit", seed=1)
    cfg = Config(layers=[ds.in_dim, 16, ds.num_classes], dropout_rate=RATE,
                 eval_every=10**9, num_parts=4, model="gat", heads=2,
                 stream=True)
    tr = make_trainer(cfg, ds, build_model("gat", cfg.layers, RATE, "",
                                           heads=2))
    assert np.isfinite(float(tr.run_epoch()))
    assert drawn and set(drawn) == {RATE}


# -- layout ----------------------------------------------------------------

def _sub_jaxprs(value):
    from jax.extend import core as jcore
    if isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def _all_shapes(jaxpr, out):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", None)
            if shape is not None:
                out.append((eqn.primitive.name, tuple(shape)))
        for p in eqn.params.values():
            for sub in _sub_jaxprs(p):
                _all_shapes(sub, out)
    return out


@pytest.mark.parametrize("dropout", [0.0, RATE])
def test_plan_path_keeps_edges_on_the_lane_axis(dropout, monkeypatch):
    """Every intermediate of the plan path's forward and backward that is
    as long as the edge list (or a scan step's slots) has the heads on the
    second-to-last axis and the edges LAST: [K, E], never [E, K].  On the
    TPU an [E, 8] float32 array is tiled to 128 lanes a row, 16 x its size
    (12 GB apiece at the Reddit shape); no CPU test used to see that."""
    monkeypatch.setattr(edge_mod, "_LANE_GATHER_CHUNK", 4096)
    ds, g = _graph(n=400, degree=30.0)
    # 4 heads of 16: no other axis of the path is 4 long (the plans'
    # window rows VB are 8, as the cell's heads are)
    N, K, F, E = g.num_nodes, 4, 16, g.num_edges
    assert E > 4 * 4096 and N * K < 4096
    h, a_src, a_dst = _attention_inputs(g, K, F)
    es, ed = jnp.asarray(g.col_idx), jnp.asarray(g.dst_idx)
    plans = ops.build_gat_plans(g.col_idx, g.dst_idx, N, N)
    drop = (jax.random.PRNGKey(5), dropout) if dropout else None

    def loss(hh, s, d):
        return jnp.sum(ops.gat_attend_plan(hh, hh, s, d, plans, (es, ed),
                                           0.2, "default", drop) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(h, a_src, a_dst)
    shapes = _all_shapes(jaxpr.jaxpr, [])
    long_ = 4096                    # a lane-gather chunk, a scan step's slots
    edges_last = [s for _, s in shapes if len(s) == 2 and s[0] == K
                  and s[1] >= E]
    assert len(edges_last) >= 8, "the [K, E] intermediates are gone?"
    bad = [(p, s) for p, s in shapes
           if len(s) >= 2 and s[-1] == K and int(np.prod(s[:-1])) >= long_]
    assert not bad, f"heads on the lane axis of edge-sized arrays: {bad[:5]}"
    # feature rows are [slots, K*F]; nothing edge-sized is [.., K, F] either
    bad3 = [(p, s) for p, s in shapes
            if len(s) >= 3 and s[-2:] == (K, F)
            and int(np.prod(s[:-2])) >= long_]
    assert not bad3, bad3[:5]


def test_hand_built_model_can_drop_coefficients_only():
    """`Model.gat(attn_drop=)` is its own switch; input dropout is not
    needed for it, and `keep_masks` names exactly the masks a step draws."""
    m = Model(in_dim=8)
    t = m.gat(m.input, 4, heads=2, attn_drop=0.25)
    m.end_layer()
    m.softmax_cross_entropy(m.gat(t, 3, heads=1))
    masks = m.keep_masks(jax.random.PRNGKey(0), 10, 50)
    assert {i: v.shape for i, v in masks.items()} == {0: (2, 50)}
