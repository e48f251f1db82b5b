"""Dynamic attention over in-edges (GATv2's op, ops.edge gatv2_attend /
gatv2_attend_plan; models/gatv2.py): the plan road against the xla road and
against a float64 NumPy formula of the equations (values, and gradients by
central differences), the rows a softmax can trip on, dropout, the slope at
zero, the [K, E] layout and the scans, what the trainer says about the op,
what the memory estimator prices, and every road that does not carry it
refusing it by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu import obs
from roc_tpu.graph import datasets
from roc_tpu.memory import estimator
from roc_tpu.models import build_gatv2, build_model
from roc_tpu.models.model import attention_heads, attention_score
from roc_tpu.ops import edge as em
from roc_tpu.ops.pallas.segment_sum import EB
from roc_tpu.train.config import Config, parse_args
from roc_tpu.train.driver import Trainer, make_trainer

from test_gat_plans import (_edges, _eqns, _scans_and_their_gathers,
                            _small_steps)

SLOPE = 0.2


# -- the equations, float64 NumPy --------------------------------------------

def _dense_gatv2(xl, xr, a, src, dst, rows, keep=None, rate=0.0):
    """Equation 7 and the attention, per edge in float64: s = a .
    LeakyReLU(xr[dst] + xl[src]) per head, a softmax over each row's
    in-edges (a multigraph counts each copy), coefficients times ``keep /
    (1 - rate)`` ([K, E] bool) where given, out = the rows' sums of
    coefficient x xl[src]; a row with no in-edge gives zeros."""
    xl, xr, a = (np.asarray(t, np.float64) for t in (xl, xr, a))
    p = xr[dst] + xl[src]                                   # [E, K, F]
    s = np.sum(a * np.where(p >= 0, p, SLOPE * p), axis=-1)  # [E, K]
    m = np.full((rows, s.shape[1]), -np.inf)
    np.maximum.at(m, dst, s)
    e = np.exp(s - m[dst])
    z = np.zeros((rows, s.shape[1]))
    np.add.at(z, dst, e)
    alpha = e / z[dst]
    if keep is not None:
        alpha = alpha * np.asarray(keep, np.float64).T / (1.0 - rate)
    out = np.zeros((rows,) + xl.shape[1:])
    np.add.at(out, dst, alpha[:, :, None] * xl[src])
    return out


def _tables(rows, heads, f, seed):
    rng = np.random.default_rng(seed)
    xl, xr = (jnp.asarray(rng.standard_normal((rows, heads, f)), jnp.float32)
              for _ in range(2))
    a = jnp.asarray(rng.standard_normal((heads, f)), jnp.float32)
    return xl, xr, a


def _road(road, src, dst, rows):
    """The op on one road as f(xl, xr, a, drop)."""
    if road == "plan":
        plans = em.build_gat_plans(src, dst, rows, rows)
        return lambda xl, xr, a, drop=None: em.gatv2_attend_plan(
            xl, xr, a, plans, dst.size, drop)
    sj, dj = jnp.asarray(src), jnp.asarray(dst)
    return lambda xl, xr, a, drop=None: em.gatv2_attend(
        xl, xr, a, sj, dj, rows, drop)


@pytest.mark.parametrize("heads", [1, 2, 4])
@pytest.mark.parametrize("kind", ["regular", "hub"])
@pytest.mark.parametrize("road", ["xla", "plan"])
def test_attention_against_the_equations(road, kind, heads):
    """Values within 64 ulps of the output's scale (a hub of 3,000 in-edges
    included; rows without in-edges exact zeros)."""
    src, dst, rows = _edges(kind, seed=5)
    xl, xr, a = _tables(rows, heads, 8, heads)
    got = np.asarray(_road(road, src, dst, rows)(xl, xr, a))
    want = _dense_gatv2(xl, xr, a, src, dst, rows)
    assert not got[np.setdiff1d(np.arange(rows), np.unique(dst))].any()
    scale = np.abs(want).max() * np.finfo(np.float32).eps
    assert np.abs(got - want).max() <= 64 * scale


@pytest.mark.parametrize("dropout", [0.0, 0.6])
@pytest.mark.parametrize("heads", [1, 4])
@pytest.mark.parametrize("kind", ["regular", "hub"])
def test_the_hand_derived_backward_against_autodiff(kind, heads, dropout):
    """dxl, dxr and da of the plan road (one scan over each plan, the slope
    recomputed from regathered rows, the mask redrawn from the key) against
    jax.grad of the xla road given the same key."""
    src, dst, rows = _edges(kind, seed=6)
    xl, xr, a = _tables(rows, heads, 8, 10 + heads)
    drop = (jax.random.PRNGKey(3), dropout) if dropout else None

    def grads(road):
        fn = _road(road, src, dst, rows)
        return jax.grad(lambda *t: jnp.sum(jnp.sin(fn(*t, drop))),
                        argnums=(0, 1, 2))(xl, xr, a)

    for name, g, w in zip(("dxl", "dxr", "da"), grads("plan"), grads("xla")):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert np.linalg.norm(w) > 0, name
        assert np.linalg.norm(g - w) / np.linalg.norm(w) <= 2e-5, name


@pytest.mark.parametrize("which", [0, 1, 2])
def test_gradients_against_central_differences_of_the_equations(which):
    """<grad, v> of the plan road for random directions v of xl, xr or a,
    against the float64 formula's central difference: the backward is the
    derivative of the equations, not only of the xla road."""
    src, dst, rows = _edges("hub", seed=8)
    tables = _tables(rows, 2, 8, 4)
    fn = _road("plan", src, dst, rows)
    rng = np.random.default_rng(which)
    weight = rng.standard_normal((rows, 2, 8))
    grad = np.asarray(jax.grad(
        lambda *t: jnp.sum(fn(*t) * jnp.asarray(weight, jnp.float32)),
        argnums=which)(*tables), np.float64)

    def loss(t):
        args = [np.asarray(x, np.float64) for x in tables]
        args[which] = t
        return np.sum(_dense_gatv2(*args, src, dst, rows) * weight)

    # a step far below the distance of most pre-activations from the
    # LeakyReLU's kink (1e-4 crosses a few of the 150,000 and reads 2e-3)
    x0, h = np.asarray(tables[which], np.float64), 1e-6
    for _ in range(2):
        v = rng.standard_normal(x0.shape)
        fd = (loss(x0 + h * v) - loss(x0 - h * v)) / (2 * h)
        assert abs(np.sum(grad * v) - fd) <= 1e-5 * max(abs(fd), 1.0)


def test_one_in_edge_a_lone_self_edge_and_a_row_with_none():
    """A destination with ONE in-edge copies that source's xl row (its
    coefficient is 1 whatever the score, so its score has no gradient: the
    row's dxr and its a share are zero); a row with only its self-edge
    copies itself; a row with none (a padded row) reads zeros and hands
    finite zeros back (z = 0 meets _Z_GUARD, not 0 / 0)."""
    rows = 40
    src = np.array([5, 7, 9, 9, 6, 3], np.int64)
    dst = np.array([2, 4, 4, 4, 6, 11], np.int64)   # 2, 6 (self), 11: one
    xl, xr, a = _tables(rows, 2, 8, 1)
    xr = xr * 30.0                                  # scores far from 0
    plans = em.build_gat_plans(src, dst, rows, rows)

    def out(*t):
        return em.gatv2_attend_plan(*t, plans, dst.size)

    got = np.asarray(out(xl, xr, a))
    for i, j in ((2, 5), (6, 6), (11, 3)):
        np.testing.assert_array_equal(got[i], np.asarray(xl)[j])
    assert not got[[0, 1, 3, 39]].any()
    dxl, dxr, da = (np.asarray(g) for g in jax.grad(
        lambda *t: jnp.sum(out(*t) ** 2), argnums=(0, 1, 2))(xl, xr, a))
    for g in (dxl, dxr, da):
        assert np.isfinite(g).all()
    assert not dxr[[2, 6, 11]].any() and dxr[4].any()
    assert not dxl[[0, 1, 39]].any() and dxl[[5, 6, 3]].any()


@pytest.mark.parametrize("road", ["xla", "plan"])
def test_dropout_drops_the_coefficients_the_key_draws(road):
    """With (key, rate) the output is the equations' given the mask
    ops.edge.attention_keep draws from that key: the same coefficients on
    both roads, scaled by 1 / (1 - p), not renormalised."""
    src, dst, rows = _edges("hub", seed=2)
    xl, xr, a = _tables(rows, 4, 8, 3)
    key, rate = jax.random.PRNGKey(9), 0.6
    got = np.asarray(_road(road, src, dst, rows)(xl, xr, a, (key, rate)))
    keep = np.asarray(em.attention_keep(key, rate, 4, dst.size))
    assert 0.3 < keep.mean() < 0.5
    want = _dense_gatv2(xl, xr, a, src, dst, rows, keep, rate)
    scale = np.abs(want).max() * np.finfo(np.float32).eps
    assert np.abs(got - want).max() <= 64 * scale
    plain = _dense_gatv2(xl, xr, a, src, dst, rows)
    assert np.abs(plain - want).max() > 0.1


def test_the_slope_at_zero_is_one_on_every_road():
    """LeakyReLU'(0): xr = -xl makes every self-edge's pre-activation an
    exact 0 at every channel.  jax.nn.leaky_relu's derivative there is 1,
    the reference's ``where(p >= 0, p, 0.2 p)`` has the same, and the plan
    road's recomputed slope (``where(p >= 0, 1, slope)``) agrees: its
    gradients match autodiff of the xla road.  Were it 0.2 at 0, dxr would
    move by far more than rounding."""
    assert float(jax.grad(lambda p: jax.nn.leaky_relu(p, SLOPE))(0.0)) == 1.0
    assert float(jax.grad(
        lambda p: jnp.where(p >= 0, p, SLOPE * p))(0.0)) == 1.0
    rows = 300
    rng = np.random.default_rng(1)
    src = np.concatenate([rng.integers(0, rows, 900), np.arange(rows)])
    dst = np.concatenate([rng.integers(0, rows, 900), np.arange(rows)])
    order = np.lexsort((src, dst))
    src, dst = src[order], dst[order]
    xl, _, a = _tables(rows, 2, 8, 7)
    xr = -xl
    assert not np.asarray(xr[dst[src == dst]] + xl[src[src == dst]]).any()
    got, want = (jax.grad(lambda *t, f=_road(r, src, dst, rows): jnp.sum(
        jnp.sin(f(*t))), argnums=(0, 1, 2))(xl, xr, a)
        for r in ("plan", "xla"))
    for g, w in zip(got, want):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert np.linalg.norm(g - w) <= 2e-5 * np.linalg.norm(w)


# -- layout and scans --------------------------------------------------------

@pytest.mark.parametrize("dropout", [0.0, 0.6])
def test_no_edge_sized_row_array_and_four_row_scans(dropout, monkeypatch):
    """jax.grad of gatv2_attend_plan: no scatter (no gather transposed), no
    gather's indices as long as the edge list, and no edge-sized array with
    the heads or a feature row on its lane axis: the [E, K F]
    pre-activation and slope exist a scan step at a time.  FOUR scans gather
    node rows (score, u, the dst-keyed backward over xl rows; the src-keyed
    one over [xr | du] rows), and the src scan's column gather reads ONE
    stacked [2K, E] array, the only scan that reads per-edge values by
    column."""
    _small_steps(monkeypatch)
    src, dst, rows = _edges("hub", seed=6)
    K, F, E = 4, 16, dst.size
    step_slots = 16 * EB
    assert E > 2 * step_slots > 2 * rows
    xl, xr, a = _tables(rows, K, F, 0)
    plans = em.build_gat_plans(src, dst, rows, rows)
    drop = (jax.random.PRNGKey(5), dropout) if dropout else None

    def loss(*t):
        return jnp.sum(em.gatv2_attend_plan(*t, plans, E, drop) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
        xl, xr, a).jaxpr
    shapes, gathers = [], []
    for eqn in _eqns(jaxpr):
        assert not eqn.primitive.name.startswith("scatter"), str(eqn)[:200]
        if eqn.primitive.name == "gather":
            gathers.append(int(np.prod(eqn.invars[1].aval.shape[:-1])))
        shapes += [tuple(o.aval.shape) for o in eqn.outvars
                   if getattr(o.aval, "shape", None) is not None]
    assert gathers and max(gathers) <= step_slots
    assert sum(1 for s in shapes if s == (K, E)) >= 4
    assert sum(1 for s in shapes if s == (2 * K, E)) >= 2
    assert not [s for s in shapes if len(s) >= 2 and s[-1] in (K, K * F)
                and int(np.prod(s[:-1])) > step_slots]
    scans = _scans_and_their_gathers(jaxpr)
    narrow = [g for g in scans if (rows, K * F) in g]
    wide = [g for g in scans if (rows, 2 * K * F) in g]
    assert (len(narrow), len(wide)) == (3, 1)
    by_column = [g for g in scans if (K, E) in g or (2 * K, E) in g]
    assert by_column == wide and by_column[0].count((2 * K, E)) == 1


# -- the builder and the op IR -----------------------------------------------

def test_build_gatv2_reads_layers_as_build_gat_does():
    model = build_gatv2([602, 8, 41], 0.6, heads=8)
    assert [op.kind for op in model.ops] == [
        "dropout", "gat", "activation", "dropout", "gat"]
    gat = [op for op in model.ops if op.kind == "gat"]
    assert {attention_score(op) for op in model.ops} == {None, "dynamic"}
    assert [(op.attrs["heads"], op.attrs["head_dim"], attention_heads(op),
             op.attrs["slope"], op.attrs["attn_drop"]) for op in gat] == [
        (8, 8, 8, 0.2, 0.6), (1, 41, 1, 0.2, 0.6)]
    # inputs and coefficients dropped at one rate, masks of their own
    assert sorted(op.attrs["slot"] for op in model.ops
                  if op.kind in ("dropout", "gat")) == list(range(4))
    params = model.init_params(jax.random.PRNGKey(0))
    assert {k: v.shape for k, v in params.items()} == {
        "gatv2_0_wl": (602, 64), "gatv2_0_wr": (602, 64),
        "gatv2_0_a": (8, 8), "gatv2_1_wl": (64, 41),
        "gatv2_1_wr": (64, 41), "gatv2_1_a": (1, 41)}
    assert not np.array_equal(params["gatv2_0_wl"], params["gatv2_0_wr"])
    assert sum(v.size for v in params.values()) == 2 * 602 * 64 + 64 \
        + 2 * 64 * 41 + 41
    assert model.logits.dim == 41
    masks = model.keep_masks(jax.random.PRNGKey(1), 10, 50)
    assert sorted(v.shape for v in masks.values()) == sorted(
        [(10, 602), (10, 64), (8, 50), (1, 50)])
    assert parse_args(["-model", "gatv2", "-layers", "8-4-3"]).model \
        == "gatv2"
    assert [op.kind for op in build_model(
        "gatv2", [8, 4, 3], 0.0, heads=2).ops][:2] == ["dropout", "gat"]


# -- what the trainer says ---------------------------------------------------

def _dataset(n=200):
    return datasets.synthetic("t", n, 4.0, 8, 4, n_train=30, n_val=30,
                              n_test=30, seed=3)


def _config(ds, **kw):
    base = dict(layers=[ds.in_dim, 4, ds.num_classes], num_epochs=1,
                eval_every=10**9, dropout_rate=0.6, model="gatv2", heads=2,
                aggregate_backend="matmul", weight_decay=0.0)
    base.update(kw)
    return Config(**base)


def _model(cfg):
    return build_model("gatv2", cfg.layers, cfg.dropout_rate,
                       heads=cfg.heads)


def test_attention_record_gauges_and_start_up_line(tmp_path, capsys):
    ds = _dataset()
    cfg = _config(ds, obs=True, obs_dir=str(tmp_path / "obs"))
    tr = Trainer(cfg, ds, _model(cfg))
    info = tr.attention_info()
    e = ds.graph.num_edges
    assert list(info) == ["backend", "plan_pad_ratio", "score", "score_bytes",
                          "residual_bytes", "row_scans", "src_scans",
                          "short_scans"]
    assert (info["backend"], info["score"]) == ("plan", "dynamic")
    # one [K, E] float32 array; e of both ops (2 heads, then 1), the
    # estimator's count; four scans an op gather rows, one of them over the
    # src-keyed plan
    assert info["score_bytes"] == 2 * e * 4
    assert info["residual_bytes"] == (2 + 1) * e * 4 == sum(
        estimator.gat_edge_residual_bytes(op, e) for op in tr.model.ops)
    assert (info["row_scans"], info["src_scans"], info["short_scans"]) \
        == (8, 2, 0)
    line = next(ln for ln in capsys.readouterr().err.splitlines()
                if ln.startswith("# attention:"))
    assert line == (
        "# attention: backend=plan"
        f" gatv2_plan_pad_ratio={info['plan_pad_ratio']:.4f}"
        f" gatv2_score=dynamic gatv2_score_bytes={info['score_bytes']}"
        f" gatv2_residual_bytes={info['residual_bytes']}"
        " gatv2_row_scans=8 gatv2_src_scans=2 gatv2_short_scans=0")
    tr.train(print_fn=lambda *a, **k: None)
    recs = obs.load_jsonl(str(tmp_path / "obs" / "metrics.jsonl"))
    att, = [r for r in recs if r["type"] == "attention"]
    assert att["backend"] == "plan" and att["gatv2_score"] == "dynamic"
    assert att["gatv2_residual_bytes"] == info["residual_bytes"]
    prom = (tmp_path / "obs" / "metrics.prom").read_text()
    for name in ("residual_bytes", "row_scans", "src_scans"):
        assert f"roc_gatv2_{name} " in prom
    assert 'roc_gatv2_score{score="dynamic"} 1' in prom


def test_the_xla_road_keeps_no_plan_residual(capsys):
    ds = _dataset()
    cfg = _config(ds, aggregate_backend="xla")
    tr = Trainer(cfg, ds, _model(cfg))
    info = tr.attention_info()
    assert (info["backend"], info["residual_bytes"]) == ("xla", 0)
    assert (info["row_scans"], info["src_scans"], info["short_scans"]) \
        == (0, 0, 0)
    assert tr.gdata.gat_plans is None
    assert "# attention: backend=xla " in capsys.readouterr().err


def test_plan_and_xla_roads_train_to_the_same_losses():
    """Three epochs with both dropouts on: the same keys drop the same
    inputs and coefficients on both roads, so the losses agree to float32
    reassociation, epoch after epoch, and fall."""
    ds = _dataset(300)
    losses = {}
    for backend in ("xla", "matmul"):
        cfg = _config(ds, aggregate_backend=backend, num_epochs=3,
                      aggregate_precision="exact", learning_rate=0.01)
        tr = Trainer(cfg, ds, _model(cfg))
        losses[backend] = [float(tr.run_epoch()) for _ in range(3)]
    np.testing.assert_allclose(losses["xla"], losses["matmul"], rtol=2e-5)
    assert losses["xla"][-1] < losses["xla"][0]


# -- what the memory estimator prices ----------------------------------------

def _residuals(score, K, F, src, dst, rows):
    """(the custom VJP's residual leaves, the op) of one op of ``score``
    over a small graph, dropout on: what the rule keeps for its backward."""
    rng = np.random.default_rng(0)
    plans = em.build_gat_plans(src, dst, rows, rows)
    key, E = jax.random.PRNGKey(1), dst.size
    t = [jnp.asarray(rng.standard_normal((rows, K, F)), jnp.float32)
         for _ in range(3)]
    a = jnp.asarray(rng.standard_normal((K, F)), jnp.float32)
    from roc_tpu.models.model import Model
    m = Model(in_dim=4)
    if score == "additive":
        m.gat(m.input, F, heads=K, attn_drop=0.5)
        _, res = em._gat_plan_fwd(t[0], t[0], a, a, plans, (
            jnp.asarray(src), jnp.asarray(dst)), key, SLOPE, "highest", 0.5)
        res = res[:4] + res[6:]           # the plans and the edge ids apart
    elif score == "dot":
        m.tconv(m.input, F, heads=K, attn_drop=0.5)
        _, res = em._tconv_plan_fwd(*t, plans, key, E, 0.5)
        res = res[:3] + res[4:]
    else:
        m.gatv2(m.input, F, heads=K, attn_drop=0.5)
        _, res = em._gatv2_plan_fwd(t[0], t[1], a, plans, key, E, SLOPE,
                                    0.5)
        res = res[:3] + res[4:]
    return [r for r in jax.tree.leaves(res)
            if jnp.issubdtype(r.dtype, jnp.number)
            or r.dtype == jnp.bool_], m.ops[0]


@pytest.mark.parametrize("score", ["additive", "dot", "dynamic"])
def test_the_estimator_prices_what_each_rule_keeps(score):
    """gat_edge_residual_bytes is the bytes of the rule's [K, E] residuals
    (e, and the sign for the additive score alone), and
    attention_table_bytes of its node tables besides its output ([rows, K
    F]: none for additive, whose one table is its output's width; q, k, v;
    xl, xr), at a small size."""
    src, dst, rows = _edges("regular", seed=3)
    K, F, E = 4, 8, dst.size
    leaves, op = _residuals(score, K, F, src, dst, rows)
    assert attention_score(op) == score
    edge = sum(r.nbytes for r in leaves if r.shape == (K, E))
    assert estimator.gat_edge_residual_bytes(op, E) == edge == K * E * (
        5 if score == "additive" else 4)
    tables = [r for r in leaves if r.shape == (rows, K, F)]
    # the op's output is one of them, counted as the op's output
    assert estimator.attention_table_bytes(op, rows) == (
        len(tables) - 1) * rows * K * F * 4 * (score != "additive")


# -- roads that do not carry the op say so by name ---------------------------

ROADS = {
    "spmd-halo": (dict(num_parts=4), r"SpmdTrainer \(-exchange halo"),
    "spmd-edge-shard": (dict(num_parts=4, edge_shard="on"),
                        r"SpmdTrainer \(-edge-shard, -exchange halo"),
    "spmd-overcommit": (dict(num_parts=16), r"SpmdTrainer \(overcommit"),
    "stream": (dict(num_parts=2, stream=True),
               r"streamed executor \(-stream"),
}


@pytest.mark.parametrize("road", sorted(ROADS))
def test_a_road_without_the_op_refuses_it_by_name(road):
    import re
    ds = _dataset()
    kw, says = ROADS[road]
    cfg = _config(ds, **kw)
    with pytest.raises(ValueError, match="-model gatv2: the gatv2 op") as e:
        make_trainer(cfg, ds, _model(cfg))
    assert re.search(says, str(e.value)), str(e.value)
    assert "score 'dynamic'" in str(e.value)
    assert "one-chip Trainer" in str(e.value)


def test_the_serving_loader_refuses_it_by_name():
    from roc_tpu.serve.engine import ServeEngine
    ds = _dataset()
    cfg = _config(ds)
    with pytest.raises(ValueError, match=r"gatv2 op \(dynamic attention, "
                                         r"score 'dynamic'.*frozen loader"):
        ServeEngine(cfg, ds, _model(cfg), start_queue=False)
