"""Dot-product attention over in-edges (the tconv op, ops.edge
tconv_attend / tconv_attend_plan; models/tconv.py): the plan road against a
dense masked softmax written here, the rows a softmax can trip on, the
[K, E] layout, what the trainer says about the op, and every road that does
not carry it refusing it by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu import obs, ops
from roc_tpu.graph import datasets
from roc_tpu.models import build_model, build_tconv
from roc_tpu.models.model import Model, attention_heads, attention_score
from roc_tpu.ops import edge as em
from roc_tpu.ops.pallas.segment_sum import EB, VB
from roc_tpu.train.config import Config, parse_args
from roc_tpu.train.driver import Trainer, make_trainer

from test_gat_plans import (_edges, _eqns, _scans_and_their_gathers,
                            _small_steps)


# -- the plan road against softmax(Q K^T / sqrt(d) + mask) V ----------------

def _dense_attention(q, k, v, src, dst, rows):
    """softmax(Q K^T / sqrt(d) + mask) V per head, float64 NumPy: the mask
    is 0 where j -> i is an in-edge (once per copy of the edge: a multigraph
    counts multiplicity) and -inf elsewhere; a row with no in-edge gives
    zeros."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    n, heads, d = q.shape
    count = np.zeros((rows, k.shape[0]))
    np.add.at(count, (dst, src), 1.0)
    out = np.zeros((n, heads, d))
    for c in range(heads):
        s = q[:, c] @ k[:, c].T / np.sqrt(d)
        s = np.where(count > 0, s, -np.inf)
        m = np.where(np.isfinite(s.max(1)), s.max(1), 0.0)
        e = np.exp(s - m[:, None]) * count
        z = e.sum(1)
        out[:, c] = (e / np.where(z > 0, z, 1.0)[:, None]) @ v[:, c]
    return out


def _qkv(rows, heads, d, seed):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((rows, heads, d)),
                             jnp.float32) for _ in range(3))


@pytest.mark.parametrize("heads", [1, 2, 4])
@pytest.mark.parametrize("kind", ["regular", "hub"])
@pytest.mark.parametrize("road", ["xla", "plan"])
def test_attention_against_a_dense_masked_softmax(road, kind, heads):
    """Values within 64 ulps of the output's scale (a hub of 3,000 in-edges
    included; rows without in-edges exact zeros)."""
    src, dst, rows = _edges(kind, seed=5)
    q, k, v = _qkv(rows, heads, 8, heads)
    if road == "plan":
        plans = em.build_gat_plans(src, dst, rows, rows)
        got = em.tconv_attend_plan(q, k, v, plans, dst.size)
    else:
        got = em.tconv_attend(q, k, v, jnp.asarray(src), jnp.asarray(dst),
                              rows)
    want = _dense_attention(q, k, v, src, dst, rows)
    got = np.asarray(got)
    assert not got[np.setdiff1d(np.arange(rows), np.unique(dst))].any()
    scale = np.abs(want).max() * np.finfo(np.float32).eps
    assert np.abs(got - want).max() <= 64 * scale


@pytest.mark.parametrize("dropout", [0.0, 0.3])
@pytest.mark.parametrize("heads", [1, 4])
def test_the_hand_derived_backward_against_autodiff(heads, dropout):
    """dq, dk, dv of the plan road (three weighted row sums over the dst and
    src plans, the mask redrawn from the key) against jax.grad of the xla
    road given the same key."""
    src, dst, rows = _edges("hub", seed=6)
    q, k, v = _qkv(rows, heads, 8, 10 + heads)
    plans = em.build_gat_plans(src, dst, rows, rows)
    sj, dj = jnp.asarray(src), jnp.asarray(dst)
    drop = (jax.random.PRNGKey(3), dropout) if dropout else None

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    got = jax.grad(loss(lambda *a: em.tconv_attend_plan(
        *a, plans, dst.size, drop)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda *a: em.tconv_attend(
        *a, sj, dj, rows, drop)), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.linalg.norm(a - b) / np.linalg.norm(b) <= 2e-5, name


def test_one_in_edge_and_a_padded_row_with_none():
    """A destination with ONE in-edge copies that source's value (its
    coefficient is 1 whatever the score, its score's gradient 0); a row
    with none (a padded shard row) reads zeros and hands finite zeros back
    (z = 0 meets _Z_GUARD, not 0 / 0)."""
    rows = 40
    src = np.array([5, 7, 9, 9, 3], np.int64)
    dst = np.array([2, 4, 4, 4, 11], np.int64)      # 2 and 11: one in-edge
    q, k, v = _qkv(rows, 2, 8, 1)
    q = q * 30.0                                    # scores far from 0
    plans = em.build_gat_plans(src, dst, rows, rows)

    def out(q_, k_, v_):
        return em.tconv_attend_plan(q_, k_, v_, plans, dst.size)

    got = np.asarray(out(q, k, v))
    np.testing.assert_array_equal(got[2], np.asarray(v)[5])
    np.testing.assert_array_equal(got[11], np.asarray(v)[3])
    assert not got[[0, 1, 3, 39]].any()
    grads = jax.grad(lambda *a: jnp.sum(out(*a) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
    dq, dk, _ = (np.asarray(g) for g in grads)
    assert not dq[[2, 11]].any() and not dk[[5, 3]].any()
    assert dq[4].any()          # three in-edges: the scores matter there


# -- layout: [K, E], edges on the lane axis ---------------------------------

@pytest.mark.parametrize("dropout", [0.0, 0.3])
def test_the_plan_road_gathers_a_step_at_a_time_and_keeps_edges_last(
        dropout, monkeypatch):
    """jax.grad of tconv_attend_plan, forward and hand-derived backward:
    the road takes no edge list at all (the plans hold every index), no
    gather's indices are as long as the edge list (each is one scan step's
    slots), no scatter exists (no gather was transposed), and every
    edge-sized intermediate is [K, E]: never the heads, nor a feature row,
    on the lane axis of an edge-sized array."""
    _small_steps(monkeypatch)
    src, dst, rows = _edges("hub", seed=6)
    K, F, E = 4, 16, dst.size           # no other axis of the road is 4 long
    step_slots = 16 * EB
    assert E > 2 * step_slots > 2 * rows
    q, k, v = _qkv(rows, K, F, 0)
    plans = em.build_gat_plans(src, dst, rows, rows)
    drop = (jax.random.PRNGKey(5), dropout) if dropout else None

    def loss(*a):
        return jnp.sum(em.tconv_attend_plan(*a, plans, E, drop) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr
    shapes, gathers = [], []
    for eqn in _eqns(jaxpr):
        assert not eqn.primitive.name.startswith("scatter"), str(eqn)[:200]
        if eqn.primitive.name == "gather":
            gathers.append(int(np.prod(eqn.invars[1].aval.shape[:-1])))
        shapes += [tuple(o.aval.shape) for o in eqn.outvars
                   if getattr(o.aval, "shape", None) is not None]
    assert gathers and max(gathers) <= step_slots
    # s, the broadcast max, s - max and e (the mask and e w with dropout);
    # de and ds are no [K, E] arrays since PR 36: ds is written over e in
    # the [2K, E] stack the src scan reads; the score's scale is applied
    # inside the forward's one scan
    assert sum(1 for s in shapes if s == (K, E)) >= 4
    assert sum(1 for s in shapes if s == (2 * K, E)) >= 2
    heads_last = [s for s in shapes if len(s) >= 2 and s[-1] == K
                  and int(np.prod(s[:-1])) >= step_slots]
    assert not heads_last, heads_last[:5]
    rows_last = [s for s in shapes if len(s) >= 2 and s[-1] == K * F
                 and int(np.prod(s[:-1])) > step_slots]
    assert not rows_last, rows_last[:5]


def _backward_pieces(kind, heads, dropout, seed):
    """A forward over the ``kind`` graph and what the backward's scans
    start from: (plans, (q, k, v), e, w, du, dz, gout, res)."""
    src, dst, rows = _edges(kind, seed=seed)
    K, F, E = heads, 8, dst.size
    q, k, v = _qkv(rows, K, F, 20 + heads)
    plans = em.build_gat_plans(src, dst, rows, rows)
    key = jax.random.PRNGKey(7) if dropout else None
    out, res = em._tconv_plan_fwd(q, k, v, plans, key, E, dropout)
    gout = jnp.cos(out)
    e, zc = res[5], res[6]
    du = gout / zc.T[:, :, None]
    dz = -jnp.einsum("nkf,nkf->kn", gout, out, precision="highest") / zc
    w = em._keep_scale((key, dropout), K, E, e.dtype)
    assert (w is None) == (dropout == 0.0)
    return plans, (q, k, v), e, w, du, dz, gout, res


@pytest.mark.parametrize("dropout", [0.0, 0.3])
@pytest.mark.parametrize("heads", [1, 4])
@pytest.mark.parametrize("kind", ["regular", "hub"])
def test_dk_and_dv_in_one_scan_against_the_two_sums(kind, heads, dropout,
                                                    monkeypatch):
    """The backward's src side, ONE _plan_sum of 2K heads (stacked [2K, E]
    weights, the tables side by side), against the two calls it replaced,
    over several scan steps of the packed src-keyed plan: every column is
    the same float32 contraction over the same slots in the same order, so
    at most another blocking of the same dot (1e-6 of the sum's norm)."""
    _small_steps(monkeypatch)
    plans, (q, k, v), e, w, du, dz, gout, res = _backward_pieces(
        kind, heads, dropout, seed=4)
    rows, K, F = q.shape
    E = e.shape[1]
    assert plans.src_obi.shape[0] > 2 * 16          # several steps
    dq, dk, dv = em._tconv_plan_bwd(E, dropout, res, gout)[:3]
    # the two sums as they were, from the same per-edge weights
    dplan = (plans.dst_obi, plans.dst_edst, plans.dst_pos, plans.dst_nid)
    splan = (plans.src_obi, plans.src_edst, plans.src_pos, plans.src_nid)
    de = em._edge_contract(du, v, *dplan, E)
    de = em._plan_broadcast(dz, *dplan[:3], E, de if w is None else de * w)
    ds = e * de * (1.0 / np.sqrt(F))
    want_dk = em._plan_sum(ds, q, *splan, rows, "highest")
    want_dv = em._plan_sum(e if w is None else e * w, du, *splan, rows,
                           "highest")
    for name, a, b in (("dk", dk, want_dk), ("dv", dv, want_dv)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert a.shape == (rows, K, F) and np.linalg.norm(b) > 0
        assert np.linalg.norm(a - b) <= 1e-6 * np.linalg.norm(b), name
    assert np.asarray(dq).shape == (rows, K, F)


@pytest.mark.parametrize("dropout", [0.0, 0.3])
@pytest.mark.parametrize("heads", [1, 4])
@pytest.mark.parametrize("kind", ["regular", "hub"])
def test_de_and_dq_in_one_scan_against_the_scans_it_replaced(
        kind, heads, dropout, monkeypatch):
    """The backward's dst side, ONE scan over one gather of [k | v] rows
    (_contract_then_sum), against the three scans it replaced (the
    contraction with v, dz's broadcast, the sum with k), over several steps
    of the aligned plan: rows without an in-edge, empty windows and window
    boundaries inside an aligned block included.  ds is the same value slot
    by slot up to one reassociation ((e w) de + e dz for e (de w + dz)) and
    every masked slot adds an exact zero, so it is held elementwise; dq is the same sum in steps of its own length."""
    _small_steps(monkeypatch)
    plans, (q, k, v), e, w, du, dz, _, _ = _backward_pieces(
        kind, heads, dropout, seed=4)
    rows, K, F = q.shape
    E = e.shape[1]
    dplan = (plans.dst_obi, plans.dst_edst, plans.dst_pos, plans.dst_nid)
    chunks, blocks = plans.dst_obi.shape[0], -(-E // EB)
    assert chunks > 2 * 16                          # several steps
    live = np.asarray(plans.dst_edst) != VB
    assert chunks > blocks and not live.all(axis=1).all()   # cut blocks
    assert not live.any(axis=1).all()               # an empty window's chunk
    no_in_edge = np.setdiff1d(np.arange(rows), np.asarray(
        plans.src_nid)[np.asarray(plans.src_edst) != VB])
    assert no_in_edge.size
    ew = e if w is None else e * w
    sw, dq = em._contract_then_sum(du, dz, k, v, e, ew, *dplan, E)
    # the three scans as they were
    de = em._edge_contract(du, v, *dplan, E)
    de = em._plan_broadcast(dz, *dplan[:3], E, de if w is None else de * w)
    want_ds = np.asarray(e * de * (1.0 / np.sqrt(F)), np.float64)
    want_dq = np.asarray(em._plan_sum(jnp.asarray(want_ds, jnp.float32), k,
                                      *dplan, rows, "highest", True),
                         np.float64)
    assert sw.shape == (2 * K, E) and dq.shape == (rows, K, F)
    # the stack the src scan reads: ds over e, and e w as it came
    np.testing.assert_array_equal(np.asarray(sw[K:]), np.asarray(ew))
    ds = np.asarray(sw[:K], np.float64)
    assert np.abs(want_ds).max() > 0
    assert np.abs(ds - want_ds).max() <= 1e-6 * np.abs(want_ds).max()
    dq = np.asarray(dq, np.float64)
    assert np.linalg.norm(dq - want_dq) <= 1e-6 * np.linalg.norm(want_dq)
    assert not dq[no_in_edge].any()                 # exact zeros


@pytest.mark.parametrize("dropout", [0.0, 0.3])
def test_the_backward_walks_the_src_plan_once_an_op(dropout, monkeypatch):
    """jax.grad of tconv_attend_plan: THREE scans gather node rows (the
    fused score / u forward; the fused de / dq and the fused dk / dv
    backward) and all three read 2 K F wide rows ([k | v] by dst_nid
    twice, [q | du] by src_nid); the src scan's column gather reads ONE
    stacked [2K, E] per-edge array, and no other scan gathers from a
    [K, E] or [2K, E] array by column (every dst-keyed read is by aligned
    blocks)."""
    _small_steps(monkeypatch)
    src, dst, rows = _edges("hub", seed=6)
    K, F, E = 4, 16, dst.size
    q, k, v = _qkv(rows, K, F, 0)
    plans = em.build_gat_plans(src, dst, rows, rows)
    drop = (jax.random.PRNGKey(5), dropout) if dropout else None

    def loss(*a):
        return jnp.sum(em.tconv_attend_plan(*a, plans, E, drop) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr
    scans = _scans_and_their_gathers(jaxpr)
    narrow = [g for g in scans if (rows, K * F) in g]
    wide = [g for g in scans if (rows, 2 * K * F) in g]
    assert (len(narrow), len(wide)) == (0, 3)
    by_column = [g for g in scans if (K, E) in g or (2 * K, E) in g]
    assert len(by_column) == 1 and by_column[0] in wide
    assert by_column[0].count((2 * K, E)) == 1 and (K, E) not in by_column[0]
    # of the other two, the backward's takes its blocks of e and e w out of
    # one step's lane range of the stack, which it carries
    dst_side = [g for g in wide if g is not by_column[0]]
    assert sum((2 * K, 8, EB) in g for g in dst_side) == 1  # _small_steps


def test_an_additive_score_has_no_second_table_to_pair(monkeypatch):
    """Separation by what the op is: gat's backward has ONE dst-keyed row
    gather (de; its dq is a plain [K, E] -> [K, N] sum with no table), so
    it keeps its scans and never reaches the fused one."""
    def refuse(*a, **k):
        raise AssertionError("gat_attend_plan reached _contract_then_sum")
    monkeypatch.setattr(em, "_contract_then_sum", refuse)
    src, dst, rows = _edges("regular", seed=2)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((rows, 2, 8)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((2, 2, 8)), jnp.float32)
    plans = em.build_gat_plans(src, dst, rows, rows)
    ids = (jnp.asarray(src), jnp.asarray(dst))

    def loss(h_, a_):
        return jnp.sum(em.gat_attend_plan(h_, h_, a_[0], a_[1], plans, ids,
                                          0.2) ** 2)

    dh, da = jax.grad(loss, argnums=(0, 1))(h, a)
    assert np.isfinite(np.asarray(dh)).all() and np.asarray(da).any()
    q, k, v = _qkv(rows, 2, 8, 0)
    with pytest.raises(AssertionError, match="_contract_then_sum"):
        jax.grad(lambda q_: jnp.sum(em.tconv_attend_plan(
            q_, k, v, plans, dst.size)))(q)


# -- the forward's one scan: score, max, normaliser and u -------------------

@pytest.mark.parametrize("dropout", [0.0, 0.3])
@pytest.mark.parametrize("kind", ["regular", "hub"])
def test_the_fused_forward_against_the_xla_road(kind, dropout, monkeypatch):
    """The forward's ONE scan over the aligned plan (_score_then_sum: the
    softmax's max and normaliser carried online over several steps)
    against tconv_attend given the same key: within 64 ulps of the
    output's scale, rows without an in-edge exact zeros."""
    _small_steps(monkeypatch)
    src, dst, rows = _edges(kind, seed=8)
    q, k, v = _qkv(rows, 4, 8, 30)
    plans = em.build_gat_plans(src, dst, rows, rows)
    assert plans.dst_obi.shape[0] > 2 * 8           # several steps
    drop = (jax.random.PRNGKey(9), dropout) if dropout else None
    got = np.asarray(em.tconv_attend_plan(q, k, v, plans, dst.size, drop))
    want = np.asarray(em.tconv_attend(q, k, v, jnp.asarray(src),
                                      jnp.asarray(dst), rows, drop))
    assert not got[np.setdiff1d(np.arange(rows), np.unique(dst))].any()
    scale = np.abs(want).max() * np.finfo(np.float32).eps
    assert np.abs(got - want).max() <= 64 * scale


def test_a_hub_whose_largest_score_comes_last_is_rescaled(monkeypatch):
    """A hub row of 6,000 in-edges over at least three scan steps, its
    largest scores (110) on its last 16 edges, in its LAST step, every other
    score 77 to 83: exp of the largest overflows float32 and 6,000 of the
    others nearly do, so every sum is taken against a running max, and what
    the earlier steps summed must be scaled by exp(m_old - m_new) (about
    e^-30) when the last step raises it.  The hub then reads row 0's
    values, as the dense softmax does."""
    _small_steps(monkeypatch)
    rng = np.random.default_rng(11)
    rows, hub, K, F = 64, 17, 2, 8
    other = rng.integers(0, 40, 2000)
    dst = np.sort(np.concatenate([other[other != hub],
                                  np.full(6000, hub)])).astype(np.int64)
    src = rng.integers(1, rows, dst.size).astype(np.int64)
    last = np.flatnonzero(dst == hub)[-16:]
    src[last] = 0
    t = 80.0 + rng.uniform(-3.0, 3.0, (rows, K))
    t[0] = 110.0
    q = jnp.ones((rows, K, F), jnp.float32)         # the score is t[src]
    k = jnp.asarray(np.repeat(t[:, :, None] / np.sqrt(F), F, axis=2),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((rows, K, F)), jnp.float32)
    plans = em.build_gat_plans(src, dst, rows, rows)
    # the hub's chunks span three steps or more, its last edge in the last
    obi, edst, pos = (np.asarray(a) for a in (
        plans.dst_obi, plans.dst_edst, plans.dst_pos))
    steps = np.unique(np.flatnonzero(obi == hub // VB) // em._PLAN_CB_BLOCKS)
    assert steps.size >= 3
    chunk, = np.flatnonzero(((pos == last[-1]) & (edst < VB)).any(axis=1))
    assert chunk // em._PLAN_CB_BLOCKS == steps[-1]
    with np.errstate(over="ignore"):
        assert np.isinf(np.exp(np.float32(t.max())))
    got = np.asarray(em.tconv_attend_plan(q, k, v, plans, dst.size))
    assert np.isfinite(got).all()
    want = _dense_attention(q, k, v, src, dst, rows)
    scale = np.abs(want).max() * np.finfo(np.float32).eps
    assert np.abs(got - want).max() <= 64 * scale
    np.testing.assert_allclose(got[hub], np.asarray(v)[0], atol=1e-5)


def test_a_row_with_no_in_edge_sums_nothing_in_the_fused_scan(monkeypatch):
    """_score_then_sum over the hub graph (empty windows, a tail past the
    last edge): the score at every edge, the row max, and on a row with no
    in-edge a max of -inf, exact zeros of z and u, and an output of 0."""
    _small_steps(monkeypatch)
    src, dst, rows = _edges("hub", seed=6)
    K, F = 2, 8
    q, k, v = _qkv(rows, K, F, 3)
    plans = em.build_gat_plans(src, dst, rows, rows)
    dplan = (plans.dst_obi, plans.dst_edst, plans.dst_pos, plans.dst_nid)
    s, m, z, u = (np.asarray(a) for a in em._score_then_sum(
        q, None, *dplan, dst.size, em._dot_tables(q, k, v), "highest"))
    want_s = np.einsum("ekf,ekf->ke", np.asarray(q, np.float64)[dst],
                       np.asarray(k, np.float64)[src]) / np.sqrt(F)
    np.testing.assert_allclose(s, want_s, rtol=1e-5, atol=1e-5)
    want_m = np.full((K, rows), -np.inf)
    np.maximum.at(want_m.T, dst, s.T)
    np.testing.assert_array_equal(m, want_m.astype(np.float32))
    none = np.setdiff1d(np.arange(rows), dst)
    some = np.unique(dst)
    assert none.size and not z[:, none].any() and not u[none].any()
    assert (z[:, some] >= 1.0).all()
    out = np.asarray(em.tconv_attend_plan(q, k, v, plans, dst.size))
    assert not out[none].any() and out[some].any()


@pytest.mark.parametrize("dropout", [0.0, 0.3])
@pytest.mark.parametrize("kind", ["regular", "hub"])
def test_the_gradient_through_the_fused_forward_against_the_xla_road(
        kind, dropout, monkeypatch):
    """dq, dk, dv of the plan road over several steps of every scan (the
    backward starts from the fused forward's e and normaliser) against
    jax.grad of tconv_attend given the same key."""
    _small_steps(monkeypatch)
    src, dst, rows = _edges(kind, seed=9)
    q, k, v = _qkv(rows, 4, 8, 40)
    plans = em.build_gat_plans(src, dst, rows, rows)
    sj, dj = jnp.asarray(src), jnp.asarray(dst)
    drop = (jax.random.PRNGKey(4), dropout) if dropout else None

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    got = jax.grad(loss(lambda *a: em.tconv_attend_plan(
        *a, plans, dst.size, drop)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda *a: em.tconv_attend(
        *a, sj, dj, rows, drop)), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.linalg.norm(a - b) / np.linalg.norm(b) <= 2e-5, name


@pytest.mark.parametrize("dropout", [0.0, 0.3])
def test_three_row_gathering_scans_an_op_and_no_row_on_a_lane(dropout,
                                                              monkeypatch):
    """jax.grad through two tconv ops: three scans an op gather node rows
    (the forward's score / u, the backward's de / dq and dk / dv), each of
    2 K F wide rows; no scatter; no edge-sized array has a node row, or
    the heads, on its lane axis."""
    _small_steps(monkeypatch)
    src, dst, rows = _edges("hub", seed=6)
    K, F, E = 4, 16, dst.size
    step_slots = 16 * EB
    q, k, v = _qkv(rows, K, F, 0)
    plans = em.build_gat_plans(src, dst, rows, rows)
    drop = (jax.random.PRNGKey(5), dropout) if dropout else None

    def loss(q_, k_, v_):
        h = em.tconv_attend_plan(q_, k_, v_, plans, E, drop)
        return jnp.sum(em.tconv_attend_plan(h, k_, v_, plans, E, drop) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr
    scans = _scans_and_their_gathers(jaxpr)
    by_rows = [g for g in scans if any(
        s[0] == rows and len(s) == 2 for s in g)]
    assert len(by_rows) == 2 * 3
    assert all((rows, 2 * K * F) in g for g in by_rows)
    shapes = []
    for eqn in _eqns(jaxpr):
        assert not eqn.primitive.name.startswith("scatter"), str(eqn)[:200]
        shapes += [tuple(o.aval.shape) for o in eqn.outvars
                   if getattr(o.aval, "shape", None) is not None]
    on_lanes = [s for s in shapes if len(s) >= 2
                and s[-1] in (K, K * F, 2 * K * F)
                and int(np.prod(s[:-1])) > step_slots]
    assert not on_lanes, on_lanes[:5]


# -- the builder and the op IR ----------------------------------------------

def test_build_tconv_reads_layers_as_the_docstring_says():
    model = build_tconv([602, 128, 128, 41], 0.3, heads=4)
    kinds = [op.kind for op in model.ops]
    # the op is a gat op of the IR whose score is a dot product
    assert kinds == ["dropout", "gat", "layernorm", "activation"] * 2 + [
        "dropout", "gat"]
    tconv = [op for op in model.ops if op.kind == "gat"]
    assert {attention_score(op) for op in model.ops} == {None, "dot"}
    assert [(op.attrs["heads"], op.attrs["mean_heads"], op.attrs["head_dim"],
             attention_heads(op)) for op in tconv] == [
        (4, 1, 32, 4), (4, 1, 32, 4), (1, 4, 41, 4)]
    # inputs and coefficients dropped at one rate, masks of their own
    slots = [op.attrs["slot"] for op in model.ops
             if op.kind in ("dropout", "gat")]
    assert sorted(slots) == list(range(6))
    assert all(op.attrs["attn_drop"] == 0.3 for op in tconv)
    params = model.init_params(jax.random.PRNGKey(0))
    assert params["tconv_2_wq"].shape == (128, 164)
    assert params["tconv_2_wr"].shape == (128, 41)
    assert params["tconv_2_wg"].shape == (123,)
    assert params["tconv_0_wg"].shape == (384,)
    assert not np.asarray(params["tconv_1_bq"]).any()
    assert np.asarray(params["ln_1_gain"]).tolist() == [1.0] * 128
    assert "ln_2_gain" not in params            # no LayerNorm on the output
    assert model.logits.dim == 41
    masks = model.keep_masks(jax.random.PRNGKey(1), 10, 50)
    assert sorted(v.shape for v in masks.values()) == sorted(
        [(10, 602), (10, 128), (10, 128)] + [(4, 50)] * 3)
    with pytest.raises(ValueError, match="not a multiple of heads=3"):
        build_tconv([602, 128, 41], heads=3)
    assert [op.kind for op in build_model(
        "tconv", [8, 4, 3], 0.0, heads=2).ops][:2] == ["dropout", "gat"]
    assert parse_args(["-model", "tconv", "-layers", "8-4-3"]).model \
        == "tconv"


def test_layer_norm_against_numpy():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((50, 12)).astype(np.float32) * 3 + 1
    gain = rng.standard_normal(12).astype(np.float32)
    bias = rng.standard_normal(12).astype(np.float32)
    x64 = x.astype(np.float64)
    want = (x64 - x64.mean(1, keepdims=True)) / np.sqrt(
        x64.var(1, keepdims=True) + 1e-5) * gain + bias
    got = np.asarray(ops.layer_norm(jnp.asarray(x), gain, bias))
    assert np.abs(got - want).max() < 1e-5


def test_a_hand_built_model_can_average_heads_anywhere():
    """Two output groups, each the mean of three heads, against the
    six-head concatenation averaged by hand."""
    ds = _dataset()
    n = ds.graph.num_nodes

    def build(heads, mean_heads):
        m = Model(in_dim=8)
        t = m.tconv(m.input, 5, heads=heads, mean_heads=mean_heads)
        m.end_layer()
        m.softmax_cross_entropy(t)
        return m

    grouped, flat = build(2, 3), build(6, 1)
    assert (grouped.logits.dim, flat.logits.dim) == (10, 30)
    assert grouped.keep_masks(jax.random.PRNGKey(0), 10, 50) == {}
    params = flat.init_params(jax.random.PRNGKey(4))
    from roc_tpu.train.driver import dense_graph_data, make_gctx
    gctx = make_gctx(dense_graph_data(ds.graph, "xla", "exact"), n)
    # gate shut: wg = 0 gives b = 1/2; skip off: logits = m / 2
    params = dict(params, tconv_0_wg=jnp.zeros(90),
                  tconv_0_wr=jnp.zeros((8, 30)))
    x = jnp.asarray(ds.features)
    m_flat = 2 * np.asarray(flat.apply(params, x, gctx))       # [N, 6 x 5]
    want = m_flat.reshape(n, 2, 3, 5).mean(2).reshape(n, 10)
    small = dict(params, tconv_0_wg=jnp.zeros(30),
                 tconv_0_wr=jnp.zeros((8, 10)), tconv_0_br=jnp.zeros(10))
    got = 2 * np.asarray(grouped.apply(small, x, gctx))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# -- what the trainer says ---------------------------------------------------

def _dataset(n=200):
    return datasets.synthetic("t", n, 4.0, 8, 4, n_train=30, n_val=30,
                              n_test=30, seed=3)


def _config(ds, **kw):
    base = dict(layers=[ds.in_dim, 8, 8, ds.num_classes], num_epochs=1,
                eval_every=10**9, dropout_rate=0.3, model="tconv", heads=2,
                aggregate_backend="matmul", weight_decay=0.0)
    base.update(kw)
    return Config(**base)


def _model(cfg):
    return build_model("tconv", cfg.layers, cfg.dropout_rate, heads=cfg.heads)


@pytest.fixture
def recording():
    was = obs.enabled()
    obs.enable(True)
    obs.get_tracer().clear()
    yield obs.get_tracer()
    obs.get_tracer().clear()
    obs.enable(was)


@pytest.mark.parametrize("model,serves", [("gat", "gat"),
                                          ("tconv", "tconv")])
def test_gat_plan_build_says_which_op_kind_its_plans_serve(model, serves,
                                                           recording):
    ds = _dataset()
    cfg = _config(ds, model=model, layers=[ds.in_dim, 8, ds.num_classes])
    Trainer(cfg, ds, build_model(model, cfg.layers, 0.3, heads=2))
    span, = [s for s in recording.spans() if s.name == "gat_plan_build"]
    assert span.args["serves"] == serves
    assert span.args["pad_ratio"] >= 1.0


def test_attention_record_gauges_and_start_up_line(tmp_path, capsys):
    ds = _dataset()
    cfg = _config(ds, obs=True, obs_dir=str(tmp_path / "obs"))
    tr = Trainer(cfg, ds, _model(cfg))
    info = tr.attention_info()
    e = ds.graph.num_edges
    assert list(info) == ["backend", "plan_pad_ratio", "score", "score_bytes",
                          "residual_bytes", "row_passes", "row_scans",
                          "src_scans", "short_scans"]
    assert (info["backend"], info["score"]) == ("plan", "dot")
    # one [K, E] float32 array; e of each of the three ops; six tables an
    # op read by row in three scans (k with v for the score and u, k with
    # v for de and dq, q with du for dk and dv); ONE of them an op over the
    # src-keyed plan
    assert info["score_bytes"] == 2 * e * 4
    assert info["residual_bytes"] == 3 * 2 * e * 4
    assert info["row_passes"] == 18
    assert info["row_scans"] == 9
    assert info["src_scans"] == 3
    assert info["short_scans"] == 0         # every row here is 128 lanes
    line = next(ln for ln in capsys.readouterr().err.splitlines()
                if ln.startswith("# attention:"))
    assert line == (
        "# attention: backend=plan"
        f" tconv_plan_pad_ratio={info['plan_pad_ratio']:.4f}"
        f" tconv_score=dot tconv_score_bytes={info['score_bytes']}"
        f" tconv_residual_bytes={info['residual_bytes']}"
        " tconv_row_passes=18 tconv_row_scans=9 tconv_src_scans=3"
        " tconv_short_scans=0")
    tr.train(print_fn=lambda *a, **k: None)
    recs = obs.load_jsonl(str(tmp_path / "obs" / "metrics.jsonl"))
    att, = [r for r in recs if r["type"] == "attention"]
    assert att["backend"] == "plan" and att["tconv_score"] == "dot"
    assert att["tconv_residual_bytes"] == info["residual_bytes"]
    assert (att["tconv_row_passes"], att["tconv_row_scans"],
            att["tconv_src_scans"]) == (18, 9, 3)
    assert list(att)[-4:] == ["tconv_row_passes", "tconv_row_scans",
                              "tconv_src_scans", "tconv_short_scans"]
    prom = (tmp_path / "obs" / "metrics.prom").read_text()
    for name in ("plan_pad_ratio", "score_bytes", "residual_bytes",
                 "row_passes", "row_scans", "src_scans", "short_scans"):
        assert f"roc_tconv_{name} " in prom
    assert "roc_tconv_src_scans 3" in prom          # unlabelled: a counter
    assert "roc_tconv_row_scans 9" in prom
    assert 'roc_tconv_backend{backend="plan"} 1' in prom
    assert 'roc_tconv_score{score="dot"} 1' in prom
    from roc_tpu.obs import report as obs_report
    text = obs_report.report(str(tmp_path / "obs" / "trace.json"),
                             str(tmp_path / "obs" / "metrics.jsonl"))
    assert line in text


def test_the_xla_road_keeps_no_plan_residual(capsys):
    ds = _dataset()
    cfg = _config(ds, aggregate_backend="xla")
    tr = Trainer(cfg, ds, _model(cfg))
    info = tr.attention_info()
    assert (info["backend"], info["residual_bytes"]) == ("xla", 0)
    assert info["src_scans"] == 0           # no plan is walked at all
    assert info["short_scans"] == 0
    assert (info["row_scans"], info["row_passes"]) == (0, 18)
    assert tr.gdata.gat_plans is None and tr.gdata.backend == "xla"
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


def test_checkpoint_round_trip_keeps_every_parameter(tmp_path):
    from roc_tpu.train import checkpoint
    ds = _dataset()
    cfg = _config(ds)
    tr = Trainer(cfg, ds, _model(cfg))
    tr.run_epoch()
    path = str(tmp_path / "ck.npz")
    tr.save_checkpoint(path)
    fresh = Trainer(cfg, ds, _model(cfg))
    loaded = checkpoint.load_params(path, fresh.params)
    assert set(loaded) == set(tr.params)
    for name in loaded:
        np.testing.assert_array_equal(np.asarray(loaded[name]),
                                      np.asarray(tr.params[name]))


# -- roads that do not carry the op say so by name ---------------------------

ROADS = {
    "spmd-halo": dict(num_parts=4),
    "spmd-allgather": dict(num_parts=4, exchange="allgather"),
    "spmd-ring": dict(num_parts=4, exchange="ring"),
    "spmd-edge-shard": dict(num_parts=4, edge_shard="on"),
    "spmd-overcommit": dict(num_parts=16),
    "stream": dict(num_parts=2, stream=True),
}
SAYS = {
    "spmd-halo": r"SpmdTrainer \(-exchange halo, -parts 4\)",
    "spmd-allgather": r"SpmdTrainer \(-exchange allgather, -parts 4\)",
    "spmd-ring": r"SpmdTrainer \(-exchange ring, -parts 4\)",
    "spmd-edge-shard": r"SpmdTrainer \(-edge-shard, -exchange halo",
    "spmd-overcommit": r"SpmdTrainer \(overcommit, -exchange halo, -parts 16",
    "stream": r"streamed executor \(-stream, stream/segments.py",
}


@pytest.mark.parametrize("road", sorted(ROADS))
def test_a_road_without_the_op_refuses_it_by_name(road):
    ds = _dataset()
    cfg = _config(ds, **ROADS[road])
    with pytest.raises(ValueError, match="-model tconv: the tconv op") as e:
        make_trainer(cfg, ds, _model(cfg))
    import re
    assert re.search(SAYS[road], str(e.value)), str(e.value)
    assert "one-chip Trainer" in str(e.value)


def test_the_serving_loader_refuses_it_by_name():
    from roc_tpu.serve.engine import ServeEngine
    ds = _dataset()
    cfg = _config(ds)
    with pytest.raises(ValueError, match=r"tconv op.*frozen loader behind "
                                         r"serve/ and fleet/"):
        ServeEngine(cfg, ds, _model(cfg), start_queue=False)


def test_a_gat_model_still_takes_every_road():
    """The refusal is the tconv op's alone."""
    ds = _dataset()
    cfg = _config(ds, model="gat", layers=[ds.in_dim, 8, ds.num_classes],
                  num_parts=4)
    tr = make_trainer(cfg, ds, build_model("gat", cfg.layers, 0.3, heads=2))
    assert np.isfinite(float(tr.run_epoch()))
