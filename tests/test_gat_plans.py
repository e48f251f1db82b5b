"""The aligned dst-keyed position plan of the plan attention path: every
chunk is one aligned block of EB edge positions inside one window, so the
device reads whole blocks of the [K, E] arrays instead of gathering
columns (ops.edge._aligned_position_plan, _slot_reader)."""

import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.ops import edge as em
from roc_tpu.ops.pallas.segment_sum import EB, VB


def _edges(kind, seed=0):
    """(src, sorted dst, rows): a near-regular list; one with a hub row of
    3,000 in-edges, empty windows and a tail past the last edge; none."""
    rng = np.random.default_rng(seed)
    if kind == "none":
        return np.zeros(0, np.int64), np.zeros(0, np.int64), 40
    rows = 700
    dst = rng.integers(0, 300, 6000)
    if kind == "hub":
        dst = np.concatenate([dst[dst != 17], np.full(3000, 17),
                              rng.integers(600, 640, 500)])
    dst = np.sort(dst).astype(np.int64)
    return rng.integers(0, rows, dst.size).astype(np.int64), dst, rows


@pytest.mark.parametrize("kind", ["regular", "hub", "none"])
def test_aligned_plan_invariants(kind):
    src, dst, rows = _edges(kind)
    E = dst.size
    obi, edst, pos, nid = em._aligned_position_plan(dst, src, rows)
    C = obi.shape[0]
    assert edst.shape == pos.shape == nid.shape == (C, EB)
    # a chunk is one aligned block: slot j is position EB * block + j
    assert np.all(pos[:, 0] % EB == 0)
    assert np.array_equal(pos, pos[:, :1] + np.arange(EB)[None, :])
    live = edst != VB
    # every edge is live in exactly one slot, at its own position
    assert live.sum() == E
    assert np.array_equal(np.sort(pos[live]), np.arange(E))
    assert np.array_equal(nid[live], src[pos[live]])
    assert np.array_equal(obi[:, None].repeat(EB, 1)[live] * VB + edst[live],
                          dst[pos[live]])
    assert np.all(nid[~live] == 0)
    # window order, and every window at least one chunk (empty ones too)
    windows = (rows + VB - 1) // VB
    assert obi[0] == 0 and obi[-1] == windows - 1
    assert np.all(np.diff(obi) >= 0) and np.all(np.diff(obi) <= 1)
    # about E / EB + windows chunks
    assert C <= -(-E // EB) + windows


@pytest.mark.parametrize("heads", [1, 8])
def test_block_reads_equal_column_gathers(heads):
    """_plan_max and both shapes of _plan_sum over the aligned plan, read
    by blocks, against NumPy; and the same sums read by column gather
    (aligned=False: the positions are true positions either way)."""
    src, dst, rows = _edges("hub", seed=3)
    E, F = dst.size, 4
    rng = np.random.default_rng(1)
    w = rng.standard_normal((heads, E)).astype(np.float32)
    x = rng.standard_normal((rows, heads, F)).astype(np.float32)
    plan = tuple(jnp.asarray(a) for a in
                 em._aligned_position_plan(dst, src, rows))
    obi, edst, pos, nid = plan
    m = np.asarray(em._plan_max(jnp.asarray(w), obi, edst, pos, rows))
    want = np.full((heads, rows), -np.inf, np.float32)
    np.maximum.at(want.T, dst, w.T)
    np.testing.assert_array_equal(m, want)
    z = {a: np.asarray(em._plan_sum(jnp.asarray(w), None, *plan, rows,
                                    "highest", a)) for a in (True, False)}
    zo = np.zeros((rows, heads), np.float64)
    np.add.at(zo, dst, w.T.astype(np.float64))
    np.testing.assert_allclose(z[True], zo.T, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(z[False], z[True], rtol=1e-6, atol=1e-5)
    u = {a: np.asarray(em._plan_sum(jnp.asarray(w), jnp.asarray(x), *plan,
                                    rows, "highest", a))
         for a in (True, False)}
    uo = np.zeros((rows, heads, F), np.float64)
    np.add.at(uo, dst, w.T[:, :, None].astype(np.float64) * x[src])
    np.testing.assert_allclose(u[True], uo, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(u[False], u[True], rtol=1e-5, atol=1e-5)


def test_gat_plans_carry_the_aligned_dst_plan():
    src, dst, rows = _edges("regular")
    plans = em.build_gat_plans(src, dst, rows, rows)
    want = em._aligned_position_plan(dst, src, rows)
    for got, ref in zip(plans[:4], want):
        np.testing.assert_array_equal(np.asarray(got), ref)
    # the src-keyed half stays the packed chunk plan: positions in src order
    live = np.asarray(plans.src_edst) != VB
    assert live.sum() == dst.size
    assert not np.all(np.asarray(plans.src_pos)[:, 0] % EB == 0)


# -- the plan road against a dense softmax written here ---------------------

def _attend_reference(h, a_src, a_dst, src, dst, n, slope=0.2):
    """Per-destination softmax attention with jax.ops segments at
    `highest`: the reference the plan road is held to (values, and through
    jax.grad the hand-derived backward)."""
    import jax
    q = (jnp.einsum("nkf,kf->nk", h, a_dst, precision="highest")[dst]
         + jnp.einsum("nkf,kf->nk", h, a_src, precision="highest")[src])
    s = jnp.where(q >= 0, q, slope * q)                           # [E, K]
    m = jax.ops.segment_max(s, dst, num_segments=n)
    e = jnp.exp(s - m[dst])
    z = jax.ops.segment_sum(e, dst, num_segments=n)
    u = jax.ops.segment_sum(e[:, :, None] * h[src], dst, num_segments=n)
    return u / jnp.maximum(z, 1e-30)[:, :, None]


def _attend_plan(h, a_src, a_dst, plans, src, dst):
    return em.gat_attend_plan(h, h, a_src, a_dst, plans,
                              (jnp.asarray(src), jnp.asarray(dst)), 0.2,
                              "highest")


@pytest.mark.parametrize("heads", [1, 2, 8])
@pytest.mark.parametrize("what", ["integer", "continuous", "gradients"])
def test_plan_attention_against_a_dense_softmax(what, heads):
    """`integer`: non-negative integer features, a_src = 0: every edge of a
    row scores alike, so the coefficients are 1 / in-degree, the weighted
    sums are integers, and plan and reference agree to the BIT (a hub of
    3,000 in-edges included).  `continuous`: within 32 ulps of the output's
    scale.  `gradients`: all three, against the reference's autodiff."""
    import jax
    src, dst, rows = _edges("hub", seed=5)
    rows_with_edges = np.unique(dst)
    F = 4
    rng = np.random.default_rng(heads)
    plans = em.build_gat_plans(src, dst, rows, rows)
    if what == "integer":
        h = jnp.asarray(rng.integers(0, 8, (rows, heads, F)), jnp.float32)
        a_dst = jnp.asarray(rng.integers(0, 3, (heads, F)), jnp.float32)
        a_src = jnp.zeros((heads, F), jnp.float32)
    else:
        h = jnp.asarray(rng.standard_normal((rows, heads, F)), jnp.float32)
        a_src = jnp.asarray(rng.standard_normal((heads, F)), jnp.float32)
        a_dst = jnp.asarray(rng.standard_normal((heads, F)), jnp.float32)
    sj, dj = jnp.asarray(src), jnp.asarray(dst)
    if what == "gradients":
        def loss(fn):
            return lambda *a: jnp.sum(jnp.sin(fn(*a)))
        got = jax.grad(loss(lambda hh, s, d: _attend_plan(
            hh, s, d, plans, src, dst)), argnums=(0, 1, 2))(h, a_src, a_dst)
        ref = jax.grad(loss(lambda hh, s, d: _attend_reference(
            hh, s, d, sj, dj, rows)), argnums=(0, 1, 2))(h, a_src, a_dst)
        for name, a, b in zip(("dh", "da_src", "da_dst"), got, ref):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            err = np.linalg.norm(a - b) / np.linalg.norm(b)
            assert err <= 2e-5, (name, err)
        return
    got = np.asarray(_attend_plan(h, a_src, a_dst, plans, src, dst))
    ref = np.asarray(_attend_reference(h, a_src, a_dst, sj, dj, rows))
    assert not got[np.setdiff1d(np.arange(rows), rows_with_edges)].any()
    if what == "integer":
        np.testing.assert_array_equal(got, ref)
    else:
        scale = np.abs(ref).max() * np.finfo(np.float32).eps
        assert np.abs(got - ref).max() <= 32 * scale
