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


# -- reads by edge_dst through the plan: the segment broadcast ---------------

def _stepped_edges():
    """A sorted dst list whose aligned plan, walked 8 chunks a step, opens
    its SECOND step with an empty window's all-masked chunk (block 0)
    while that step's live chunks start at block 2 and run past block 7:
    six windows of 100 in-edges (8 pieces over blocks 0-2), an empty
    window, a hub of 3,000 in one row, another empty window, a few rows of
    a ragged tail, and empty windows to the end."""
    rng = np.random.default_rng(11)
    dst = np.concatenate([np.repeat(np.arange(6) * VB + 3, 100),
                          np.full(3000, 7 * VB + 5),
                          np.sort(rng.integers(9 * VB, 12 * VB, 333))])
    rows = 16 * VB + 3
    return rng.integers(0, rows, dst.size).astype(np.int64), \
        dst.astype(np.int64), rows


def _edge_list(kind):
    return _stepped_edges() if kind == "stepped" else _edges(kind, seed=2)


def test_the_stepped_list_opens_a_step_with_an_empty_windows_chunk():
    _, dst, rows = _stepped_edges()
    obi, edst, pos, _ = em._aligned_position_plan(dst, dst, rows)
    live = (edst != VB).any(axis=1)
    step = slice(8, 16)
    assert obi.shape[0] > 16 and not live[8] and pos[8, 0] == 0
    blocks = pos[step, 0][live[step]] // EB
    assert blocks.min() == 2 and blocks.max() > 7


@pytest.mark.parametrize("heads", [1, 8])
@pytest.mark.parametrize("kind", ["regular", "hub", "none", "stepped"])
def test_plan_broadcast_is_the_gather_by_edge_dst_bit_for_bit(
        kind, heads, monkeypatch):
    """``table[:, edge_dst]`` without a gather: chunks of one block summed
    in place, empty windows' chunks dropped, several steps (8 chunks a
    step), and one step over the whole plan."""
    src, dst, rows = _edge_list(kind)
    obi, edst, pos, _ = (jnp.asarray(a) for a in
                         em._aligned_position_plan(dst, src, rows))
    table = np.random.default_rng(heads).standard_normal(
        (heads, rows)).astype(np.float32)
    for cb in (8, 256):
        monkeypatch.setattr(em, "_PLAN_CB_BLOCKS", cb)
        got = np.asarray(em._plan_broadcast(jnp.asarray(table), obi, edst,
                                            pos, dst.size))
        assert got.shape == (heads, dst.size)
        np.testing.assert_array_equal(got, table[:, dst])
    # onto a [K, E] array already there: one addition a slot, as x + gather
    onto = np.random.default_rng(9).standard_normal(
        (heads, dst.size)).astype(np.float32)
    got = np.asarray(em._plan_broadcast(jnp.asarray(table), obi, edst, pos,
                                        dst.size, jnp.asarray(onto)))
    np.testing.assert_array_equal(got, onto + table[:, dst])


@pytest.mark.parametrize("heads", [1, 8])
@pytest.mark.parametrize("kind", ["regular", "hub", "none", "stepped"])
def test_edge_contract_over_the_plan_against_numpy(kind, heads, monkeypatch):
    """c[k, e] = sum_f du[dst_e, k, f] * table[src_e, k, f]: du rows spread
    by the plan, table rows by the plan's nid."""
    src, dst, rows = _edge_list(kind)
    F = 5
    rng = np.random.default_rng(heads + 1)
    du = rng.standard_normal((rows, heads, F)).astype(np.float32)
    table = rng.standard_normal((rows + 9, heads, F)).astype(np.float32)
    plan = tuple(jnp.asarray(a) for a in
                 em._aligned_position_plan(dst, src, rows))
    want = np.einsum("ekf,ekf->ke", du[dst].astype(np.float64),
                     table[src].astype(np.float64))
    for cb in (8, 256):
        monkeypatch.setattr(em, "_PLAN_CB_BLOCKS", cb)
        got = np.asarray(em._edge_contract(jnp.asarray(du),
                                           jnp.asarray(table), *plan,
                                           dst.size))
        assert got.shape == (heads, dst.size)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("heads", [1, 8])
def test_pad_chunks_of_stacked_plans_change_nothing(heads, monkeypatch):
    """pad_gat_plans pads the shorter shard's plan with all-masked chunks
    (block 0, the last window): the broadcast and the contraction read the
    same values from a padded plan, mid-step and over whole steps of pad."""
    monkeypatch.setattr(em, "_PLAN_CB_BLOCKS", 8)
    lists = [_edges("hub", seed=4), _edges("regular", seed=4)]
    rows = lists[0][2]
    plans = [em.build_gat_plans(s, d, rows, rows) for s, d, _ in lists]
    counts = [int(p.dst_obi.shape[0]) for p in plans]
    stacked = em.pad_gat_plans(plans, min_d=max(counts) + 21)
    assert stacked.dst_obi.shape == (2, max(counts) + 21)
    F = 3
    rng = np.random.default_rng(heads)
    table = jnp.asarray(rng.standard_normal((heads, rows)), jnp.float32)
    du = jnp.asarray(rng.standard_normal((rows, heads, F)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((rows, heads, F)), jnp.float32)
    for i, (plan, (_, dst, _)) in enumerate(zip(plans, lists)):
        bare = (plan.dst_obi, plan.dst_edst, plan.dst_pos)
        padded = (stacked.dst_obi[i], stacked.dst_edst[i], stacked.dst_pos[i])
        got = np.asarray(em._plan_broadcast(table, *padded, dst.size))
        np.testing.assert_array_equal(got, np.asarray(table)[:, dst])
        np.testing.assert_array_equal(
            np.asarray(em._edge_contract(du, x, *padded,
                                         stacked.dst_nid[i], dst.size)),
            np.asarray(em._edge_contract(du, x, *bare, plan.dst_nid,
                                         dst.size)))


# -- further weights riding a row sum's read (the backward's src side) -------

@pytest.mark.parametrize("precision", ["highest", "default"])
@pytest.mark.parametrize("heads,riding", [(1, 1), (8, 8), (1, 2)])
def test_riding_weights_against_the_two_calls(heads, riding, precision,
                                              monkeypatch):
    """_plan_sum(w, x, ..., ride=r) over a PACKED src-keyed plan with pad
    chunks and an empty window, several scan steps: the row sum and the
    plain sum of the two calls it replaces (dtable and dast of
    _gat_plan_bwd), bit for bit: the rows' weights are the first K rows of
    the one stacked read, the riding ones (K' of them) the rest."""
    monkeypatch.setattr(em, "_PLAN_CB_SUM", 8)
    src, dst, rows = _edges("hub", seed=8)
    src = np.where(src // VB == 5, src + VB, src)       # window 5: empty
    E, F = dst.size, 4
    plans = em.build_gat_plans(src, dst, rows, rows)
    splan = em._pad_posplan(plans.src_obi, plans.src_edst, plans.src_pos,
                            plans.src_nid, 5)           # not a step multiple
    assert splan[0].shape[0] > 16 and splan[0].shape[0] % 8
    assert 5 in np.asarray(splan[0])
    assert not (np.asarray(splan[1])[np.asarray(splan[0]) == 5] != VB).any()
    rng = np.random.default_rng(heads)
    w, r = (jnp.asarray(rng.standard_normal((k, E)), jnp.float32)
            for k in (heads, riding))
    x = jnp.asarray(rng.standard_normal((rows, heads, F)), jnp.float32)
    got_rows, got_plain = em._plan_sum(w, x, *splan, rows, precision, ride=r)
    want_rows = em._plan_sum(w, x, *splan, rows, precision)
    want_plain = em._plan_sum(r, None, *splan, rows, "highest")
    assert got_rows.shape == (rows, heads, F)
    assert got_plain.shape == (riding, rows)
    np.testing.assert_array_equal(np.asarray(got_rows), np.asarray(want_rows))
    np.testing.assert_array_equal(np.asarray(got_plain),
                                  np.asarray(want_plain))
    # and the plain sum is the sum: NumPy over the edges by source
    ref = np.zeros((rows, riding), np.float64)
    np.add.at(ref, src, np.asarray(r, np.float64).T)
    np.testing.assert_allclose(np.asarray(got_plain), ref.T, rtol=1e-5,
                               atol=1e-4)
    assert not np.asarray(got_plain)[:, 5 * VB:6 * VB].any()


# -- the step of a row-gathering sum, from the gathered row's width ---------

# the shipped budget's steps: gat's rows (41, 64) and 128-lane rows keep the
# cap; 164 lanes, tconv's hidden src (256) and L2 src (328) do not
_STEPS = {41: 512, 64: 512, 128: 512, 164: 256, 256: 256, 328: 128}


@pytest.mark.parametrize("width", sorted(_STEPS))
def test_plan_sum_step_reads_the_lane_tiled_width(width):
    cb = em.plan_sum_step(width)
    lanes = -(-width // 128) * 128
    block = lambda c: c * EB * lanes * 4                    # noqa: E731
    assert cb == _STEPS[width] and cb & (cb - 1) == 0
    assert cb <= em._PLAN_CB_SUM and block(cb) <= em._PLAN_SUM_BLOCK_BYTES
    assert cb == em._PLAN_CB_SUM or block(2 * cb) > em._PLAN_SUM_BLOCK_BYTES
    # the width, not the bytes of a row, decides: 41 and 128 lanes alike
    assert em.plan_sum_step(lanes) == cb
    assert em.short_plan_sums([width]) == (cb < em._PLAN_CB_SUM)


def _scan_lengths(fn, *args):
    """The scans' trip counts, traced afresh (the module's constants are
    read at trace time)."""
    import jax
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a))(*args).jaxpr
    return [e.params["length"] for e in _eqns(jaxpr)
            if e.primitive.name == "scan"]


@pytest.mark.parametrize("form", ["rows", "ride"])
def test_plan_sum_at_the_short_step_equals_the_cap(form, monkeypatch):
    """Rows of 2 x 100 lanes (256 tiled) under a budget of 4 chunks at that
    width step at 4 where the cap says 16: the same sums, to float32
    reassociation, over a packed src-keyed plan with pad chunks and an
    empty window; the row-less call keeps the cap."""
    import jax
    monkeypatch.setattr(em, "_PLAN_CB_SUM", 16)
    src, dst, rows = _edges("hub", seed=8)
    src = np.where(src // VB == 5, src + VB, src)       # window 5: empty
    E, K, F = dst.size, 2, 100
    plans = em.build_gat_plans(src, dst, rows, rows)
    splan = em._pad_posplan(plans.src_obi, plans.src_edst, plans.src_pos,
                            plans.src_nid, 5)           # not a step multiple
    C = splan[0].shape[0]
    assert C > 64 and C % 16 and 5 in np.asarray(splan[0])
    rng = np.random.default_rng(4)
    w, r = (jnp.asarray(rng.standard_normal((K, E)), jnp.float32)
            for _ in range(2))
    x = jnp.asarray(rng.standard_normal((rows, K, F)), jnp.float32)
    ride = r if form == "ride" else None

    def rowsum(w, x, r):
        return em._plan_sum(w, x, *splan, rows, "highest", ride=r)

    def plain(r):
        return em._plan_sum(r, None, *splan, rows, "highest")

    want = rowsum(w, x, ride)
    assert _scan_lengths(rowsum, w, x, ride) == [-(-C // 16)]
    monkeypatch.setattr(em, "_PLAN_SUM_BLOCK_BYTES", 4 * EB * 256 * 4)
    assert em.plan_sum_step(K * F) == 4
    assert _scan_lengths(rowsum, w, x, ride) == [-(-C // 4)]
    assert _scan_lengths(plain, r) == [-(-C // 16)]       # no rows: the cap
    got = rowsum(w, x, ride)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)
    assert not np.asarray(jax.tree.leaves(got)[0])[5 * VB:6 * VB].any()


def _sub_jaxprs(param):
    from jax.extend import core as jcore
    if isinstance(param, jcore.ClosedJaxpr):
        yield param.jaxpr
    elif isinstance(param, jcore.Jaxpr):
        yield param
    elif isinstance(param, (tuple, list)):
        for p in param:
            yield from _sub_jaxprs(p)


def _small_steps(monkeypatch):
    """Several scan steps over the small test graphs."""
    for name, cb in (("_PLAN_CB_BLOCKS", 8), ("_PLAN_CB_SUM", 16),
                     ("_PLAN_CB_MAX", 16)):
        monkeypatch.setattr(em, name, cb)
    monkeypatch.setattr(em, "_LANE_GATHER_CHUNK", 4096)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in _sub_jaxprs(p):
                yield from _eqns(sub)


def _scans_and_their_gathers(jaxpr):
    """For every scan in the jaxpr, nested ones included: the shapes of the
    arrays its body gathers from."""
    return [[tuple(g.invars[0].aval.shape)
             for g in _eqns(eqn.params["jaxpr"].jaxpr)
             if g.primitive.name == "gather"]
            for eqn in _eqns(jaxpr) if eqn.primitive.name == "scan"]


def _walk(jaxpr, tainted, gathers, shapes):
    """Follow everything computed from the ``tainted`` variables through
    ``jaxpr`` and its sub-jaxprs (an equation with a tainted input taints
    its outputs): collect the gathers whose INDICES are tainted, and every
    result shape.  Returns the tainted outvars' flags."""
    from jax.extend.core import Literal
    tainted = set(tainted)
    for eqn in jaxpr.eqns:
        ins = [not isinstance(v, Literal) and v in tainted
               for v in eqn.invars]
        if eqn.primitive.name == "gather" and ins[1]:
            gathers.append(str(eqn))
        hit = any(ins)
        for p in eqn.params.values():
            for sub in _sub_jaxprs(p):
                # operands map onto the sub-jaxpr's inputs from the end
                # (scan, pjit, custom calls: one for one; while and cond
                # put their own constants and predicate in front)
                flags = ([False] * len(sub.invars) + ins)[-len(sub.invars):] \
                    if len(sub.invars) <= len(ins) else [hit] * len(sub.invars)
                inner = _walk(sub, [v for v, f in zip(sub.invars, flags)
                                    if f], gathers, shapes)
                hit = hit or any(inner)
        for v in eqn.outvars:
            if getattr(v.aval, "shape", None) is not None:
                shapes.append((eqn.primitive.name, tuple(v.aval.shape)))
            if hit:
                tainted.add(v)
    return [not isinstance(v, Literal) and v in tainted
            for v in jaxpr.outvars]


@pytest.mark.parametrize("dropout", [0.0, 0.6])
def test_no_gather_of_the_plan_path_is_indexed_by_edge_dst(dropout,
                                                           monkeypatch):
    """jax.grad of gat_attend_plan, forward and hand-derived backward: no
    gather takes edge_dst, or anything computed from it, as its indices,
    and none takes edge_src either since the score's source half rides
    u's rows (the plans' nid does: the walk must find those, or it sees
    nothing); and what the plan reads add has no edge-sized array with the
    heads, or a feature row, on the lane axis: rows of K*F exist a scan
    step at a time only, as in _plan_sum."""
    import jax
    _small_steps(monkeypatch)
    src, dst, rows = _edges("hub", seed=6)
    K, F, E = 4, 16, dst.size            # no other axis of the path is 4 long
    step_slots = 16 * EB
    assert E > 2 * step_slots > 2 * rows
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((rows, K, F)), jnp.float32)
    a_src = jnp.asarray(rng.standard_normal((K, F)), jnp.float32)
    a_dst = jnp.asarray(rng.standard_normal((K, F)), jnp.float32)
    plans = em.build_gat_plans(src, dst, rows, rows)
    drop = (jax.random.PRNGKey(5), dropout) if dropout else None

    def loss(hh, s, d, e_src, e_dst, nid):
        return jnp.sum(em.gat_attend_plan(
            hh, hh, s, d, plans._replace(dst_nid=nid), (e_src, e_dst), 0.2,
            "default", drop) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
        h, a_src, a_dst, jnp.asarray(src, jnp.int32),
        jnp.asarray(dst, jnp.int32), plans.dst_nid).jaxpr
    by_dst, by_src, by_nid, shapes = [], [], [], []
    _walk(jaxpr, [jaxpr.invars[4]], by_dst, shapes)
    _walk(jaxpr, [jaxpr.invars[3]], by_src, [])
    _walk(jaxpr, [jaxpr.invars[5]], by_nid, [])
    assert by_nid, "the walk no longer finds the gathers by the plan's nid"
    assert not by_dst, by_dst[:3]
    assert not by_src, by_src[:3]
    assert sum(1 for _, s in shapes if s == (K, E)) >= 8
    heads_last = [(p, s) for p, s in shapes if len(s) >= 2 and s[-1] == K
                  and int(np.prod(s[:-1])) >= step_slots]
    assert not heads_last, heads_last[:5]
    rows_last = [(p, s) for p, s in shapes if len(s) >= 2 and s[-1] == K * F
                 and int(np.prod(s[:-1])) > step_slots]
    assert not rows_last, rows_last[:5]



@pytest.mark.parametrize("heads,scans", [(1, 1), (4, 1), (8, 2)])
def test_dast_rides_dtables_scan_while_the_stack_fits_a_tile(heads, scans,
                                                             monkeypatch):
    """jax.grad of gat_attend_plan: the scans that gather per-edge weights
    by COLUMN are the backward's walks of the src-keyed plan (every
    dst-keyed read is by aligned blocks).  While 2K rows fit a tile's 8
    sublanes it is ONE scan reading the stacked [2K, E] array; at K = 8 the
    two scans it always was, [K, E] each (the stack would be one more
    edge-sized array at the step's fullest moment: gat_src_scans)."""
    import jax
    _small_steps(monkeypatch)
    assert em.gat_src_scans(heads) == scans
    src, dst, rows = _edges("hub", seed=6)
    K, F, E = heads, 4, dst.size
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((rows, K, F)), jnp.float32)
    a_s, a_d = (jnp.asarray(rng.standard_normal((K, F)), jnp.float32)
                for _ in range(2))
    plans = em.build_gat_plans(src, dst, rows, rows)
    ids = (jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32))

    def loss(h, a_s, a_d):
        return jnp.sum(em.gat_attend_plan(h, h, a_s, a_d, plans, ids, 0.2,
                                          drop=(jax.random.PRNGKey(1), 0.5))
                       ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(h, a_s, a_d)
    by_column = [g for g in _scans_and_their_gathers(jaxpr.jaxpr)
                 if any(len(s) in (1, 2) and s[-1] == E for s in g)]
    assert len(by_column) == scans
    want = [(2 * K, E)] if scans == 1 else [(K, E)]
    assert all([s for s in g if s[-1] == E] == want for g in by_column)


def test_the_edge_sharded_backward_keeps_its_two_calls_and_its_numbers():
    """parallel/spmd.py's _egat_bwd sums dast and dtable in two _plan_sum
    calls WITHOUT riding weights (_scatter_to_owner sits between them): its
    first step's gradients are the single-device road's, which sums both in
    one scan.  After one Adam step from zero moments ``m / (1 - beta1)`` is
    the gradient the step applied."""
    import jax

    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gat
    from roc_tpu.parallel.spmd import SpmdTrainer
    from roc_tpu.train.config import Config
    from roc_tpu.train.driver import Trainer
    ds = datasets.synthetic("t", 220, 4.0, 8, 4, n_train=30, n_val=30,
                            n_test=30, seed=3)
    layers = [ds.in_dim, 6, ds.num_classes]
    base = dict(layers=layers, num_epochs=1, dropout_rate=0.0,
                eval_every=10**9, weight_decay=0.0,
                aggregate_backend="matmul")
    one = Trainer(Config(**base, edge_shard="off"), ds,
                  build_gat(layers, 0.0, heads=2))
    four = SpmdTrainer(Config(**base, num_parts=4, edge_shard=True), ds,
                       build_gat(layers, 0.0, heads=2))
    assert four.gdata.mode == "edge" and four.gdata.gat_plans is not None
    assert one.gdata.gat_plans is not None
    # what each says of itself: one scan an op against two
    assert one.attention_info()["src_scans"] == 2
    assert four.attention_info()["src_scans"] == 4
    # and forward: su and the max's broadcast against _egat_fwd's five
    assert one.attention_info()["fwd_scans"] == 4
    assert four.attention_info()["fwd_scans"] == 10
    one.run_epoch()
    four.run_epoch()
    m1, m4 = (jax.device_get(t.opt_state.m) for t in (one, four))
    assert set(m1) == set(m4)
    for name in m1:
        a, b = np.asarray(m4[name], np.float64), np.asarray(m1[name],
                                                            np.float64)
        assert np.linalg.norm(b) > 0, name
        assert np.linalg.norm(a - b) <= 2e-5 * np.linalg.norm(b), name


# -- the forward's one scan: score, max, normaliser and u -------------------

def _gat_case(heads, width, halo, seed):
    """(h, table, a_src, a_dst, src, dst, rows) over the hub graph: the
    table is h, or h and 37 rows more that some edges come from (a shard's
    ``x ++ halo``, as SpmdTrainer passes it)."""
    src, dst, rows = _edges("hub", seed=seed)
    rng = np.random.default_rng(seed)
    extra = 37 if halo else 0
    if halo:
        far = rng.random(src.size) < 0.2
        src = np.where(far, rows + rng.integers(0, extra, src.size), src)
    table = rng.standard_normal((rows + extra, heads, width))
    h = table[:rows] if not halo else rng.standard_normal(
        (rows, heads, width))
    a_src, a_dst = (rng.standard_normal((heads, width)) for _ in range(2))
    return tuple(jnp.asarray(a, jnp.float32)
                 for a in (h, table, a_src, a_dst)) + (src, dst, rows)


@pytest.mark.parametrize("precision", ["highest", "default"])
@pytest.mark.parametrize("dropout", [0.0, 0.6])
@pytest.mark.parametrize("halo", [False, True])
@pytest.mark.parametrize("heads,width", [(8, 8), (1, 41)])
def test_the_fused_forward_and_its_gradients_against_the_xla_road(
        heads, width, halo, dropout, precision, monkeypatch):
    """gat_attend_plan (the forward one `su` scan of several steps, then
    the hand-derived backward) against gat_attend, the xla road, given the
    same key: values within 64 ulps of the output's scale, every gradient
    within 2e-5 of its norm (float32 reassociation; on the CPU "default"
    is float32 too, so it is the path and not the rounding that is held);
    rows with no in-edge read exact zeros."""
    import jax
    _small_steps(monkeypatch)
    h, table, a_src, a_dst, src, dst, rows = _gat_case(heads, width, halo,
                                                       seed=7)
    plans = em.build_gat_plans(src, dst, rows, table.shape[0])
    assert plans.dst_obi.shape[0] > 2 * em._PLAN_CB_BLOCKS  # several steps
    sj, dj = jnp.asarray(src), jnp.asarray(dst)
    drop = (jax.random.PRNGKey(3), dropout) if dropout else None

    def plan(hh, tt, s, d):
        return em.gat_attend_plan(hh, tt, s, d, plans, (sj, dj), 0.2,
                                  precision, drop)

    def xla(hh, tt, s, d):
        return em.gat_attend(hh, tt, sj, dj, rows, s, d, 0.2, drop)

    args = (h, table, a_src, a_dst)
    got, want = (np.asarray(f(*args)) for f in (plan, xla))
    assert np.isfinite(got).all()
    assert not got[np.setdiff1d(np.arange(rows), dst)].any()
    scale = np.abs(want).max() * np.finfo(np.float32).eps
    assert np.abs(got - want).max() <= 64 * scale
    grads = [jax.grad(lambda *a, f=f: jnp.sum(jnp.sin(f(*a))),
                      argnums=(0, 1, 2, 3))(*args) for f in (plan, xla)]
    for name, a, b in zip(("dh", "dtable", "da_src", "da_dst"), *grads):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.linalg.norm(a - b) <= 2e-5 * np.linalg.norm(b), name


def test_a_gat_hub_whose_largest_score_comes_last_is_rescaled(monkeypatch):
    """A hub row of 6,000 in-edges over at least three scan steps, its
    largest scores (110) on its last 16 edges, in its LAST step, every
    other score 77 to 83: exp of the largest overflows float32, so every
    sum is taken against a running max, and what the earlier steps summed
    must be scaled by exp(m_old - m_new) (about e^-30) when the last step
    raises it.  The hub then reads row 0's values, as the xla road does,
    and the rows without an in-edge read 0, not NaN."""
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
    # a_src . h_j = t[j], a_dst . h_i = 0: the score is t[src]
    h = jnp.asarray(np.repeat(t[:, :, None] / F, F, axis=2), jnp.float32)
    a_src = jnp.ones((K, F), jnp.float32)
    a_dst = jnp.zeros((K, F), jnp.float32)
    plans = em.build_gat_plans(src, dst, rows, rows)
    obi, edst, pos = (np.asarray(a) for a in (
        plans.dst_obi, plans.dst_edst, plans.dst_pos))
    steps = np.unique(np.flatnonzero(obi == hub // VB) // em._PLAN_CB_BLOCKS)
    assert steps.size >= 3
    chunk, = np.flatnonzero(((pos == last[-1]) & (edst < VB)).any(axis=1))
    assert chunk // em._PLAN_CB_BLOCKS == steps[-1]
    with np.errstate(over="ignore"):
        assert np.isinf(np.exp(np.float32(t.max())))
    sj, dj = jnp.asarray(src), jnp.asarray(dst)
    got = np.asarray(em.gat_attend_plan(h, h, a_src, a_dst, plans, (sj, dj),
                                        0.2, "highest"))
    assert np.isfinite(got).all()
    assert not got[np.setdiff1d(np.arange(rows), dst)].any()
    want = np.asarray(em.gat_attend(h, h, sj, dj, rows, a_src, a_dst, 0.2))
    scale = np.abs(want).max() * np.finfo(np.float32).eps
    assert np.abs(got - want).max() <= 64 * scale
    np.testing.assert_allclose(got[hub], np.asarray(h)[0], rtol=1e-6)


@pytest.mark.parametrize("precision", ["highest", "default"])
def test_only_the_feature_sums_take_the_ops_precision(precision,
                                                      monkeypatch):
    """jax.grad of gat_attend_plan: the op's ``precision`` reaches two
    products, u's in the forward's one scan and dtable's in the src scan
    (under `fast`, "default": one bf16 rounding of each product, as the
    feature sums always took it), and every other dot of the rule, the
    score's, the max's, the normaliser's and every one-hot spread, stays
    at "highest"."""
    import jax
    from jax import lax
    _small_steps(monkeypatch)
    h, table, a_src, a_dst, src, dst, rows = _gat_case(8, 8, False, seed=4)
    plans = em.build_gat_plans(src, dst, rows, rows)
    ids = (jnp.asarray(src), jnp.asarray(dst))

    def loss(hh, s, d):
        return jnp.sum(em.gat_attend_plan(hh, hh, s, d, plans, ids, 0.2,
                                          precision) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
        h, a_src, a_dst).jaxpr
    found = {}
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "dot_general":
            p = eqn.params["precision"]
            key = p[0] if isinstance(p, tuple) else p
            found[key] = found.get(key, 0) + 1
    if precision == "highest":
        assert set(found) == {lax.Precision.HIGHEST}
    else:
        assert found[lax.Precision.DEFAULT] == 2
        assert set(found) == {lax.Precision.DEFAULT, lax.Precision.HIGHEST}
