"""Edge-tensor ops + GAT model tests.

The reference leaves edge tensors latent (create_edge_tensor,
gnn.cc:534-589, never produced by a live op); these tests pin the TPU
realization: edge softmax and attention aggregation against dense NumPy,
sharded == single-device equality (the edge-partitioned path), and
end-to-end GAT training on the synthetic oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu import ops
from roc_tpu.graph import datasets
from roc_tpu.models import build_gat
from roc_tpu.parallel.spmd import SpmdTrainer
from roc_tpu.train.config import Config
from roc_tpu.train.driver import Trainer


def graph_and_x(seed=3, n=150, h=6):
    ds = datasets.synthetic("t", n, 4.0, 8, 4, n_train=30, n_val=30,
                            n_test=30, seed=seed)
    g = ds.graph
    x = np.random.default_rng(seed).normal(size=(g.num_nodes, h)).astype(
        np.float32)
    return ds, g, x


def test_edge_softmax_normalizes():
    _, g, _ = graph_and_x()
    rng = np.random.default_rng(0)
    scores = rng.normal(size=(g.num_edges, 3)).astype(np.float32)
    alpha = np.asarray(ops.edge_softmax(jnp.asarray(scores),
                                        jnp.asarray(g.dst_idx), g.num_nodes))
    # per-destination sums == 1 wherever the vertex has in-edges
    sums = np.zeros((g.num_nodes, 3), np.float32)
    np.add.at(sums, g.dst_idx, alpha)
    has_edges = np.diff(g.row_ptr) > 0
    np.testing.assert_allclose(sums[has_edges], 1.0, rtol=1e-5)
    # matches a direct NumPy softmax per destination
    v = int(np.argmax(np.diff(g.row_ptr)))
    sl = slice(int(g.row_ptr[v]), int(g.row_ptr[v + 1]))
    expect = np.exp(scores[sl] - scores[sl].max(0))
    expect /= expect.sum(0)
    np.testing.assert_allclose(alpha[sl], expect, rtol=1e-5)


def test_gat_attend_matches_dense():
    _, g, x = graph_and_x(h=8)
    K, F = 2, 4
    h = x.reshape(g.num_nodes, K, F)
    rng = np.random.default_rng(7)
    a_src = rng.normal(size=(K, F)).astype(np.float32)
    a_dst = rng.normal(size=(K, F)).astype(np.float32)
    out = np.asarray(ops.gat_attend(
        jnp.asarray(h), jnp.asarray(h), jnp.asarray(g.col_idx),
        jnp.asarray(g.dst_idx), g.num_nodes, jnp.asarray(a_src),
        jnp.asarray(a_dst), 0.2))

    # dense reference
    s = np.einsum("nkf,kf->nk", h, a_dst)[g.dst_idx] \
        + np.einsum("nkf,kf->nk", h, a_src)[g.col_idx]
    s = np.where(s >= 0, s, 0.2 * s)
    expect = np.zeros_like(h)
    for v in range(g.num_nodes):
        sl = slice(int(g.row_ptr[v]), int(g.row_ptr[v + 1]))
        if sl.start == sl.stop:
            continue
        a = np.exp(s[sl] - s[sl].max(0))
        a /= a.sum(0)
        expect[v] = np.einsum("ek,ekf->kf", a, h[g.col_idx[sl]])
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-5)


def test_chunked_gat_matches_dense(monkeypatch):
    """The memory-bounded edge-chunked GAT path (taken automatically above
    2^28 gathered elements — Reddit-scale GAT would OOM a 16 GB chip
    otherwise) must match the dense path up to float reassociation, in
    value AND gradient."""
    from roc_tpu.ops import edge as edge_mod

    _, g, x = graph_and_x(h=8)
    K, F = 2, 4
    h = jnp.asarray(x.reshape(g.num_nodes, K, F))
    rng = np.random.default_rng(11)
    a_src = jnp.asarray(rng.normal(size=(K, F)).astype(np.float32))
    a_dst = jnp.asarray(rng.normal(size=(K, F)).astype(np.float32))
    args = (h, h, jnp.asarray(g.col_idx), jnp.asarray(g.dst_idx),
            g.num_nodes, a_src, a_dst, 0.2)

    dense = np.asarray(ops.gat_attend(*args))
    # force the chunked path with a tiny chunk so the scan has many steps
    # (floor included — otherwise the 1024-edge minimum masks the shrink)
    monkeypatch.setattr(edge_mod, "_GAT_CHUNK_THRESHOLD_ELEMS", 1)
    monkeypatch.setattr(edge_mod, "_GAT_CHUNK_TARGET_ELEMS", 16 * K * F)
    monkeypatch.setattr(edge_mod, "_GAT_CHUNK_MIN", 16)
    chunked = np.asarray(ops.gat_attend(*args))
    np.testing.assert_allclose(chunked, dense, rtol=1e-5, atol=1e-5)

    def loss(hh):
        return jnp.sum(ops.gat_attend(hh, hh, jnp.asarray(g.col_idx),
                                      jnp.asarray(g.dst_idx), g.num_nodes,
                                      a_src, a_dst, 0.2) ** 2)
    gc = jax.grad(loss)(h)                        # chunked (threshold = 1)
    monkeypatch.setattr(edge_mod, "_GAT_CHUNK_THRESHOLD_ELEMS", 1 << 60)
    gd = jax.grad(loss)(h)                        # dense
    np.testing.assert_allclose(np.asarray(gc), np.asarray(gd),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("configs", [
    # fast lane: one representative shape; the other shapes ride the slow
    # lane (each config compiles 6 programs — value+grad for both impls)
    [(3, 150, 3, 5)],
    pytest.param([(7, 333, 1, 16), (11, 64, 4, 3)], marks=pytest.mark.slow),
])
def test_gat_plan_matches_dense_and_grads(configs):
    """Plan-backend attention (ops.gat_attend_plan — scatter-free chunk-plan
    softmax/aggregation) must match the dense oracle in value and in every
    gradient (its backward is hand-derived, not autodiff)."""
    for seed, n, K, F in configs:
        ds = datasets.synthetic("t", n, 4.0, 8, 4, n_train=10, n_val=10,
                                n_test=10, seed=seed)
        g = ds.graph
        N = g.num_nodes
        rng = np.random.default_rng(seed)
        h = jnp.asarray(rng.normal(size=(N, K, F)).astype(np.float32))
        a_s = jnp.asarray(rng.normal(size=(K, F)).astype(np.float32))
        a_d = jnp.asarray(rng.normal(size=(K, F)).astype(np.float32))
        es, ed = jnp.asarray(g.col_idx), jnp.asarray(g.dst_idx)
        plans = ops.build_gat_plans(g.col_idx, g.dst_idx, N, N)
        ref = ops.gat_attend(h, h, es, ed, N, a_s, a_d, 0.2)
        got = ops.gat_attend_plan(h, h, a_s, a_d, plans, (es, ed), 0.2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

        def loss_ref(h, a_s, a_d):
            return jnp.sum(jnp.sin(
                ops.gat_attend(h, h, es, ed, N, a_s, a_d, 0.2)))

        def loss_plan(h, a_s, a_d):
            return jnp.sum(jnp.sin(
                ops.gat_attend_plan(h, h, a_s, a_d, plans, (es, ed), 0.2)))
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(h, a_s, a_d)
        gp = jax.grad(loss_plan, argnums=(0, 1, 2))(h, a_s, a_d)
        for a, b in zip(gr, gp):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-3, atol=1e-4)


def test_gat_plan_multistep_scan_matches_oracle():
    """A graph big enough that _plan_max/_plan_sum run MULTIPLE scan steps
    (chunk count > the per-step block), with large-magnitude scores so a
    wrong softmax max cannot hide behind shift-invariance.  Pins the
    window-vs-row accumulator indexing (caught broken in review: every
    step after the first wrote maxima to the wrong windows)."""
    from roc_tpu.ops import edge as em
    ds = datasets.synthetic("t", 2000, 20.0, 8, 4, n_train=10, n_val=10,
                            n_test=10, seed=5)
    g = ds.graph
    N, K, F = g.num_nodes, 2, 4
    plans = ops.build_gat_plans(g.col_idx, g.dst_idx, N, N)
    assert plans.dst_obi.shape[0] > em._PLAN_CB_MAX, \
        "graph too small to exercise the multi-step path"
    rng = np.random.default_rng(5)
    # 20x scale: exp(s - wrong_m) visibly diverges or overflows
    h = jnp.asarray(20 * rng.normal(size=(N, K, F)).astype(np.float32))
    a_s = jnp.asarray(rng.normal(size=(K, F)).astype(np.float32))
    a_d = jnp.asarray(rng.normal(size=(K, F)).astype(np.float32))
    es, ed = jnp.asarray(g.col_idx), jnp.asarray(g.dst_idx)
    # _plan_max against the NumPy segment-max oracle
    s = np.einsum("nkf,kf->nk", np.asarray(h), np.asarray(a_d))[g.dst_idx] \
        + np.einsum("nkf,kf->nk", np.asarray(h), np.asarray(a_s))[g.col_idx]
    s = np.where(s >= 0, s, 0.2 * s).astype(np.float32)
    mo = np.full((N, K), -np.inf, np.float32)
    np.maximum.at(mo, g.dst_idx, s)
    # per-edge arrays of the plan path are [K, E] (edges on the lane axis)
    m = np.asarray(em._plan_max(jnp.asarray(s.T), plans.dst_obi,
                                plans.dst_edst, plans.dst_pos, N)).T
    np.testing.assert_allclose(m, mo, rtol=1e-5, atol=1e-5)
    # end-to-end against the dense oracle
    ref = ops.gat_attend(h, h, es, ed, N, a_s, a_d, 0.2)
    got = ops.gat_attend_plan(h, h, a_s, a_d, plans, (es, ed), 0.2)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.slow
def test_gat_plan_training_matches_xla():
    """End-to-end GAT training with -aggr-backend matmul (which routes
    attention through the plan backend) must track the xla-backend run."""
    ds, g, _ = graph_and_x(n=200)
    layers = [ds.in_dim, 8, ds.num_classes]

    def run(backend):
        cfg = Config(layers=layers, num_epochs=5, dropout_rate=0.0,
                     learning_rate=0.01, weight_decay=0.0, eval_every=10**9,
                     model="gat", heads=2, aggregate_backend=backend)
        tr = Trainer(cfg, ds, build_gat(layers, 0.0, heads=2))
        return [float(tr.run_epoch()) for _ in range(5)], tr

    lx, _ = run("xla")
    lm, tr = run("matmul")
    assert tr.gdata.gat_plans is not None, "plan backend not engaged"
    np.testing.assert_allclose(lm, lx, rtol=1e-3)


def test_gat_plan_sharded_equals_single():
    """Plan attention under halo vertex sharding: 4-part run must match the
    single-device xla run epoch for epoch."""
    ds, g, _ = graph_and_x(n=220)
    layers = [ds.in_dim, 6, ds.num_classes]
    cfg1 = Config(layers=layers, num_epochs=2, dropout_rate=0.0,
                  eval_every=10**9)
    cfgP = Config(layers=layers, num_epochs=2, dropout_rate=0.0,
                  eval_every=10**9, num_parts=4, halo=True,
                  aggregate_backend="matmul")
    t1 = Trainer(cfg1, ds, build_gat(layers, 0.0, heads=2))
    tp = SpmdTrainer(cfgP, ds, build_gat(layers, 0.0, heads=2))
    assert tp.gdata.gat_plans is not None, "plan backend not engaged"
    for i in range(2):
        l1, lp = float(t1.run_epoch()), float(tp.run_epoch())
        np.testing.assert_allclose(lp, l1, rtol=1e-4, err_msg=f"epoch {i}")
    m1 = jax.device_get(t1.evaluate())
    mp = jax.device_get(tp.evaluate())
    assert int(m1.train_correct) == int(mp.train_correct)
    assert int(m1.val_correct) == int(mp.val_correct)


def test_gat_ring_attention_equals_single():
    """-exchange ring + GAT = literal ring attention (online softmax over
    rotating shards, two-buffer memory, no source table).  Must train
    equal to the single-device and halo runs up to fp32 reassociation."""
    ds, g, _ = graph_and_x(n=220)
    layers = [ds.in_dim, 6, ds.num_classes]
    base = dict(layers=layers, num_epochs=3, dropout_rate=0.0,
                eval_every=10**9, edge_shard="off")
    t1 = Trainer(Config(**base), ds, build_gat(layers, 0.0, heads=2))
    th = SpmdTrainer(Config(**base, num_parts=4, halo=True), ds,
                     build_gat(layers, 0.0, heads=2))
    tr = SpmdTrainer(Config(**base, num_parts=4, exchange="ring"), ds,
                     build_gat(layers, 0.0, heads=2))
    assert tr.gdata.mode == "ring"
    for i, rtol in enumerate((2e-5, 5e-3, 5e-3)):
        l1 = float(t1.run_epoch())
        lh = float(th.run_epoch())
        lr = float(tr.run_epoch())
        np.testing.assert_allclose(lr, l1, rtol=rtol, err_msg=f"epoch {i}")
        np.testing.assert_allclose(lr, lh, rtol=rtol, err_msg=f"epoch {i}")
    m1 = jax.device_get(t1.evaluate())
    mr = jax.device_get(tr.evaluate())
    assert int(m1.val_correct) == int(mr.val_correct)


def test_gat_edge_shard_equals_single():
    """-edge-shard + GAT (the last model x distribution cell): block-local
    scores, pmax softmax shift, psum_scatter normalizer/output.  Must
    train equal to the single-device and halo runs."""
    ds, g, _ = graph_and_x(n=220)
    layers = [ds.in_dim, 6, ds.num_classes]
    base = dict(layers=layers, num_epochs=3, dropout_rate=0.0,
                eval_every=10**9)
    t1 = Trainer(Config(**base, edge_shard="off"), ds,
                 build_gat(layers, 0.0, heads=2))
    te = SpmdTrainer(Config(**base, num_parts=4, edge_shard=True), ds,
                     build_gat(layers, 0.0, heads=2))
    assert te.gdata.mode == "edge"
    for i, rtol in enumerate((2e-5, 5e-3, 5e-3)):
        l1, le = float(t1.run_epoch()), float(te.run_epoch())
        np.testing.assert_allclose(le, l1, rtol=rtol, err_msg=f"epoch {i}")
    m1 = jax.device_get(t1.evaluate())
    me = jax.device_get(te.evaluate())
    assert int(m1.val_correct) == int(me.val_correct)


def test_gat_edge_shard_plan_equals_single_and_scatter_free():
    """Edge-sharded GAT on the PLAN backend (edge_gat_attend, round 4):
    must train equal to the single-device run, and the compiled sharded
    train step must contain no HLO scatter op — the autodiff-backward
    serialized-scatter pathology VERDICT r3 item 5 flagged is gone
    (reduce-scatter, the collective, is fine and expected)."""
    import re

    ds, g, _ = graph_and_x(n=220)
    layers = [ds.in_dim, 6, ds.num_classes]
    base = dict(layers=layers, num_epochs=3, dropout_rate=0.0,
                eval_every=10**9)
    t1 = Trainer(Config(**base, edge_shard="off"), ds,
                 build_gat(layers, 0.0, heads=2))
    te = SpmdTrainer(Config(**base, num_parts=4, edge_shard=True,
                            aggregate_backend="matmul"), ds,
                     build_gat(layers, 0.0, heads=2))
    assert te.gdata.mode == "edge" and te.gdata.gat_plans is not None
    for i, rtol in enumerate((2e-5, 5e-3, 5e-3)):
        l1, le = float(t1.run_epoch()), float(te.run_epoch())
        np.testing.assert_allclose(le, l1, rtol=rtol, err_msg=f"epoch {i}")
    m1 = jax.device_get(t1.evaluate())
    me = jax.device_get(te.evaluate())
    assert int(m1.val_correct) == int(me.val_correct)

    # compiled-text check: no scatter op anywhere in the fwd+bwd step
    # (matches " scatter(" but not "reduce-scatter(" / "select-and-scatter(")
    txt = te._train_step.lower(
        te.params, te.opt_state, te.x, te.labels, te.mask, te.gdata,
        jax.random.key(0), jnp.float32(0.01),
        np.float32(1.0)).compile().as_text()
    hits = re.findall(r"(?<![\w-])scatter\(", txt)
    assert not hits, f"compiled step still contains {len(hits)} scatter ops"


@pytest.mark.slow
def test_gat_plan_perhost_equals_full_load(tmp_path):
    """Plan attention under -perhost (per-host `.lux` slice loading):
    the per-host-built, floor-padded plans must train identically to the
    full-load sharded run."""
    from roc_tpu.graph import lux

    ds, g, _ = graph_and_x(n=240)
    prefix = str(tmp_path / "g")
    lux.write_dataset(prefix, ds.graph, ds.features, ds.label_ids, ds.mask)
    layers = [ds.in_dim, 6, ds.num_classes]
    base = dict(layers=layers, num_epochs=2, dropout_rate=0.0,
                eval_every=10**9, num_parts=4, halo=True,
                aggregate_backend="matmul")
    tp = SpmdTrainer(Config(**base), ds, build_gat(layers, 0.0, heads=2))
    from roc_tpu.graph import datasets as dsets
    ds_stub = dsets.load_roc_dataset(prefix, ds.in_dim, ds.num_classes,
                                     graph_stub=True)
    th = SpmdTrainer(Config(**base, perhost_load=True, filename=prefix),
                     ds_stub, build_gat(layers, 0.0, heads=2))
    assert th.gdata.gat_plans is not None, "perhost plan attention off"
    for i in range(2):
        lp, lh = float(tp.run_epoch()), float(th.run_epoch())
        np.testing.assert_allclose(lh, lp, rtol=1e-4, err_msg=f"epoch {i}")


def test_gat_training_learns():
    ds, g, _ = graph_and_x(n=200)
    cfg = Config(layers=[ds.in_dim, 8, ds.num_classes], num_epochs=30,
                 dropout_rate=0.0, learning_rate=0.01, weight_decay=0.0,
                 eval_every=10**9, model="gat", heads=2)
    tr = Trainer(cfg, ds, build_gat(cfg.layers, 0.0, heads=2))
    first = float(tr.run_epoch())
    for _ in range(29):
        last = float(tr.run_epoch())
    assert last < first * 0.5, (first, last)
    m = jax.device_get(tr.evaluate())
    assert int(m.train_correct) / max(int(m.train_all), 1) > 0.6


@pytest.mark.parametrize("halo", [
    # all_gather exchange rides the slow lane: same code path shape as
    # halo, and every non-GAT sharded test covers halo=False fast
    pytest.param(False, marks=pytest.mark.slow), True])
def test_gat_sharded_equals_single(halo):
    ds, g, _ = graph_and_x(n=220)
    layers = [ds.in_dim, 6, ds.num_classes]
    cfg1 = Config(layers=layers, num_epochs=2, dropout_rate=0.0,
                  eval_every=10**9)
    cfgP = Config(layers=layers, num_epochs=2, dropout_rate=0.0,
                  eval_every=10**9, num_parts=4, halo=halo)
    t1 = Trainer(cfg1, ds, build_gat(layers, 0.0, heads=2))
    tp = SpmdTrainer(cfgP, ds, build_gat(layers, 0.0, heads=2))
    for i in range(2):
        l1, lp = float(t1.run_epoch()), float(tp.run_epoch())
        np.testing.assert_allclose(lp, l1, rtol=1e-4, err_msg=f"epoch {i}")
    m1 = jax.device_get(t1.evaluate())
    mp = jax.device_get(tp.evaluate())
    assert int(m1.train_correct) == int(mp.train_correct)
    assert int(m1.val_correct) == int(mp.val_correct)


def test_gat_cli_registry():
    from roc_tpu.models import build_model
    m = build_model("gat", [8, 4, 3], 0.5, heads=2)
    kinds = [op.kind for op in m.ops]
    assert "gat" in kinds and "aggregate" not in kinds
