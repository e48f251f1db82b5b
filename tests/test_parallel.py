"""SPMD tests on the 8-virtual-device CPU mesh.

Oracle: the sharded trainer must produce the same losses/metrics as the
single-device trainer (up to fp reassociation) — distribution is an
implementation detail of the same math.  Both comms modes (v0 all_gather
replication, v1 halo all_to_all) are tested against it and each other.
"""

import jax
import numpy as np
import pytest

from roc_tpu.graph import datasets
from roc_tpu.graph.partition import partition_graph
from roc_tpu.models import build_gcn
from roc_tpu.parallel.halo import build_halo_maps
from roc_tpu.parallel.spmd import SpmdTrainer
from roc_tpu.train.config import Config
from roc_tpu.train.driver import Trainer


def small_ds(seed=31, n=200, in_dim=12, classes=4):
    return datasets.synthetic("t", n, 3.0, in_dim, classes, n_train=50,
                              n_val=50, n_test=50, seed=seed)


def cfg_for(ds, parts, halo, epochs=5):
    return Config(layers=[ds.in_dim, 8, ds.num_classes], num_epochs=epochs,
                  learning_rate=0.01, weight_decay=5e-4, dropout_rate=0.0,
                  eval_every=10**9, num_parts=parts, halo=halo)


def test_halo_maps_cover_all_remote_sources():
    ds = small_ds()
    part = partition_graph(ds.graph, 4)
    halo = build_halo_maps(part)
    P, S, K = part.num_parts, part.shard_nodes, halo.K
    # Rebuild a global gather table per shard and check the remap reproduces
    # the original padded-global sources.
    x = np.arange(P * S, dtype=np.float32)  # identity "features"
    xs = x.reshape(P, S)
    for p in range(P):
        recv = np.stack([xs[q][halo.send_idx[q, p]] for q in range(P)])
        table = np.concatenate([xs[p], recv.reshape(-1)])
        reconstructed = table[halo.edge_src_local[p]]
        np.testing.assert_array_equal(reconstructed,
                                      x[part.edge_src[p]])


@pytest.mark.parametrize("halo", [False, True])
@pytest.mark.parametrize("parts", [
    2, 4,
    # the 8-part variant adds compile time, not new code paths (2 and 4
    # already cover uneven + even cuts); slow lane keeps it
    pytest.param(8, marks=pytest.mark.slow)])
def test_spmd_matches_single_device(parts, halo):
    ds = small_ds()
    ref = Trainer(cfg_for(ds, 1, False), ds,
                  build_gcn([ds.in_dim, 8, ds.num_classes], 0.0))
    sp = SpmdTrainer(cfg_for(ds, parts, halo), ds,
                     build_gcn([ds.in_dim, 8, ds.num_classes], 0.0))
    # identical initialization (same seed -> same glorot draws)
    np.testing.assert_allclose(
        np.asarray(ref.params["linear_0"]),
        np.asarray(jax.device_get(sp.params["linear_0"])), rtol=1e-6)
    for i in range(5):
        l_ref = float(ref.run_epoch())
        l_sp = float(sp.run_epoch())
        np.testing.assert_allclose(l_sp, l_ref, rtol=2e-3, err_msg=f"epoch {i}")
    m_ref = jax.device_get(ref.evaluate())
    m_sp = jax.device_get(sp.evaluate())
    assert int(m_sp.train_all) == int(m_ref.train_all)
    assert int(m_sp.val_all) == int(m_ref.val_all)
    assert int(m_sp.test_all) == int(m_ref.test_all)
    assert abs(int(m_sp.val_correct) - int(m_ref.val_correct)) <= 1
    np.testing.assert_allclose(float(m_sp.train_loss),
                               float(m_ref.train_loss), rtol=5e-3, atol=1e-2)


def test_sharded_grads_are_summed_once():
    """The gradient all-reduce must SUM the shards' gradients — once.
    Under check_vma (the xla and matmul backends) jax all-reduces the
    cotangent of a replicated parameter itself; an explicit psum on top
    multiplies the gradient by P.  Adam hides a scaled gradient except
    against the weight-decay term, so the pin uses a strong decay: every
    loss must track the single-device run to fp32 reassociation (the
    P-times-too-large gradient drifts to 1e-4 by epoch 8)."""
    ds = small_ds()

    def losses(parts):
        cfg = Config(layers=[ds.in_dim, 8, ds.num_classes],
                     learning_rate=0.01, weight_decay=0.5, dropout_rate=0.0,
                     eval_every=10**9, num_parts=parts,
                     aggregate_backend="xla")
        cls = SpmdTrainer if parts > 1 else Trainer
        tr = cls(cfg, ds, build_gcn(cfg.layers, 0.0))
        return [float(tr.run_epoch()) for _ in range(8)]

    np.testing.assert_allclose(losses(4), losses(1), rtol=5e-6)


def test_halo_equals_allgather_exactly():
    ds = small_ds(seed=7)
    m1 = build_gcn([ds.in_dim, 8, ds.num_classes], 0.0)
    m2 = build_gcn([ds.in_dim, 8, ds.num_classes], 0.0)
    a = SpmdTrainer(cfg_for(ds, 4, False), ds, m1)
    b = SpmdTrainer(cfg_for(ds, 4, True), ds, m2)
    for _ in range(3):
        la, lb = float(a.run_epoch()), float(b.run_epoch())
        np.testing.assert_allclose(la, lb, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(a.params["linear_1"])),
        np.asarray(jax.device_get(b.params["linear_1"])), rtol=1e-4,
        atol=1e-6)


def test_spmd_with_dropout_trains():
    ds = small_ds(seed=17)
    cfg = cfg_for(ds, 4, True, epochs=40)
    cfg.dropout_rate = 0.3
    tr = SpmdTrainer(cfg, ds, build_gcn(cfg.layers, cfg.dropout_rate))
    m0 = jax.device_get(tr.evaluate())
    for _ in range(40):
        tr.run_epoch()
    m1 = jax.device_get(tr.evaluate())
    acc0 = m0.val_correct / max(m0.val_all, 1)
    acc1 = m1.val_correct / max(m1.val_all, 1)
    assert acc1 > max(acc0, 0.5)


def test_halo_moves_fewer_rows_than_allgather():
    # The point of v1: for a partitioned graph the halo is a strict subset
    # of full replication.
    ds = small_ds(seed=3, n=400)
    part = partition_graph(ds.graph, 8)
    halo = build_halo_maps(part)
    full_rows = part.num_parts * part.shard_nodes * (part.num_parts - 1)
    assert halo.halo_rows_total < full_rows


@pytest.mark.parametrize("parts", [2, 3, 4, 8])
def test_fast_halo_builders_equal_reference(parts):
    """The native and vectorized-NumPy builders must be bit-identical to
    the original per-pair loop implementation (kept as the oracle)."""
    from roc_tpu import native
    from roc_tpu.parallel.halo import (_build_halo_maps_numpy,
                                       _build_halo_maps_reference)
    # without the native lib, build_halo_maps degenerates to the numpy arm
    # and the C++ path would pass with zero coverage — make that visible
    assert native.available(), "native lib not built: C++ halo path untested"
    ds = small_ds()
    part = partition_graph(ds.graph, parts)
    ref = _build_halo_maps_reference(part)
    for fast in (build_halo_maps(part), _build_halo_maps_numpy(part)):
        assert fast.K == ref.K
        assert fast.halo_rows_total == ref.halo_rows_total
        np.testing.assert_array_equal(fast.send_idx, ref.send_idx)
        np.testing.assert_array_equal(fast.edge_src_local, ref.edge_src_local)


def test_ring_exchange_matches_halo_and_single_device():
    """-exchange ring (ppermute rotation, parallel/ring.py) must train
    equal to the halo and single-device paths up to fp32 reassociation
    (partial sums accumulate per visiting shard)."""
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gcn
    from roc_tpu.parallel.spmd import SpmdTrainer
    from roc_tpu.train.config import Config
    from roc_tpu.train.driver import Trainer

    ds = datasets.synthetic("ring", 260, 4.0, 8, 4, n_train=50, n_val=50,
                            n_test=50, seed=6)
    layers = [8, 8, 4]
    base = dict(layers=layers, num_epochs=3, dropout_rate=0.0,
                eval_every=10 ** 9, edge_shard="off")
    t1 = Trainer(Config(**base), ds, build_gcn(layers, 0.0))
    th = SpmdTrainer(Config(**base, num_parts=4, halo=True), ds,
                     build_gcn(layers, 0.0))
    tr = SpmdTrainer(Config(**base, num_parts=4, exchange="ring"), ds,
                     build_gcn(layers, 0.0))
    assert tr.gdata.mode == "ring" and tr.gdata.ring_src is not None
    # first epoch tight; later epochs loose (fp32 reassociation amplifies
    # chaotically across epochs — same policy as the sage test below)
    for i, rtol in enumerate((2e-5, 5e-3, 5e-3)):
        l1 = float(t1.run_epoch())
        lh = float(th.run_epoch())
        lr = float(tr.run_epoch())
        np.testing.assert_allclose(lr, lh, rtol=rtol, err_msg=f"epoch {i}")
        np.testing.assert_allclose(lr, l1, rtol=rtol, err_msg=f"epoch {i}")


def test_overcommit_parts_per_device_match_single():
    """num_parts > devices (the reference's parts>GPUs overcommit,
    gnn.cc:61-63): 16 parts on the 8-device CPU mesh stack k=2 shard
    blocks per device and must train equal to single-device and to the
    one-part-per-device run — halo and allgather, GCN and sage-avg."""
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gcn, build_sage
    from roc_tpu.parallel.spmd import SpmdTrainer
    from roc_tpu.train.config import Config
    from roc_tpu.train.driver import Trainer

    ds = datasets.synthetic("over", 340, 4.0, 8, 4, n_train=60, n_val=60,
                            n_test=60, seed=9)
    layers = [8, 8, 4]
    base = dict(layers=layers, num_epochs=2, dropout_rate=0.0,
                eval_every=10 ** 9, edge_shard="off")
    for halo in (True, False):
        t1 = Trainer(Config(**base), ds, build_gcn(layers, 0.0))
        t8 = SpmdTrainer(Config(**base, num_parts=8, halo=halo), ds,
                         build_gcn(layers, 0.0))
        t16 = SpmdTrainer(Config(**base, num_parts=16, halo=halo), ds,
                          build_gcn(layers, 0.0))
        assert t16.k == 2, "overcommit not engaged"
        for i in range(2):
            l1 = float(t1.run_epoch())
            l8 = float(t8.run_epoch())
            l16 = float(t16.run_epoch())
            np.testing.assert_allclose(l16, l1, rtol=1e-4,
                                       err_msg=f"halo={halo} epoch {i}")
            np.testing.assert_allclose(l16, l8, rtol=1e-4,
                                       err_msg=f"halo={halo} epoch {i}")
    m1 = jax.device_get(t1.evaluate())
    m16 = jax.device_get(t16.evaluate())
    assert int(m1.val_correct) == int(m16.val_correct)

    # sage-avg rides the same overcommit path (plan-less xla backend here)
    t1s = Trainer(Config(**base, model="sage", aggr="avg"), ds,
                  build_sage(layers, 0.0, aggr="avg"))
    t16s = SpmdTrainer(Config(**base, model="sage", aggr="avg",
                              num_parts=16, halo=True), ds,
                       build_sage(layers, 0.0, aggr="avg"))
    for i in range(2):
        l1, l16 = float(t1s.run_epoch()), float(t16s.run_epoch())
        np.testing.assert_allclose(l16, l1, rtol=1e-4, err_msg=f"epoch {i}")


def test_chunked_paths_inside_shard_map(monkeypatch):
    """Regression (found at products shape, H=32): the memory-bounded
    chunked scan paths — _chunked_segment_sum and _chunked_gat_attend —
    must carry device-varying vma through their scans, or the sharded xla
    backend crashes the moment a SHARD's E*H crosses the chunk threshold
    (the round-3 products rehearsal happened to sit just under it).
    Thresholds are shrunk so the chunked paths run at test scale; losses
    must match the unchunked run."""
    import roc_tpu.ops.aggregate as agg
    import roc_tpu.ops.edge as em
    from roc_tpu.models import build_gat, build_gcn

    ds = datasets.synthetic("chunked-vma", 400, 6.0, 10, 4, n_train=80,
                            n_val=80, n_test=80, seed=17)
    base = dict(layers=[10, 8, 4], num_epochs=2, dropout_rate=0.0,
                eval_every=10**9, num_parts=4, halo=True,
                aggregate_backend="xla", edge_shard="off")

    ref = SpmdTrainer(Config(**base), ds, build_gcn(base["layers"], 0.0))
    losses = [float(ref.run_epoch()) for _ in range(2)]

    monkeypatch.setattr(agg, "_CHUNK_THRESHOLD_ELEMS", 1 << 10)
    tr = SpmdTrainer(Config(**base), ds, build_gcn(base["layers"], 0.0))
    for i in range(2):
        np.testing.assert_allclose(float(tr.run_epoch()), losses[i],
                                   rtol=1e-5, err_msg=f"gcn epoch {i}")

    refg = SpmdTrainer(Config(**base, model="gat"), ds,
                       build_gat(base["layers"], 0.0, heads=2))
    gl = [float(refg.run_epoch()) for _ in range(2)]
    monkeypatch.setattr(em, "_GAT_CHUNK_THRESHOLD_ELEMS", 1 << 10)
    monkeypatch.setattr(em, "_GAT_CHUNK_MIN", 64)
    trg = SpmdTrainer(Config(**base, model="gat"), ds,
                      build_gat(base["layers"], 0.0, heads=2))
    for i in range(2):
        np.testing.assert_allclose(float(trg.run_epoch()), gl[i],
                                   rtol=1e-4, err_msg=f"gat epoch {i}")


@pytest.mark.slow
def test_overcommit_gat_and_plan_backend():
    """Overcommit composes with the matmul plan backend and with GAT
    (plan attention per stacked part)."""
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gat, build_gcn
    from roc_tpu.parallel.spmd import SpmdTrainer
    from roc_tpu.train.config import Config
    from roc_tpu.train.driver import Trainer

    ds = datasets.synthetic("overg", 340, 4.0, 8, 4, n_train=60, n_val=60,
                            n_test=60, seed=11)
    layers = [8, 6, 4]
    base = dict(layers=layers, num_epochs=2, dropout_rate=0.0,
                eval_every=10 ** 9, edge_shard="off")
    # GCN on the matmul plan backend
    t1 = Trainer(Config(**base), ds, build_gcn(layers, 0.0))
    t16 = SpmdTrainer(Config(**base, num_parts=16, halo=True,
                             aggregate_backend="matmul"), ds,
                      build_gcn(layers, 0.0))
    assert t16.gdata.plans is not None
    for i in range(2):
        l1, l16 = float(t1.run_epoch()), float(t16.run_epoch())
        np.testing.assert_allclose(l16, l1, rtol=1e-4, err_msg=f"epoch {i}")
    # GAT, plan attention
    g1 = Trainer(Config(**base, model="gat", heads=2), ds,
                 build_gat(layers, 0.0, heads=2))
    g16 = SpmdTrainer(Config(**base, model="gat", heads=2, num_parts=16,
                             halo=True, aggregate_backend="matmul"), ds,
                      build_gat(layers, 0.0, heads=2))
    assert g16.gdata.gat_plans is not None
    for i in range(2):
        l1, l16 = float(g1.run_epoch()), float(g16.run_epoch())
        np.testing.assert_allclose(l16, l1, rtol=1e-4, err_msg=f"epoch {i}")


def test_overcommit_rejects_ring_and_edge_shard():
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gcn
    from roc_tpu.parallel.spmd import SpmdTrainer
    from roc_tpu.train.config import Config

    ds = datasets.synthetic("overr", 200, 3.0, 8, 4, n_train=30, n_val=30,
                            n_test=30, seed=3)
    layers = [8, 8, 4]
    for kw in (dict(exchange="ring"), dict(edge_shard=True)):
        cfg = Config(layers=layers, num_epochs=1, dropout_rate=0.0,
                     eval_every=10 ** 9, num_parts=16, **kw)
        with pytest.raises(ValueError, match="overcommit"):
            SpmdTrainer(cfg, ds, build_gcn(layers, 0.0))


def test_ring_exchange_matmul_plans_match_xla():
    """-exchange ring -aggr-backend matmul (per-owner chunk plans,
    ring_owner_matmul — the ring fast path VERDICT r2 flagged missing)
    must track the xla ring and single-device runs, and avg must ride the
    same plans."""
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gcn, build_sage
    from roc_tpu.parallel.spmd import SpmdTrainer
    from roc_tpu.train.config import Config
    from roc_tpu.train.driver import Trainer

    ds = datasets.synthetic("ringmm", 260, 4.0, 8, 4, n_train=50, n_val=50,
                            n_test=50, seed=6)
    layers = [8, 8, 4]
    base = dict(layers=layers, num_epochs=3, dropout_rate=0.0,
                eval_every=10 ** 9, edge_shard="off")
    t1 = Trainer(Config(**base), ds, build_gcn(layers, 0.0))
    tx = SpmdTrainer(Config(**base, num_parts=4, exchange="ring"), ds,
                     build_gcn(layers, 0.0))
    tm = SpmdTrainer(Config(**base, num_parts=4, exchange="ring",
                            aggregate_backend="matmul"), ds,
                     build_gcn(layers, 0.0))
    assert tm.gdata.backend == "matmul"
    assert tm.gdata.ring_plans is not None, "ring plans not engaged"
    for i, rtol in enumerate((2e-5, 5e-3, 5e-3)):
        l1 = float(t1.run_epoch())
        lx = float(tx.run_epoch())
        lm = float(tm.run_epoch())
        np.testing.assert_allclose(lm, lx, rtol=rtol, err_msg=f"epoch {i}")
        np.testing.assert_allclose(lm, l1, rtol=rtol, err_msg=f"epoch {i}")

    # avg on the plan path (sage-mean): sum plans / in-degree
    ds2 = datasets.synthetic("ringmma", 220, 4.0, 8, 4, n_train=40,
                             n_val=40, n_test=40, seed=7)
    base2 = dict(layers=layers, num_epochs=2, dropout_rate=0.0,
                 eval_every=10 ** 9, edge_shard="off", aggr="avg",
                 model="sage")
    t1a = Trainer(Config(**base2), ds2, build_sage(layers, 0.0, aggr="avg"))
    tma = SpmdTrainer(Config(**base2, num_parts=4, exchange="ring",
                             aggregate_backend="matmul"), ds2,
                      build_sage(layers, 0.0, aggr="avg"))
    assert tma.gdata.ring_plans is not None
    for i, rtol in enumerate((2e-5, 5e-3)):
        l1, lm = float(t1a.run_epoch()), float(tma.run_epoch())
        np.testing.assert_allclose(lm, l1, rtol=rtol, err_msg=f"epoch {i}")


def test_ring_exchange_sage_avg_and_max():
    """Ring mode supports avg (sum/degree) and max (max-of-maxes across
    visiting shards)."""
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_sage
    from roc_tpu.parallel.spmd import SpmdTrainer
    from roc_tpu.train.config import Config
    from roc_tpu.train.driver import Trainer

    ds = datasets.synthetic("ringsage", 220, 4.0, 8, 4, n_train=40,
                            n_val=40, n_test=40, seed=7)
    layers = [8, 8, 4]
    for aggr in ("avg", "max"):
        base = dict(layers=layers, num_epochs=2, dropout_rate=0.0,
                    eval_every=10 ** 9, edge_shard="off", aggr=aggr,
                    model="sage")
        t1 = Trainer(Config(**base), ds, build_sage(layers, 0.0, aggr=aggr))
        tr = SpmdTrainer(Config(**base, num_parts=4, exchange="ring"), ds,
                         build_sage(layers, 0.0, aggr=aggr))
        # op-level ring == single-device to ~2e-6 (verified directly);
        # across epochs fp32 reassociation amplifies chaotically, so only
        # the first epoch is tight.
        for i, rtol in enumerate((2e-5, 5e-3)):
            l1, lr = float(t1.run_epoch()), float(tr.run_epoch())
            np.testing.assert_allclose(lr, l1, rtol=rtol,
                                       err_msg=f"{aggr} epoch {i}")


# ---------------------------------------------------------------------------
# Halo mode on a plan backend: ONE plan set over the combined table (own
# rows ++ received halo rows).  A second plan for the remote edges would pay
# a floor chunk for every window without one (PERF.md, PR 30).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["matmul", "binned"])
def test_halo_plan_backends_train_as_one_device(backend):
    """Four parts over the halo exchange == the one-device Trainer, fwd AND
    bwd (training epochs), on both plan backends; avg (SAGE) rides the same
    sum, then divides by degree."""
    from roc_tpu.models import build_sage

    ds = small_ds(seed=23)
    base = dict(layers=[ds.in_dim, 8, ds.num_classes], num_epochs=3,
                dropout_rate=0.0, eval_every=10**9, edge_shard="off",
                aggregate_precision="exact")
    four = SpmdTrainer(Config(**base, num_parts=4, halo=True,
                              aggregate_backend=backend), ds,
                       build_gcn(base["layers"], 0.0))
    one = Trainer(Config(**base), ds, build_gcn(base["layers"], 0.0))
    assert four.gdata.backend == backend and four.gdata.plans is not None
    assert four._exchange_mode == "halo" and one.gdata.backend == "xla"
    for i in range(3):
        np.testing.assert_allclose(float(four.run_epoch()),
                                   float(one.run_epoch()), rtol=1e-5,
                                   err_msg=f"epoch {i}")
    np.testing.assert_allclose(
        np.asarray(jax.device_get(four.params["linear_1"])),
        np.asarray(jax.device_get(one.params["linear_1"])), rtol=1e-4,
        atol=1e-6)
    sage = dict(base, model="sage", aggr="avg")
    m4 = SpmdTrainer(Config(**sage, num_parts=4, halo=True,
                            aggregate_backend=backend), ds,
                     build_sage(base["layers"], 0.0, aggr="avg"))
    m1 = Trainer(Config(**sage), ds,
                 build_sage(base["layers"], 0.0, aggr="avg"))
    for i in range(2):
        np.testing.assert_allclose(float(m4.run_epoch()),
                                   float(m1.run_epoch()), rtol=1e-5,
                                   err_msg=f"sage epoch {i}")


def test_combined_plan_folds_remote_edges_into_local_windows():
    """On a community-local graph (the gcn-products.p4 recipe at 6,000
    nodes: a window of 8 rows holds about 400 edges, the cut sits in the
    border communities) a part's remote edges land in windows that already
    own chunks: the combined forward plan holds hardly more chunks than a
    plan of the local edges alone, where a plan of the remote edges alone
    pays a floor chunk for every window.  exchange_info reports the
    placed plans' own shapes."""
    import os

    from benchmark import graphgen
    from benchmark import manifest as mf
    from roc_tpu.ops.pallas.segment_sum import (CPAD, EB, VB,
                                                build_chunk_plan)

    recipe = dict(graphgen.load_recipe(os.path.join(
        mf.ROOT, "benchmark", "traffic", "products-local-p4.json")),
        nodes=6000, splits={"train": 600, "val": 600, "test": 600})
    ds = graphgen.generate(recipe, 8, 4, 3)
    cfg = Config(layers=[8, 8, 4], num_epochs=1, dropout_rate=0.0,
                 eval_every=10**9, num_parts=4, halo=True,
                 edge_shard="off", aggregate_backend="matmul")
    tr = SpmdTrainer(cfg, ds, build_gcn(cfg.layers, 0.0))
    part, halo, S = tr.part, tr.halo, tr.part.shard_nodes
    combined = []
    for p in range(4):
        src, dst = halo.edge_src_local[p], part.edge_dst[p]
        remote = src >= S
        assert 0 < remote.sum() < 0.2 * src.size
        comb = build_chunk_plan(src, dst, S).num_chunks
        local = build_chunk_plan(src[~remote], dst[~remote], S).num_chunks
        alone = build_chunk_plan(src[remote] - S, dst[remote],
                                 S).num_chunks
        windows = np.unique(dst[remote] // VB).size
        assert comb < local + -(-int(remote.sum()) // EB) + windows + CPAD
        assert alone >= S // VB and comb - local < alone // 4
        combined.append(comb)
    plans, info = tr.gdata.plans, tr.exchange_info()
    assert plans.fwd_esrc.shape == (4, max(combined), EB)
    live = int(np.asarray(part.num_edges_valid).max())
    for d in ("fwd", "bwd"):
        chunks = getattr(plans, d + "_obi").shape[1]
        assert info["agg_chunks_" + d] == chunks
        assert info["agg_slot_fill_" + d] == pytest.approx(
            live / (chunks * EB))
    assert 0.5 < info["agg_slot_fill_fwd"] < 1.0
    # the backward plan is keyed by table row: the halo rows keep windows
    # of their own, so it holds more chunks for the same edges
    assert info["agg_chunks_bwd"] > info["agg_chunks_fwd"]


def test_sharded_sum_is_one_scan_each_way_fed_by_the_exchange():
    """One shard's aggregate(x, "sum") on the matmul backend is ONE scan
    over the table the all_to_all built, and its gradient adds ONE more
    (the transposed plan), nothing beside them: a plan set beside `plans`
    cannot arrive unnoticed."""
    import jax.numpy as jnp
    from jax.extend.core import Literal

    from roc_tpu.parallel import spmd as sp

    ds = small_ds(seed=29)
    cfg = Config(layers=[ds.in_dim, 8, ds.num_classes], num_epochs=1,
                 dropout_rate=0.0, eval_every=10**9, num_parts=4, halo=True,
                 edge_shard="off", aggregate_backend="matmul")
    tr = SpmdTrainer(cfg, ds, build_gcn(cfg.layers, 0.0))
    S = tr.part.shard_nodes
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("parts",))
    Pspec = jax.sharding.PartitionSpec
    gd_specs = jax.tree.map(lambda a: Pspec("parts"), tr.gdata)

    # trace THROUGH shard_map so all_to_all sees a bound axis name
    @jax.shard_map(mesh=mesh, in_specs=(Pspec("parts"), gd_specs),
                   out_specs=Pspec("parts"))
    def aggregate(x, gd_block):
        gctx = sp._shard_gctx(sp._squeeze_gd(gd_block), S, "halo")
        return gctx.aggregate(x, "sum")

    x = jnp.zeros((4 * S, ds.in_dim), jnp.float32)

    def subjaxprs(e):
        for v in e.params.values():
            for vv in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(vv, "jaxpr") and hasattr(vv.jaxpr, "eqns"):
                    yield vv.jaxpr          # ClosedJaxpr
                elif hasattr(vv, "eqns"):
                    yield vv                # open Jaxpr (shard_map)

    def loops(jx, tainted_in, found):
        """Every scan/while of ``jx`` and below as (name, reads a value
        derived from an all_to_all); returns whether an output does."""
        tainted = set(tainted_in)
        for e in jx.eqns:
            ein = [v for v in e.invars if not isinstance(v, Literal)]
            hot = any(v in tainted for v in ein) \
                or "all_to_all" in e.primitive.name
            if e.primitive.name in ("scan", "while"):
                found.append((e.primitive.name, hot))
            else:
                for sj in subjaxprs(e):
                    same = len(sj.invars) == len(ein)
                    tin = {sv for sv, ov in zip(sj.invars, ein)
                           if ov in tainted} if same \
                        else (set(sj.invars) if hot else set())
                    hot = loops(sj, tin, found) or hot
            if hot:
                tainted.update(e.outvars)
        return any(v in tainted for v in jx.outvars)

    fwd = []
    loops(jax.make_jaxpr(aggregate)(x, tr.gdata).jaxpr, set(), fwd)
    assert fwd == [("scan", True)], fwd
    both = []
    loops(jax.make_jaxpr(jax.grad(
        lambda x_: aggregate(x_, tr.gdata).sum()))(x).jaxpr, set(), both)
    assert [name for name, _ in both] == ["scan", "scan"], both
