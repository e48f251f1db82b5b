"""Binned two-phase aggregation (ops/pallas/binned.py) vs the segment-sum
oracle, in interpret mode on CPU.  Hardware behavior is covered by the
TPU-gated tests in tests/test_tpu_hw.py, skipped off-TPU (interpret mode
has already let two Mosaic lowering bugs ship; see docs/PERF.md)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu import ops
from roc_tpu.ops.pallas.binned import RB, SB, build_binned_plan, run_binned


def oracle_bf16(x, src, dst, n):
    """The binned backend's numerical contract: features rounded to bf16
    once, fp32 accumulation.  Shared with tests/test_tpu_hw.py."""
    xb = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    out = np.zeros((n, x.shape[1]), np.float32)
    np.add.at(out, dst, xb[src])
    return out


_oracle_bf16 = oracle_bf16


CASES = [
    # (num_rows, table_rows, num_edges, hidden)
    (700, 700, 5000, 64),
    (1500, 2000, 30000, 64),    # multi-group, table != out rows
    (100, 100, 0, 64),          # empty edge list
    (513, 513, 1, 8),           # single edge, just past one bin
    (SB + 1, SB + 1, 300, 16),  # two source blocks
    (3 * RB, 1000, 3000, 16),   # partial last bin group (G=2, bpg=2)
    (700, 700, 5000, 41),       # lane-unaligned H (GCN output layer):
                                # run_binned pads H to 128 internally
]


@pytest.mark.parametrize("n,t,e,h", CASES)
def test_binned_matches_oracle(n, t, e, h):
    rng = np.random.default_rng(42)
    src = rng.integers(0, t, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    if e > 100:
        dst[: e // 4] = 7       # hub destination spanning many slots
    x = rng.standard_normal((t, h), dtype=np.float32)
    plan = build_binned_plan(src, dst, n, t, group_row_target=1 << 14)
    out = np.asarray(run_binned(jnp.asarray(x), plan, interpret=True))
    ref = _oracle_bf16(x, src, dst, n)
    # identical sums up to fp32 reassociation (chunk order != edge order)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-3)


def test_binned_hub_source_and_dst():
    """A single source feeding a single dst many times (parallel edges) —
    multiplicity must be preserved exactly (one-hot columns are per-edge)."""
    n = 64
    src = np.full(1000, 3, np.int64)
    dst = np.full(1000, 5, np.int64)
    x = np.ones((n, 8), np.float32) * 1.5
    plan = build_binned_plan(src, dst, n, n, group_row_target=1 << 14)
    out = np.asarray(run_binned(jnp.asarray(x), plan, interpret=True))
    assert out[5, 0] == 1500.0 and np.all(out[:5] == 0) and np.all(out[6:] == 0)


def oracle_fp32(x, src, dst, n):
    """The exact path's contract: fp32 values, fp32 accumulation (the
    reference's precision, types.h:7), differing only by sum order."""
    out = np.zeros((n, x.shape[1]), np.float32)
    np.add.at(out, dst, np.asarray(x)[src])
    return out


@pytest.mark.parametrize("n,t,e,h", CASES)
def test_binned_exact_matches_fp32_oracle(n, t, e, h):
    """precision="exact" (fp32 staging + 3-way bf16 split dots) must agree
    with the fp32 oracle to reassociation-level error — and be strictly
    tighter than the fast path's designed bf16 rounding."""
    rng = np.random.default_rng(43)
    src = rng.integers(0, t, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    x = rng.standard_normal((t, h), dtype=np.float32)
    plan = build_binned_plan(src, dst, n, t, group_row_target=1 << 14)
    out = np.asarray(run_binned(jnp.asarray(x), plan, interpret=True,
                                precision="exact"))
    ref = oracle_fp32(x, src, dst, n)
    np.testing.assert_allclose(out, ref, rtol=2e-6, atol=1e-5)
    if e >= 5000:
        # the fast path cannot meet the exact tolerance on this data —
        # guards against "exact" silently running the fast kernels
        fast = np.asarray(run_binned(jnp.asarray(x), plan, interpret=True))
        assert np.abs(fast - ref).max() > 10 * np.abs(out - ref).max()


def test_binned_exact_vjp():
    rng = np.random.default_rng(11)
    n, e, h = 300, 2000, 32
    src = rng.integers(0, n, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    x = rng.standard_normal((n, h), dtype=np.float32)
    g = rng.standard_normal((n, h), dtype=np.float32)
    plans = ops.build_binned_plans(src, dst, n, n)
    _, vjp = jax.vjp(
        lambda x: ops.scatter_gather_binned(x, plans, True, "exact"), x)
    (gx,) = vjp(jnp.asarray(g))
    ref = oracle_fp32(g, dst, src, n)
    np.testing.assert_allclose(np.asarray(gx), ref, rtol=2e-6, atol=1e-5)


def test_binned_exact_sharded_matches_xla():
    """The sharded (halo) binned path must honor precision='exact': losses
    match the single-device fp32 xla run to reassociation error, tighter
    than the fast path's bf16 rounding could."""
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gcn
    from roc_tpu.parallel.spmd import SpmdTrainer
    from roc_tpu.train.config import Config
    from roc_tpu.train.driver import Trainer

    ds = datasets.synthetic("bx", 300, 5.0, 10, 4, n_train=60, n_val=60,
                            n_test=60, seed=13)
    layers = [10, 8, 4]
    base = dict(layers=layers, num_epochs=3, dropout_rate=0.0,
                eval_every=10**9)
    t1 = Trainer(Config(**base), ds, build_gcn(layers, 0.0))
    tb = SpmdTrainer(Config(**base, num_parts=4, halo=True,
                            aggregate_backend="binned",
                            aggregate_precision="exact"), ds,
                     build_gcn(layers, 0.0))
    assert tb.gdata.backend == "binned"
    for i in range(3):
        l1, lb = float(t1.run_epoch()), float(tb.run_epoch())
        np.testing.assert_allclose(lb, l1, rtol=2e-5, err_msg=f"epoch {i}")


def test_binned_rejects_unknown_precision():
    """Same rule as matmul_precision: a silent fallthrough to fast would
    drop the fp32-exact guarantee."""
    src = np.array([0], np.int64)
    dst = np.array([1], np.int64)
    plan = build_binned_plan(src, dst, 8, 8, group_row_target=1 << 14)
    x = jnp.ones((8, 8), jnp.float32)
    with pytest.raises(ValueError, match="precision"):
        run_binned(x, plan, interpret=True, precision="highest")


def test_binned_exact_degrades_to_fast_for_bf16_input():
    """A bf16 input makes exact == fast; run_binned must take the cheap
    path (same staging dtype) rather than pay 3x dots for nothing."""
    from roc_tpu.ops.pallas import binned as B
    rng = np.random.default_rng(12)
    n, e, h = 256, 1000, 16
    src = rng.integers(0, n, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    x = jnp.asarray(rng.standard_normal((n, h), dtype=np.float32)
                    ).astype(jnp.bfloat16)
    plan = B.build_binned_plan(src, dst, n, n, group_row_target=1 << 14)
    out_e = run_binned(x, plan, interpret=True, precision="exact")
    out_f = run_binned(x, plan, interpret=True, precision="fast")
    assert out_e.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out_e, np.float32),
                               np.asarray(out_f, np.float32))


def test_binned_vjp_is_transposed_aggregation():
    rng = np.random.default_rng(7)
    n, e, h = 300, 2000, 32
    src = rng.integers(0, n, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    x = rng.standard_normal((n, h), dtype=np.float32)
    g = rng.standard_normal((n, h), dtype=np.float32)
    plans = ops.build_binned_plans(src, dst, n, n)

    _, vjp = jax.vjp(lambda x: ops.scatter_gather_binned(x, plans, True), x)
    (gx,) = vjp(jnp.asarray(g))
    ref = _oracle_bf16(g, dst, src, n)   # grad_x = A^T @ g
    np.testing.assert_allclose(np.asarray(gx), ref, rtol=1e-5, atol=1e-3)


def test_binned_backend_resolution():
    from roc_tpu.train.driver import resolve_backend
    assert resolve_backend("pallas", 10) == "binned"
    assert resolve_backend("binned", 10) == "binned"
    assert resolve_backend("matmul", 10) == "matmul"


def test_binned_in_trainer():
    """End-to-end: the GCN trains with the binned backend and matches the
    xla backend to bf16-rounding tolerance on the first epoch loss."""
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gcn
    from roc_tpu.train.config import Config
    from roc_tpu.train.driver import Trainer

    ds = datasets.synthetic("binned-e2e", 600, 6.0, 32, 5,
                            n_train=200, n_val=100, n_test=100, seed=3)
    losses = {}
    for backend in ("xla", "binned"):
        cfg = Config(layers=[32, 16, 5], num_epochs=1, dropout_rate=0.0,
                     eval_every=10 ** 9, aggregate_backend=backend, seed=11)
        tr = Trainer(cfg, ds, build_gcn(cfg.layers, 0.0))
        losses[backend] = float(tr.run_epoch())
    assert np.isfinite(losses["binned"])
    assert abs(losses["binned"] - losses["xla"]) < 1e-2 * max(
        abs(losses["xla"]), 1.0)


@pytest.mark.parametrize("backend", ["binned", "matmul"])
def test_plan_backend_avg_matches_xla(backend):
    """avg rides the plan backends as sum / in-degree; it must match the
    xla segment-avg oracle (GraphSAGE-mean's aggregation) on both the
    single-device and the sharded path."""
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_sage
    from roc_tpu.parallel.spmd import SpmdTrainer
    from roc_tpu.train.config import Config
    from roc_tpu.train.driver import Trainer, dense_graph_data, make_gctx

    ds = datasets.synthetic("avg-fast", 900, 5.0, 16, 4,
                            n_train=300, n_val=100, n_test=100, seed=9)
    # op-level: aggregate(x, "avg") vs the xla oracle
    g = ds.graph
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (g.num_nodes, 16), dtype=np.float32))
    want = np.asarray(ops.scatter_gather(
        x, jnp.asarray(g.col_idx, jnp.int32), jnp.asarray(g.dst_idx,
                                                          jnp.int32),
        g.num_nodes, "avg"))
    gctx = make_gctx(dense_graph_data(g, backend), g.num_nodes)
    got = np.asarray(gctx.aggregate(x, "avg"))
    tol = 5e-2 if backend == "binned" else 1e-3    # one bf16 rounding
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)

    # end-to-end: SAGE-mean trains on the plan backend and tracks xla
    losses = {}
    for b in ("xla", backend):
        cfg = Config(layers=[16, 8, 4], num_epochs=1, dropout_rate=0.0,
                     eval_every=10 ** 9, aggregate_backend=b, seed=5,
                     num_parts=4, halo=True)
        tr = SpmdTrainer(cfg, ds, build_sage(cfg.layers, 0.0))
        assert b == "xla" or tr.gdata.backend == backend
        losses[b] = float(tr.run_epoch())
    assert abs(losses[backend] - losses["xla"]) < 1e-2 * max(
        abs(losses["xla"]), 1.0)


def test_native_plan_equals_numpy():
    """The C++ counting-sort plan builder must match the NumPy oracle bit
    for bit (same invariant style as the native halo/chunk builders)."""
    from roc_tpu import native
    from roc_tpu.ops.pallas.binned import _build_binned_plan_numpy
    if not native.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(13)
    for (n, t, e) in [(700, 700, 5000), (1500, 2000, 30000),
                      (100, 100, 0), (513, 513, 1), (5000, 4000, 120000),
                      # partial last group: num_bins=3, bpg=2, G=2 — the
                      # phantom-bin placeholder path in both builders
                      (3 * 512, 1000, 3000)]:
        src = rng.integers(0, t, e).astype(np.int64)
        dst = rng.integers(0, n, e).astype(np.int64)
        if e > 100:
            dst[: e // 4] = 7
        tgt = 2000 if n == 3 * 512 else 1 << 14
        ref = _build_binned_plan_numpy(src, dst, n, t, tgt)
        (p1_srcl, p1_off, p1_blk, p2_dstl, p2_obi, p2_first,
         bpg) = native.binned_plan(src, dst, n, t, tgt)
        assert bpg == ref.bins_per_group
        G, C1 = p1_blk.shape
        np.testing.assert_array_equal(
            p1_srcl.reshape(G, C1, 2048), np.asarray(ref.p1_srcl))
        np.testing.assert_array_equal(p1_off, np.asarray(ref.p1_off))
        np.testing.assert_array_equal(p1_blk, np.asarray(ref.p1_blk))
        C2 = p2_obi.shape[1]
        np.testing.assert_array_equal(
            p2_dstl.reshape(G, C2, 4096), np.asarray(ref.p2_dstl))
        np.testing.assert_array_equal(p2_obi, np.asarray(ref.p2_obi))
        np.testing.assert_array_equal(p2_first, np.asarray(ref.p2_first))


def test_native_plan_equals_numpy_nondefault_geometry():
    """The geometry-parametric native builder (roc_binned_plan_*_g) must
    match the NumPy oracle bit for bit at the sparse presets too."""
    from roc_tpu import native
    from roc_tpu.ops.pallas import binned as B
    if not native.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(17)
    for geom in (B.GEOM_MID, B.GEOM_SPARSE, B.GEOM_XSPARSE):
        for (n, t, e) in [(700, 700, 5000), (3 * geom.rb, 1000, 3000),
                          (5000, 4000, 120000), (100, 100, 0)]:
            src = rng.integers(0, t, e).astype(np.int64)
            dst = rng.integers(0, n, e).astype(np.int64)
            if e > 100:
                dst[: e // 4] = 7
            tgt = 1 << 14
            ref = B._build_binned_plan_numpy(src, dst, n, t, tgt, geom)
            (p1_srcl, p1_off, p1_blk, p2_dstl, p2_obi, p2_first,
             bpg) = native.binned_plan(src, dst, n, t, tgt, geom)
            msg = f"geom={geom} n={n} t={t} e={e}"
            assert bpg == ref.bins_per_group, msg
            G, C1 = p1_blk.shape
            C2 = p2_obi.shape[1]
            np.testing.assert_array_equal(
                p1_srcl.reshape(G, C1, geom.ch),
                np.asarray(ref.p1_srcl), err_msg=msg)
            np.testing.assert_array_equal(p1_off, np.asarray(ref.p1_off),
                                          err_msg=msg)
            np.testing.assert_array_equal(p1_blk, np.asarray(ref.p1_blk),
                                          err_msg=msg)
            np.testing.assert_array_equal(
                p2_dstl.reshape(G, C2, geom.ch2),
                np.asarray(ref.p2_dstl), err_msg=msg)
            np.testing.assert_array_equal(p2_obi, np.asarray(ref.p2_obi),
                                          err_msg=msg)
            np.testing.assert_array_equal(p2_first, np.asarray(ref.p2_first),
                                          err_msg=msg)


@pytest.mark.parametrize("halo", [False, True])
def test_binned_sharded_matches_xla(halo):
    """Sharded binned plans (stacked per-shard, common static geometry)
    must train equal to the sharded xla path up to the designed bf16
    rounding — both halo and all-gather exchange modes."""
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gcn
    from roc_tpu.parallel.spmd import SpmdTrainer
    from roc_tpu.train.config import Config

    ds = datasets.synthetic("bs", 220, 4.0, 8, 4, n_train=40, n_val=40,
                            n_test=40, seed=3)
    base = dict(layers=[8, 8, 4], num_epochs=2, dropout_rate=0.0,
                eval_every=10 ** 9, num_parts=4, halo=halo,
                edge_shard="off")
    tx = SpmdTrainer(Config(**base), ds, build_gcn(base["layers"], 0.0))
    tb = SpmdTrainer(Config(**base, aggregate_backend="binned"), ds,
                     build_gcn(base["layers"], 0.0))
    assert tb.gdata.backend == "binned" and tb.gdata.plans is not None
    for i in range(2):
        lx, lb = float(tx.run_epoch()), float(tb.run_epoch())
        np.testing.assert_allclose(lb, lx, rtol=5e-3, err_msg=f"epoch {i}")


def test_pad_binned_plans_floors():
    """pad_binned_plans must honor (C1, C2) floors — the perhost path
    passes allgathered global maxima so every process compiles the same
    program — and padded plans must still produce correct sums."""
    rng = np.random.default_rng(3)
    n, t, h = 400, 400, 16
    shard_plans, xs, refs = [], [], []
    for e in (900, 4000):   # different edge counts -> different C1/C2
        src = rng.integers(0, t, e).astype(np.int64)
        dst = rng.integers(0, n, e).astype(np.int64)
        x = rng.standard_normal((t, h), dtype=np.float32)
        shard_plans.append(ops.build_binned_plans(src, dst, n, t))
        xs.append(x)
        refs.append(oracle_bf16(x, src, dst, n))
    stacked = ops.pad_binned_plans(shard_plans, min_fwd=(64, 9),
                                   min_bwd=(64, 9))
    assert stacked.fwd.p1_blk.shape[1:] == (
        shard_plans[0].fwd.p1_blk.shape[0], 64)
    assert stacked.fwd.p2_obi.shape[2] >= 9
    for i in range(2):
        one = jax.tree.map(lambda a: a[i], stacked)
        out = np.asarray(ops.scatter_gather_binned(
            jnp.asarray(xs[i]), one, True))
        np.testing.assert_allclose(out, refs[i], rtol=1e-5, atol=1e-3)


def test_auto_binned_selection(monkeypatch):
    """With AUTO_BINNED on (the hardware flip), auto picks binned exactly
    when the cell-occupancy criterion holds — dense-enough graphs yes,
    huge sparse ones no."""
    import roc_tpu.train.driver as drv
    from roc_tpu.ops.pallas.binned import binned_viable

    monkeypatch.setattr(drv, "AUTO_BINNED", True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # Reddit-shape: viable (measured case)
    assert binned_viable(232_965, 232_965, 23_526_267)
    assert drv.resolve_backend("auto", 23_526_267, 232_965,
                               232_965) == "binned"
    # products-shape: not viable (measured ~5x padding)
    assert not binned_viable(2_449_029, 2_449_029, 124_000_000)
    assert drv.resolve_backend("auto", 124_000_000, 2_449_029,
                               2_449_029) == "matmul"
    # small graphs stay on xla regardless
    assert drv.resolve_backend("auto", 1000, 500, 500) == "xla"


def test_auto_binned_shard_level_refinement(monkeypatch):
    """When the global viability check fails but the per-shard halo table
    is dense (locality-heavy partitions, small K), the SPMD trainer must
    upgrade auto->matmul to binned at shard geometry."""
    import roc_tpu.train.driver as drv
    from roc_tpu.graph.csr import add_self_edges, from_edges
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gcn
    from roc_tpu.parallel.spmd import SpmdTrainer
    from roc_tpu.ops.pallas.binned import binned_viable
    from roc_tpu.train.config import Config

    monkeypatch.setattr(drv, "AUTO_BINNED", True)
    monkeypatch.setattr(drv, "AUTO_MATMUL_EDGES", 1 << 10)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the backend spoof above must not push the kernels out of interpret
    # mode on the CPU test platform
    monkeypatch.setattr(drv, "pallas_interpret", lambda: True)

    # 4 near-disjoint communities: global cells fail the bound, per-shard
    # (own rows + tiny halo) cells pass it
    n, P_ = 16384, 4
    rng = np.random.default_rng(0)
    q = n // P_
    src = np.concatenate([rng.integers(i * q, (i + 1) * q, 15000)
                          for i in range(P_)])
    dst = np.concatenate([rng.integers(i * q, (i + 1) * q, 15000)
                          for i in range(P_)])
    keep = src != dst
    g = add_self_edges(from_edges(n, src[keep], dst[keep]))
    assert not binned_viable(n, n, g.num_edges)          # global: no
    ds = datasets.Dataset(
        name="comm", graph=g,
        features=rng.normal(size=(n, 8)).astype(np.float32),
        labels=None, label_ids=np.zeros(n, np.int64),
        mask=np.zeros(n, np.int32), in_dim=8, num_classes=4)
    cfg = Config(layers=[8, 8, 4], num_epochs=1, dropout_rate=0.0,
                 eval_every=10 ** 9, num_parts=P_, halo=True,
                 edge_shard="off")
    tr = SpmdTrainer(cfg, ds, build_gcn(cfg.layers, 0.0))
    assert tr.gdata.backend == "binned", tr.gdata.backend
    assert np.isfinite(float(tr.run_epoch()))


@pytest.mark.parametrize("geom_name", ["mid", "sparse", "xsparse"])
def test_binned_nondefault_geometry_matches_oracle(geom_name):
    """The sparse-graph geometry presets (VERDICT r3 item 3) must produce
    oracle-correct sums through the same kernels, fast and exact."""
    from roc_tpu.ops.pallas import binned as B
    geom = {"mid": B.GEOM_MID, "sparse": B.GEOM_SPARSE,
            "xsparse": B.GEOM_XSPARSE}[geom_name]
    rng = np.random.default_rng(21)
    for (n, t, e, h) in [(700, 700, 5000, 64),
                         (1500, 2000, 12000, 41),    # lane-unaligned H,
                         (100, 100, 0, 16),          # multi-group (tgt 4k)
                         (geom.sb + 1, geom.sb + 1, 300, 16),
                         (3 * geom.rb, 1000, 3000, 16)]:
        src = rng.integers(0, t, e).astype(np.int64)
        dst = rng.integers(0, n, e).astype(np.int64)
        x = rng.standard_normal((t, h), dtype=np.float32)
        plan = B.build_binned_plan(src, dst, n, t,
                                   group_row_target=1 << 12, geom=geom)
        assert plan.geom == geom
        out = np.asarray(run_binned(jnp.asarray(x), plan, interpret=True))
        np.testing.assert_allclose(
            out, oracle_bf16(x, src, dst, n), rtol=1e-5, atol=1e-3,
            err_msg=f"{geom_name}: n={n} t={t} e={e} h={h}")
        out_e = np.asarray(run_binned(jnp.asarray(x), plan, interpret=True,
                                      precision="exact"))
        np.testing.assert_allclose(
            out_e, oracle_fp32(x, src, dst, n), rtol=2e-6, atol=1e-5,
            err_msg=f"{geom_name} exact: n={n} t={t} e={e} h={h}")


def test_pad_binned_plan_preserves_geometry():
    from roc_tpu.ops.pallas import binned as B
    rng = np.random.default_rng(22)
    n, e = 3 * B.GEOM_SPARSE.rb, 4000
    src = rng.integers(0, n, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    x = rng.standard_normal((n, 16), dtype=np.float32)
    plan = B.build_binned_plan(src, dst, n, n, group_row_target=1 << 14,
                               geom=B.GEOM_SPARSE)
    padded = B.pad_binned_plan(plan, plan.p1_blk.shape[1] + 8,
                               plan.p2_obi.shape[1] + 3)
    assert padded.geom == B.GEOM_SPARSE
    out = np.asarray(run_binned(jnp.asarray(x), padded, interpret=True))
    np.testing.assert_allclose(out, oracle_bf16(x, src, dst, n),
                               rtol=1e-5, atol=1e-3)


def test_choose_geometry_policy():
    """The stats-based policy (calibrated cost model, docs/PERF.md numbers):
    dense graphs keep a dense-window geometry; uniform sparse at products
    density correctly prefers matmul; the SAME density with community
    locality (the partitioner's output order) gets a binned geometry —
    the uniform bound could never see that difference."""
    from roc_tpu.ops.pallas import binned as B
    rng = np.random.default_rng(5)

    # dense: Reddit-like occupancy at small scale.  The chosen slot must
    # be the hardware sweep's winner (128): at equal padded rows the
    # smaller-slot presets pay the per-slot-DMA term the sweep measured
    # (docs/PERF.md SLOT 32 -> 128 = -19.3 ms), which the model must
    # reproduce or it mis-ranks presets on every dense graph.
    n, e = 2048, 200_000
    src = rng.integers(0, n, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    g, t = B.choose_geometry(src, dst, n, n)
    assert g is not None and g.slot == 128 and not g.flat, (g, t)

    # Reddit-like occupancy itself: about 113 edges a 512 x 512 cell (the
    # benchmark's graphs hold 207,610 cells for 23.4 M in-edges).  The
    # flat descriptor walk is what the chip is slowest at there (PR 24:
    # 406 ms a sweep against 64 for the two-pass phase 1), so the pick is
    # the slot-128 two-pass schedule, and flat prices at over twice it.
    n, e = 32768, 64 * 64 * 113
    src = rng.integers(0, n, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    g, t = B.choose_geometry(src, dst, n, n)
    assert g is not None and g.slot == 128 and not g.flat, (g, t)
    _, t_flat = B.choose_geometry(src, dst, n, n, candidates=[B.GEOM_FLAT],
                                  force=True)
    assert t_flat > 2 * t, (t_flat, t)

    # uniform products-density: ~13 edges per (512,512) cell.  The refit
    # model prices the matmul backend's per-VB-window >=1-chunk floor
    # (segment_sum.build_chunk_plan — ceil(100k/8) = 12.5k chunks here
    # REGARDLESS of edge count, the products-shape matmul pathology), so
    # even uniform sparse now beats it — either on a sparse-window preset
    # (small slots) or, since round 8, on a FLAT preset whose 8-row cell
    # granularity removes slot padding outright.  The round-2 model,
    # floorless, pinned matmul here.
    n, e = 100_000, 500_000
    src = rng.integers(0, n, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    g_u, t_u = B.choose_geometry(src, dst, n, n)
    assert g_u is not None and (g_u.flat or g_u.slot <= 32), (g_u, t_u)
    assert t_u < B._matmul_cost(e, n), (t_u, B._matmul_cost(e, n))

    # same density, block-diagonal communities: cells concentrate on the
    # diagonal, the model credits the untouched cells, and the modeled
    # time drops further
    q, k = 512, 100_000 // 512 + 1
    comm = rng.integers(0, k, 500_000) * q
    src = (comm + rng.integers(0, q, 500_000)).astype(np.int64)
    dst = (comm + rng.integers(0, q, 500_000)).astype(np.int64)
    g_c, t_c = B.choose_geometry(src, dst, k * q, k * q)
    assert g_c is not None and t_c < t_u, (g_c, t_c, t_u)


def test_sweep_products_configs_match_presets():
    """tools/sweep_binned.py hardcodes the preset tuples so its parent
    process never imports jax (subprocess isolation); this pin fails if a
    preset retune forgets that mirror."""
    import importlib.util
    import os as _os
    from roc_tpu.ops.pallas import binned as B
    spec = importlib.util.spec_from_file_location(
        "sweep_binned", _os.path.join(_os.path.dirname(__file__), "..",
                                      "tools", "sweep_binned.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    want = [tuple(g)[:5] + (g.grt or B._GROUP_ROW_TARGET, 0)
            for g in (B.GEOM_MID, B.GEOM_MID_WIDE, B.GEOM_SPARSE,
                      B.GEOM_SPARSE_WIDE, B.GEOM_XSPARSE)]
    # flat A/B leg: GEOM_FLAT_SPARSE at the production group target,
    # paired against the same-shape GEOM_SPARSE row above
    want.append(tuple(B.GEOM_FLAT_SPARSE)[:5]
                + (B.GEOM_FLAT_SPARSE.grt or B._GROUP_ROW_TARGET, 1))
    assert mod.CONFIGS_PRODUCTS == want, (mod.CONFIGS_PRODUCTS, want)


def test_binned_fuzz_plan_and_run():
    """Property fuzz: random geometries through both plan builders and the
    interpret-mode kernels must match the oracle (and each other)."""
    from roc_tpu import native
    from roc_tpu.ops.pallas.binned import _build_binned_plan_numpy

    rng = np.random.default_rng(2026)
    for trial in range(6):
        n = int(rng.integers(40, 3000))
        t = int(rng.integers(40, 3000))
        e = int(rng.integers(0, 25000))
        tgt = int(rng.integers(1 << 12, 1 << 16))
        src = rng.integers(0, t, e).astype(np.int64)
        dst = rng.integers(0, n, e).astype(np.int64)
        if e and trial % 2:
            dst[: e // 3] = int(rng.integers(0, n))   # random hub
        x = rng.standard_normal((t, 8), dtype=np.float32)
        plan = _build_binned_plan_numpy(src, dst, n, t, tgt)
        out = np.asarray(run_binned(jnp.asarray(x), plan, interpret=True))
        ref = oracle_bf16(x, src, dst, n)
        np.testing.assert_allclose(
            out, ref, rtol=1e-5, atol=1e-3,
            err_msg=f"trial {trial}: n={n} t={t} e={e} tgt={tgt}")
        if native.available():
            nat = native.binned_plan(src, dst, n, t, tgt)
            np.testing.assert_array_equal(nat[1], np.asarray(plan.p1_off),
                                          err_msg=f"trial {trial}")

def test_plan_steps_match_built_plans():
    """_plan_steps (the cost model's schedule predictor) must EXACTLY
    reproduce the built plan's grid shape.  It re-implements the builder
    arithmetic in O(cells); any drift silently mis-prices every candidate
    choose_geometry weighs, so this pin is what lets the grid-validation
    test below use model steps as build truth."""
    from roc_tpu.ops.pallas import binned as B
    rng = np.random.default_rng(7)
    shapes = [(3000, 40_000, 0), (20_000, 80_000, 0), (20_000, 80_000, 512)]
    for g in (B._default_geom(), B.GEOM_MID, B.GEOM_SPARSE_WIDE,
              B.GEOM_FLAT, B.GEOM_FLAT_SPARSE):
        for n, e, q in shapes:
            if q:                     # block-diagonal community locality
                comm = rng.integers(0, n // q, e) * q
                src = (comm + rng.integers(0, q, e)).astype(np.int64)
                dst = (comm + rng.integers(0, q, e)).astype(np.int64)
            else:
                src = rng.integers(0, n, e).astype(np.int64)
                dst = rng.integers(0, n, e).astype(np.int64)
            cblk, cbin, cnt = B._cell_stats(src, dst, g.sb, g.rb)
            padded, s1, s2 = B._plan_steps(cblk, cbin, cnt, g, n, n, e)
            plan = B.build_binned_plan(src, dst, n, n, geom=g)
            G, C1 = plan.p1_blk.shape
            C2 = plan.p2_obi.shape[1]
            assert (s1, s2) == (G * C1, G * C2), \
                (g, n, e, q, (s1, s2), (G * C1, G * C2))
            assert padded == B.padded_rows_for(src, dst, g)


def test_cost_model_grid_validation():
    """Tentpole check: across the CPU-reachable grid (two scales x three
    densities x {uniform, community-reordered}), choose_geometry must pick
    the measured-cheapest candidate — 'measured' meaning the calibrated
    cost model evaluated at the BUILD-TRUTH step counts of actually built
    plans (anchored to the builder by test_plan_steps_match_built_plans).
    >= 90% of grid cells must agree; a hybrid pick counts as agreeing when
    its base geometry is the pure winner."""
    from roc_tpu.ops.pallas import binned as B
    rng = np.random.default_rng(11)
    cands = [B._default_geom(), B.GEOM_WIDE, B.GEOM_MID, B.GEOM_MID_WIDE,
             B.GEOM_SPARSE, B.GEOM_SPARSE_WIDE, B.GEOM_XSPARSE,
             B.GEOM_FLAT, B.GEOM_FLAT_SPARSE]
    cells = []
    for n in (8192, 24576):
        for deg in (4, 16, 48):
            e = n * deg
            src = rng.integers(0, n, e).astype(np.int64)
            dst = rng.integers(0, n, e).astype(np.int64)
            cells.append((n, deg, "uniform", src, dst))
            q = 512
            comm = rng.integers(0, n // q, e) * q
            cells.append((n, deg, "reordered",
                          (comm + rng.integers(0, q, e)).astype(np.int64),
                          (comm + rng.integers(0, q, e)).astype(np.int64)))
    match, mismatches = 0, []
    for n, deg, order, src, dst in cells:
        truth = {}
        for g in cands:
            g = g.check()
            if B._vmem_bytes(g) > B._VMEM_NOMINAL_CAP:
                continue
            plan = B.build_binned_plan(src, dst, n, n, geom=g)
            G, C1 = plan.p1_blk.shape
            C2 = plan.p2_obi.shape[1]
            truth[g] = B._binned_cost_model(
                B.padded_rows_for(src, dst, g), g,
                steps1=G * C1, steps2=G * C2)
        best_true = min(truth, key=truth.get)
        pick, _ = B.choose_geometry(src, dst, n, n, force=True)
        if pick is not None and pick._replace(hub_minc=0) == best_true:
            match += 1
        else:
            mismatches.append((n, deg, order, pick, best_true))
    assert match >= 0.9 * len(cells), (match, len(cells), mismatches)


def _chip_table():
    import json
    import os as _os
    from roc_tpu.ops.pallas import binned as B
    path = _os.path.join(_os.path.dirname(B.__file__),
                         "binned_chip_table.json")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def test_cost_model_reproduces_chip_table():
    """The re-fit model against the device times it was fit to (PR 24,
    one v5e chip, ops/pallas/binned_chip_table.json): every row's phase 1
    + phase 2 at width 256 within 15 %, each phase's own rate families
    included, and within a cell the model orders the presets as the chip
    does (pairs the chip holds under 2 % apart count as ties).  The table
    says what it is, and its rates are the module's."""
    from roc_tpu.ops.pallas import binned as B
    doc = _chip_table()
    for key in ("platform", "device_kind", "jax", "libtpu", "date", "pr"):
        assert doc.get(key), key
    assert doc["platform"] == "tpu" and doc["pr"] == 24
    assert doc["rates"] == {
        "mxu_flops": B._MXU_EFF_FLOPS, "p1_step_s": B._CHUNK_OVERHEAD_S,
        "p2_row_s": B._P2_ROW_S, "slot_dma_s": B._SLOT_DMA_S,
        "flat_slot_s": B._FLAT_SLOT_S, "flat_copy_s": B._FLAT_COPY_S}
    by_cell = {}
    for row in doc["rows"]:
        geom = B.Geometry(*row["geom"]).check()
        measured = (row["p1_ms"]["256"] + row["p2_ms"]["256"]) / 1e3
        model = B._binned_cost_model(
            row["padded_rows"], geom, H=256, steps1=row["steps1"],
            steps2=row["steps2"], copies=row.get("copies"))
        assert abs(model / measured - 1) <= 0.15, \
            (row["cell"], row["preset"], model, measured)
        if not geom.flat:
            assert row["slot_dmas"] == row["padded_rows"] // geom.slot
        by_cell.setdefault(row["cell"], []).append(
            (measured, model, row["preset"]))
    assert len(doc["rows"]) >= 10 and len(by_cell) == 2
    for cell, rows in by_cell.items():
        for m_a, p_a, n_a in rows:
            for m_b, p_b, n_b in rows:
                if m_a < 0.98 * m_b:
                    assert p_a < p_b, (cell, n_a, n_b, (m_a, p_a),
                                       (m_b, p_b))


def test_choose_geometry_memory_admission(monkeypatch):
    """A candidate whose per-group temporaries (staging + the larger
    index operand, lane-dense: 4 bytes an index, from shapes) are over
    _HBM_GROUP_CAP is skipped as a VMEM-inadmissible one is: at the chip
    table's own counts GEOM_WIDE (5.51 GB of staging a group; 12.69 GiB
    of peak HBM measured by PR 24) is out and the default and GEOM_FLAT
    are in; and on a graph where the wider group prices lowest, lowering
    the cap between the two candidates' needs moves the pick."""
    from roc_tpu.ops.pallas import binned as B
    need = {}
    for row in _chip_table()["rows"]:
        if row["cell"].endswith(".regular"):
            need[row["preset"]] = B._group_hbm_bytes(
                B.Geometry(*row["geom"]), row["steps1"], row["steps2"],
                row["groups"])
    assert need["default"] < need["flat"] < B._HBM_GROUP_CAP < need["wide"]
    assert 1.3e9 < need["default"] < 1.4e9 and 5.4e9 < need["wide"] < 5.6e9
    # the index operand is the plan's own bytes, not 128 lanes a row:
    # 11.3 MB of the default group's 1.351 GB, where PR 24 counted 1.44 GB
    row = next(r for r in _chip_table()["rows"]
               if r["cell"].endswith(".regular") and r["preset"] == "default")
    g = B.Geometry(*row["geom"])
    stg = row["steps2"] // row["groups"] * g.ch2 * B._MODEL_H * 2
    assert need["default"] - stg == row["steps1"] // row["groups"] * g.ch * 4

    rng = np.random.default_rng(9)
    n, e = 16384, 32 * 32 * 113
    src = rng.integers(0, n, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    small = B._default_geom()._replace(grt=1 << 14)    # 8 groups
    big = B._default_geom()._replace(grt=1 << 17)      # 1 group
    bytes_of = {}
    for g in (small, big):
        cblk, cbin, cnt = B._cell_stats(src, dst, g.sb, g.rb)
        _, s1, s2 = B._plan_steps(cblk, cbin, cnt, g, n, n, e)
        bytes_of[g] = B._group_hbm_bytes(
            g, s1, s2, B._plan_groups(g, n, n, e)[3])
    assert bytes_of[small] < bytes_of[big]
    pick, _ = B.choose_geometry(src, dst, n, n, candidates=[small, big],
                                force=True)
    assert pick == big                      # fewer groups, fewer steps
    monkeypatch.setattr(B, "_HBM_GROUP_CAP",
                        (bytes_of[small] + bytes_of[big]) // 2)
    pick, t = B.choose_geometry(src, dst, n, n, candidates=[small, big],
                                force=True)
    assert pick == small, (pick, t)
    # nothing admissible: no pick, as when every candidate is over VMEM
    monkeypatch.setattr(B, "_HBM_GROUP_CAP", 1)
    assert B.choose_geometry(src, dst, n, n, candidates=[small, big],
                             force=True)[0] is None


def test_hybrid_forced_correctness():
    """Hybrid binned+matmul plan (hub_minc split), forced via an explicit
    geometry on a bimodal cell structure: one fat dense cell plus a dust
    spray of ~6-edge cells.  Both sides must contribute — fwd against the
    np.add.at oracle and the VJP against the transpose scatter, exactly
    (fp32 staging, 'exact' precision)."""
    from roc_tpu.ops import aggregate as A
    from roc_tpu.ops.pallas import binned as B
    rng = np.random.default_rng(1)
    n = 3000
    dsrc = rng.integers(0, 512, 4000)       # (block 0, bin 0): dense hub
    ddst = rng.integers(0, 512, 4000)
    tsrc = rng.integers(0, n, 200)          # dust over the whole grid
    tdst = rng.integers(0, n, 200)
    src = np.concatenate([dsrc, tsrc]).astype(np.int64)
    dst = np.concatenate([ddst, tdst]).astype(np.int64)
    g = B._default_geom()._replace(hub_minc=64)
    keep = B.split_hub_edges(src, dst, g)
    assert 0 < int(keep.sum()) < len(src)
    plans = A.build_binned_plans(src, dst, n, n, geom=(g, "auto"))
    assert plans.mm is not None

    h = 16
    x = rng.standard_normal((n, h), dtype=np.float32)
    out = A.scatter_gather_binned(jnp.asarray(x), plans, precision="exact",
                                  interpret=True)
    ref = np.zeros((n, h), np.float32)
    np.add.at(ref, dst, x[src])
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-4)

    w = rng.standard_normal((n, h), dtype=np.float32)
    gx = jax.grad(lambda xx: jnp.sum(
        A.scatter_gather_binned(xx, plans, precision="exact",
                                interpret=True) * w))(jnp.asarray(x))
    gref = np.zeros((n, h), np.float32)
    np.add.at(gref, src, w[dst])
    np.testing.assert_allclose(np.asarray(gx), gref, rtol=1e-5, atol=1e-4)


def test_choose_geometry_hybrid_arm(monkeypatch):
    """The policy's hybrid arm: dust cells well under half a slot next to
    a heavy hub mass make the split win over pure matmul (the hub edges'
    chunk cost) by price and over pure binned by memory — the dust's slot
    padding puts a group's staging (3.2 GiB) over the cap set here, and
    the split is admitted on its own, smaller, schedule.  (At PR 24's
    rates the pure schedule prices 8 % under the split; until PR 26 it
    was the 128-lane index operand that kept it out, under the real cap.)
    Restricted to the dense default candidate so the sparse presets
    can't absorb the dust first.  The returned hub_minc must agree with
    split_hub_edges."""
    from roc_tpu.ops.pallas import binned as B
    monkeypatch.setattr(B, "_HBM_GROUP_CAP", 2 * (1 << 30))
    rng = np.random.default_rng(2)
    n = 100_000
    g0 = B._default_geom()
    nblk, nbin = -(-n // g0.sb), -(-n // g0.rb)
    cells = rng.permutation(nblk * nbin)
    ds = np.repeat(cells // nbin, 10) * g0.sb \
        + rng.integers(0, g0.sb, cells.size * 10)
    dd = np.repeat(cells % nbin, 10) * g0.rb \
        + rng.integers(0, g0.rb, cells.size * 10)
    hub = cells[:40]
    he = 50_000
    hs = np.repeat(hub // nbin, he) * g0.sb + rng.integers(0, g0.sb, 40 * he)
    hd = np.repeat(hub % nbin, he) * g0.rb + rng.integers(0, g0.rb, 40 * he)
    src = np.clip(np.concatenate([ds, hs]), 0, n - 1)
    dst = np.clip(np.concatenate([dd, hd]), 0, n - 1)
    g, t = B.choose_geometry(src, dst, n, n, candidates=[g0])
    assert g is not None and g.hub_minc == g0.slot // 2, (g, t)
    assert t < B._matmul_cost(len(src), n)
    keep = B.split_hub_edges(src, dst, g)
    _, _, cnt = B._cell_stats(src, dst, g.sb, g.rb)
    assert int(keep.sum()) == int(cnt[cnt >= g.hub_minc].sum())
    # the full candidate list absorbs the dust with a sparse preset
    # instead — hybrid is the fallback when dense windows are forced
    g_full, t_full = B.choose_geometry(src, dst, n, n)
    assert g_full is not None and t_full <= t


def test_skewed_powerlaw_binned_selected_matches_xla():
    """Products-shape skewed synthetic (power-law out-degrees): the
    measured-stats policy must select binned over matmul, and the built
    plans must reproduce the XLA segment-sum backend exactly at 'exact'
    precision."""
    from roc_tpu.ops import aggregate as A
    from roc_tpu.ops.pallas import binned as B
    rng = np.random.default_rng(13)
    n = 20_000
    deg = np.minimum(rng.pareto(1.1, n) + 1, 500).astype(np.int64)
    dst = np.repeat(np.arange(n, dtype=np.int64), deg)
    src = rng.integers(0, n, dst.size).astype(np.int64)
    g, t = B.choose_geometry(src, dst, n, n)
    assert g is not None, (g, t)
    assert B.binned_viable(n, n, dst.size, src, dst)

    plans = A.build_binned_plans(src, dst, n, n, geom=(g, "auto"))
    h = 16
    x = rng.standard_normal((n, h), dtype=np.float32)
    out = A.scatter_gather_binned(jnp.asarray(x), plans, precision="exact",
                                  interpret=True)
    ref = jax.ops.segment_sum(jnp.asarray(x)[src], jnp.asarray(dst),
                              num_segments=n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_plan_cache_roundtrip(tmp_path, monkeypatch):
    """Content-keyed on-disk plan cache: second build with identical
    inputs must come from the cache file (the builder is poisoned to
    prove it) and match the first plan field for field."""
    from roc_tpu.ops.pallas import binned as B
    monkeypatch.setenv("ROC_PLAN_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("ROC_PLAN_CACHE_MIN_EDGES", "0")
    rng = np.random.default_rng(3)
    n, e = 4000, 30_000
    src = rng.integers(0, n, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    p1 = B.build_binned_plan(src, dst, n, n, geom=B.GEOM_MID)
    files = [f for f in tmp_path.iterdir() if f.suffix == ".npz"]
    assert len(files) == 1, files
    monkeypatch.setattr(B, "_build_binned_plan_numpy",
                        lambda *a, **k: pytest.fail("cache missed"))
    p2 = B.build_binned_plan(src, dst, n, n, geom=B.GEOM_MID)
    assert p2.geom == p1.geom == B.GEOM_MID
    assert p2.bins_per_group == p1.bins_per_group
    for f in ("p1_srcl", "p1_off", "p1_blk", "p2_dstl", "p2_obi",
              "p2_first"):
        np.testing.assert_array_equal(np.asarray(getattr(p1, f)),
                                      np.asarray(getattr(p2, f)), f)
    # a different geometry misses (key covers the schedule-shaping input)
    monkeypatch.setattr(B, "_build_binned_plan_numpy", _orig_numpy_builder)
    p3 = B.build_binned_plan(src, dst, n, n, geom=B.GEOM_SPARSE)
    assert p3.geom == B.GEOM_SPARSE
    assert len([f for f in tmp_path.iterdir() if f.suffix == ".npz"]) == 2


from roc_tpu.ops.pallas.binned import \
    _build_binned_plan_numpy as _orig_numpy_builder  # noqa: E402


# -- lane-dense index operands (PR 26) --------------------------------------

_FLAT_SMALL = dict(sb=256, ch=512, slot=128, rb=256, ch2=512, grt=1 << 14,
                   flat=1)


@pytest.mark.parametrize("precision", ["fast", "exact"])
@pytest.mark.parametrize("flat", [0, 1])
def test_staging_garbage_cannot_reach_the_result(flat, precision):
    """Phase 1 skips pad slots, so the staging rows behind them hold
    whatever the buffer held; phase 2 must mask them by the PLAN (its
    dstl == RB rows) before the dot, where 0 * NaN would be NaN.  Every
    such row is poisoned with NaN and Inf between the phases: the result
    stays finite and bit-equal to the unpoisoned run, at both precisions
    and on both schedules."""
    from roc_tpu.ops.pallas import binned as B
    rng = np.random.default_rng(26)
    n = t = 1500
    e, h = 9000, 40
    src = rng.integers(0, t, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    x = rng.standard_normal((t, h), dtype=np.float32)
    geom = B.Geometry(**dict(_FLAT_SMALL, grt=1 << 12)) if flat else None
    plan = build_binned_plan(src, dst, n, t, geom=geom,
                             group_row_target=1 << 12, tuned_ok=False)
    geom, exact = plan.geom, precision == "exact"
    G, C1 = plan.p1_blk.shape
    C2 = plan.p2_obi.shape[1]
    assert G > 1 and plan.p2_dstl.shape == (G, C2, geom.ch2)
    xp = jnp.pad(jnp.asarray(x), ((0, B._pad_to(t, geom.sb) - t),
                                  (0, 128 - h)))
    stg_rows, poisoned = C2 * geom.ch2, 0
    outs = {False: [], True: []}
    for g in range(G):
        if flat:
            stg = B._p1_flat_run(xp, plan.p1_blk[g], plan.p1_blk2[g],
                                 plan.p1_dsrc[g], plan.p1_ddst[g],
                                 plan.p1_srcl[g], C1, stg_rows, True, exact,
                                 geom)
        else:
            stg = B._p1_run(xp, plan.p1_blk[g], plan.p1_off[g],
                            plan.p1_srcl[g], C1, stg_rows, True, exact, geom)
        pad = np.asarray(plan.p2_dstl[g]).reshape(-1) == geom.rb
        poisoned += int(pad.sum())
        bad = np.where(np.arange(stg_rows) % 2, np.nan, np.inf)
        for poison in (False, True):
            if poison:
                stg = jnp.where(pad[:, None],
                                bad[:, None].astype(stg.dtype), stg)
            outs[poison].append(B._p2_run(
                stg, plan.p2_obi[g], plan.p2_first[g], plan.p2_dstl[g], C2,
                plan.bins_per_group * geom.rb, True, exact, geom))
    assert poisoned > e // 4            # padding is a real share of rows
    out = np.asarray(jnp.concatenate(outs[True]))[:n, :h]
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(
        out, np.asarray(jnp.concatenate(outs[False]))[:n, :h])
    want = np.zeros((n, h), np.float64)
    xs = x if exact else np.asarray(
        jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    np.add.at(want, dst, xs.astype(np.float64)[src])
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-4)


def _pallas_calls(jaxpr, found=None):
    """Every pallas_call equation of a jaxpr, sub-jaxprs included."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, found)
    return found


def _column_index_operands(jaxpr):
    """(kernel, operand aval) of every int32 operand a pallas_call reads
    through VMEM whose last dimension is not whole 128-lane rows: Mosaic
    tiles such an operand to 128 lanes a row — 512 bytes an index — and
    XLA re-lays the plan's slice out to that every scan step."""
    from jax.experimental.pallas import tpu as pltpu
    bad, kernels = [], []
    for eqn in _pallas_calls(jaxpr):
        gm = eqn.params["grid_mapping"]
        kernels.append(eqn.params["name"]
                       or eqn.params["jaxpr"].debug_info.func_name)
        ins = eqn.invars[gm.num_index_operands:][:gm.num_inputs]
        for var, bm in zip(ins, gm.block_mappings):
            aval = var.aval
            if aval.dtype != jnp.int32 or getattr(
                    bm.block_aval, "memory_space", None) == pltpu.SMEM:
                continue
            if aval.ndim == 0 or aval.shape[-1] % 128:
                bad.append((kernels[-1], str(aval)))
    return bad, kernels


def test_gcn_train_step_has_no_column_index_operand():
    """No [rows, 1] int32 operand is left in the GCN recipe's train step:
    every index operand of every kernel is scalar-prefetched, rides SMEM
    blocks, or is lane-dense.  A later plan field cannot bring the
    128-lane padding (a third of the Reddit epoch until PR 26) back
    unseen."""
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gcn
    from roc_tpu.train.config import Config
    from roc_tpu.train.driver import Trainer

    ds = datasets.synthetic("binned-lanes", 600, 6.0, 32, 5,
                            n_train=200, n_val=100, n_test=100, seed=3)
    cfg = Config(layers=[32, 16, 5], num_epochs=1, dropout_rate=0.5,
                 eval_every=10 ** 9, aggregate_backend="binned", seed=11)
    tr = Trainer(cfg, ds, build_gcn(cfg.layers, 0.5))
    assert tr.gdata.backend == "binned"
    args = (tr.params, tr.opt_state, tr.x, tr.labels, tr.mask, tr.gdata,
            jax.random.PRNGKey(0), jnp.float32(cfg.learning_rate),
            jnp.float32(1.0))
    bad, kernels = _column_index_operands(
        jax.make_jaxpr(tr._train_step)(*args).jaxpr)
    # two layers, forward and backward: four sweeps of both phases
    assert sum("_p1_kernel" in k for k in kernels) == 4, kernels
    assert sum("_p2_kernel" in k for k in kernels) == 4, kernels
    assert not bad, bad


def test_column_index_operand_is_seen():
    """The detector has teeth: the [rows, 1] form this PR removed, fed
    to a kernel through a (CH, 1) block, is reported; the flat schedule's
    kernels are clean."""
    from jax.experimental import pallas as pl
    from roc_tpu.ops.pallas import binned as B

    def column(idx, x):
        return pl.pallas_call(
            lambda i_ref, x_ref, o_ref: o_ref.__setitem__(
                slice(None), x_ref[:] + i_ref[:].astype(jnp.float32)),
            grid=(2,),
            in_specs=[pl.BlockSpec((256, 1), lambda c: (c, 0)),
                      pl.BlockSpec((256, 128), lambda c: (c, 0))],
            out_specs=pl.BlockSpec((256, 128), lambda c: (c, 0)),
            out_shape=jax.ShapeDtypeStruct((512, 128), jnp.float32),
            interpret=True, name="column_reader")(idx, x)

    bad, _ = _column_index_operands(jax.make_jaxpr(column)(
        jnp.zeros((512, 1), jnp.int32), jnp.zeros((512, 128))).jaxpr)
    assert bad == [("column_reader", "int32[512,1]")]

    rng = np.random.default_rng(1)
    src = rng.integers(0, 900, 6000).astype(np.int64)
    dst = rng.integers(0, 900, 6000).astype(np.int64)
    plan = build_binned_plan(src, dst, 900, 900, tuned_ok=False,
                             geom=B.Geometry(**_FLAT_SMALL))
    # (the fused family's f_rows is a column still, inside no scan)
    plan = dataclasses.replace(plan, f_meta=None)
    bad, kernels = _column_index_operands(jax.make_jaxpr(
        lambda x, p: run_binned(x, p, interpret=True))(
            jnp.zeros((900, 16)), plan).jaxpr)
    assert kernels and not bad, (kernels, bad)
