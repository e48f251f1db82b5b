"""Flat compacted chunk schedule + fused pipeline (ops/pallas/binned.py,
Geometry.flat) vs the slot-padded two-pass path and the oracles, in
interpret mode on CPU.  Hardware behavior: tests/test_tpu_hw.py.

Bit-equality tests use INTEGER-valued features and cotangents: small
integers survive the bf16 rounding and fp32 summation exactly, so the
flat schedule's different chunking (hence different fp32 add order) still
produces bit-identical sums.  Random fp32 data would differ at
reassociation level between the schedules — by design, same as chunk
order vs edge order in the two-pass path."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu import ops
from roc_tpu.ops.pallas import binned as B

# Small flat geometry for CPU interpret runs; slot rides along unused by
# the flat kernels but must keep the Geometry invariant (divides ch/ch2).
GF = B.Geometry(sb=256, ch=512, slot=128, rb=256, ch2=512, grt=1 << 14,
                flat=1)
GF2 = GF._replace(flat=0)           # the slot-padded control at same shape
GFB = GF._replace(unit=16)          # bf16-staging variant (16-row units)

CASES = [
    # (num_rows, table_rows, num_edges, hidden)
    (700, 700, 5000, 64),
    (1500, 2000, 30000, 64),    # multi-group, table != out rows
    (100, 100, 0, 64),          # empty edge list
    (GF.sb + 1, GF.sb + 1, 300, 16),    # two source blocks
    (3 * GF.rb, 1000, 3000, 16),        # partial last bin group
    (700, 700, 5000, 41),       # lane-unaligned H (GCN output layer)
]


def _int_graph(n, t, e, h, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, t, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    if e > 100:
        dst[: e // 4] = 7       # hub destination spanning many chunks
    x = rng.integers(-4, 5, (t, h)).astype(np.float32)
    return src, dst, x


def _oracle_int(x, src, dst, n):
    out = np.zeros((n, x.shape[1]), np.float32)
    np.add.at(out, dst, x[src])
    return out


@pytest.mark.parametrize("n,t,e,h", CASES)
@pytest.mark.parametrize("fuse", [False, True])
def test_flat_bit_equals_twopass_and_oracle(n, t, e, h, fuse, monkeypatch):
    """Flat schedule (both the fused pipeline and the scan fallback) must
    be BIT-identical to the existing two-pass path and the add.at oracle
    on integer data, fwd, at every case incl. lane-unaligned H=41."""
    if not fuse:
        monkeypatch.setenv("ROC_BINNED_NO_FUSE", "1")
    src, dst, x = _int_graph(n, t, e, h, 42)
    pf = B.build_binned_plan(src, dst, n, t, geom=GF)
    pt = B.build_binned_plan(src, dst, n, t, geom=GF2)
    if fuse:
        assert pf.f_meta is not None    # small cases must fuse
    out_f = np.asarray(B.run_binned(jnp.asarray(x), pf, interpret=True))
    out_t = np.asarray(B.run_binned(jnp.asarray(x), pt, interpret=True))
    np.testing.assert_array_equal(out_f, out_t)
    np.testing.assert_array_equal(out_f, _oracle_int(x, src, dst, n))
    # exact precision rides the same flat schedule
    out_e = np.asarray(B.run_binned(jnp.asarray(x), pf, interpret=True,
                                    precision="exact"))
    np.testing.assert_array_equal(out_e, _oracle_int(x, src, dst, n))


@pytest.mark.parametrize("fuse", [False, True])
def test_flat_bf16_unit_bit_equals_oracle(fuse, monkeypatch):
    """unit=16 flat plans stage in bf16 (16-row Mosaic tiles, half the
    staging-DMA bytes): primary and secondary chunk rows are disjoint, so
    every staged row is rounded to bf16 exactly once, and small-integer
    data survives that rounding — both run paths must stay BIT-identical
    to the add.at oracle, exactly like the fp32-staged flat plan."""
    if not fuse:
        monkeypatch.setenv("ROC_BINNED_NO_FUSE", "1")
    for n, t, e, h in [(700, 700, 5000, 64), (GF.sb + 1, GF.sb + 1, 300, 16),
                       (700, 700, 5000, 41)]:
        src, dst, x = _int_graph(n, t, e, h, 42)
        pb = B.build_binned_plan(src, dst, n, t, geom=GFB)
        assert pb.geom.unit == 16
        assert B.staging_dtype(pb.geom, False) == jnp.bfloat16
        if fuse:
            assert pb.f_meta is not None
        out = np.asarray(B.run_binned(jnp.asarray(x), pb, interpret=True))
        np.testing.assert_array_equal(out, _oracle_int(x, src, dst, n),
                                      err_msg=f"n={n} t={t} e={e} h={h}")


def test_flat_bf16_unit_rejects_exact():
    """precision='exact' contracts fp32 staging; a unit=16 plan can't
    provide it, and silently widening would desync gbuf/DMA dtypes — so
    run_binned must refuse."""
    src = np.array([0, 1], np.int64)
    dst = np.array([1, 0], np.int64)
    plan = B.build_binned_plan(src, dst, 32, 32, geom=GFB)
    with pytest.raises(ValueError, match="exact"):
        B.run_binned(jnp.ones((32, 16), jnp.float32), plan, interpret=True,
                     precision="exact")


def test_flat_bf16_staging_bytes_pin():
    """bf16-storage acceptance pin (same reddit_scaled shape as the
    kernel-budget gate): GEOM_FLAT_BF16 must move <= 0.6x GEOM_FLAT's
    predicted staging-DMA bytes.  Not a clean 0.5: the 16-row unit pads
    every touched cell to twice the fp32 unit's rows (~0.50 measured on
    this shape)."""
    n, e = 32768, 4_194_304
    rng = np.random.default_rng(0)
    src = rng.integers(0, n, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    b32 = B.staging_bytes_for(src, dst, B.GEOM_FLAT)
    b16 = B.staging_bytes_for(src, dst, B.GEOM_FLAT_BF16)
    assert b16 <= 0.6 * b32, (b16, b32, b16 / b32)


def test_flat_bwd_bit_equals_twopass_and_oracle():
    """VJP through the flat plans (integer cotangents) == the two-pass
    VJP == the transpose scatter, bitwise."""
    n, e, h = 900, 7000, 32
    src, dst, x = _int_graph(n, n, e, h, 7)
    g = np.random.default_rng(8).integers(-3, 4, (n, h)).astype(np.float32)
    plans_f = ops.build_binned_plans(src, dst, n, n, geom=GF)
    plans_t = ops.build_binned_plans(src, dst, n, n, geom=GF2)
    assert plans_f.fwd.geom == GF and plans_f.bwd.geom == GF
    gx = {}
    for name, plans in (("flat", plans_f), ("twopass", plans_t)):
        y, vjp = jax.vjp(
            lambda xx, p=plans: ops.scatter_gather_binned(xx, p, True),
            jnp.asarray(x))
        (gxi,) = vjp(jnp.asarray(g))
        gx[name] = np.asarray(gxi)
        np.testing.assert_array_equal(np.asarray(y),
                                      _oracle_int(x, src, dst, n), name)
    np.testing.assert_array_equal(gx["flat"], gx["twopass"])
    np.testing.assert_array_equal(gx["flat"], _oracle_int(g, dst, src, n))


def test_fused_bitwise_matches_flat_twopass_random_fp32(monkeypatch):
    """The fused pipeline replays the SAME per-chunk math as the flat
    two-pass scan (one-hot dots over identical chunks), so the two must
    agree bitwise even on random fp32 data — any divergence means the
    interleaved schedule visited chunks in a different per-bin order."""
    rng = np.random.default_rng(5)
    n, e, h = 1100, 20000, 48
    src = rng.integers(0, n, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    x = rng.standard_normal((n, h), dtype=np.float32)
    plan = B.build_binned_plan(src, dst, n, n, geom=GF)
    assert plan.f_meta is not None
    out_fused = np.asarray(B.run_binned(jnp.asarray(x), plan,
                                        interpret=True))
    monkeypatch.setenv("ROC_BINNED_NO_FUSE", "1")
    out_scan = np.asarray(B.run_binned(jnp.asarray(x), plan,
                                       interpret=True))
    np.testing.assert_array_equal(out_fused, out_scan)


def test_flat_sharded_bit_equals_single_device():
    """Stacked flat shard plans (fused lists stripped at stacking — one
    static program across shards) must reproduce the per-shard
    single-device flat results bitwise on integer data."""
    rng = np.random.default_rng(3)
    n, t, h = 400, 400, 16
    shard_plans, xs, refs = [], [], []
    for e in (900, 4000):
        src = rng.integers(0, t, e).astype(np.int64)
        dst = rng.integers(0, n, e).astype(np.int64)
        x = rng.integers(-4, 5, (t, h)).astype(np.float32)
        shard_plans.append(ops.build_binned_plans(src, dst, n, t, geom=GF))
        xs.append(x)
        refs.append(_oracle_int(x, src, dst, n))
    stacked = ops.pad_binned_plans(shard_plans)
    # fused step lists bake in per-shard chunk counts -> must be stripped
    assert stacked.fwd.f_meta is None and stacked.bwd.f_meta is None
    assert stacked.fwd.geom == GF
    for i in range(2):
        one = jax.tree.map(lambda a: a[i], stacked)
        out = np.asarray(ops.scatter_gather_binned(
            jnp.asarray(xs[i]), one, True))
        np.testing.assert_array_equal(out, refs[i], err_msg=f"shard {i}")


def test_flat_padded_plan_bit_equal():
    """pad_binned_plan on a flat plan: padded chunks are exact no-ops
    (srcl -1 one-hot rows, dstl RB masks), so outputs stay bit-identical."""
    src, dst, x = _int_graph(3 * GF.rb, 1000, 3000, 16, 9)
    plan = B.build_binned_plan(src, dst, 3 * GF.rb, 1000, geom=GF)
    padded = B.pad_binned_plan(plan, plan.p1_blk.shape[1] + 8,
                               plan.p2_obi.shape[1] + 3)
    assert padded.geom == GF
    a = np.asarray(B.run_binned(jnp.asarray(x), plan, interpret=True))
    b = np.asarray(B.run_binned(jnp.asarray(x), padded, interpret=True))
    np.testing.assert_array_equal(a, b)


def test_flat_step_reduction_pin():
    """Tentpole acceptance pin (Reddit-scale shape, the kernel_budgets
    table's reddit_scaled row): GEOM_FLAT must predict >= 25% fewer total
    grid steps than the shipped SLOT=128 default, with pad1 <= 1.05."""
    n, e = 32768, 4_194_304
    rng = np.random.default_rng(0)
    src = rng.integers(0, n, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    totals = {}
    for name, g in (("default", B._default_geom()), ("flat", B.GEOM_FLAT)):
        cb, cn, cnt = B._cell_stats(src, dst, g.sb, g.rb)
        padded, s1, s2 = B._plan_steps(cb, cn, cnt, g, n, n, e)
        totals[name] = s1 + s2
        if name == "flat":
            assert padded <= 1.05 * e, (padded, e)    # pad1 bound
    assert totals["flat"] <= 0.75 * totals["default"], totals


def test_flat_plan_steps_match_built_plans():
    """_plan_steps must EXACTLY reproduce the flat builder's grid shape
    (same pin as the two-pass schedules — any drift mis-prices every flat
    candidate choose_geometry weighs)."""
    rng = np.random.default_rng(7)
    for g in (GF, B.GEOM_FLAT_SPARSE):
        for n, e in ((3000, 40_000), (20_000, 80_000)):
            src = rng.integers(0, n, e).astype(np.int64)
            dst = rng.integers(0, n, e).astype(np.int64)
            cblk, cbin, cnt = B._cell_stats(src, dst, g.sb, g.rb)
            padded, s1, s2 = B._plan_steps(cblk, cbin, cnt, g, n, n, e)
            plan = B.build_binned_plan(src, dst, n, n, geom=g)
            G, C1 = plan.p1_blk.shape
            C2 = plan.p2_obi.shape[1]
            assert (s1, s2) == (G * C1, G * C2), \
                (g, n, e, (s1, s2), (G * C1, G * C2))
            assert padded == B.padded_rows_for(src, dst, g)


def test_native_flat_plan_equals_numpy():
    """The C++ flat builder must match the NumPy flat oracle bit for bit
    (chunk packing, run-list DMA metadata, and the phase-2 layout)."""
    from roc_tpu import native
    if not native.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(13)
    for geom in (GF, B.GEOM_FLAT_SPARSE._replace(grt=1 << 14), GFB):
        for (n, t, e) in [(700, 700, 5000), (3 * geom.rb, 1000, 3000),
                          (5000, 4000, 120000), (100, 100, 0)]:
            src = rng.integers(0, t, e).astype(np.int64)
            dst = rng.integers(0, n, e).astype(np.int64)
            if e > 100:
                dst[: e // 4] = 7
            ref = B._build_flat_plan_numpy(src, dst, n, t, 1 << 14, geom)
            (p1_srcl, p1_blk, p1_blk2, p1_dsrc, p1_ddst, p2_dstl, p2_obi,
             p2_first, bpg) = native.binned_flat_plan(
                 src, dst, n, t, 1 << 14, geom)
            msg = f"geom={geom} n={n} t={t} e={e}"
            assert bpg == ref.bins_per_group, msg
            G, C1 = p1_blk.shape
            C2 = p2_obi.shape[1]
            np.testing.assert_array_equal(
                p1_srcl.reshape(G, C1, geom.ch),
                np.asarray(ref.p1_srcl), err_msg=msg)
            for f, got in (("p1_blk", p1_blk), ("p1_blk2", p1_blk2),
                           ("p2_obi", p2_obi), ("p2_first", p2_first)):
                np.testing.assert_array_equal(
                    got, np.asarray(getattr(ref, f)), err_msg=f"{msg} {f}")
            np.testing.assert_array_equal(
                p1_dsrc.reshape(G, C1, geom.kd), np.asarray(ref.p1_dsrc),
                err_msg=msg)
            np.testing.assert_array_equal(
                p1_ddst.reshape(G, C1, geom.kd), np.asarray(ref.p1_ddst),
                err_msg=msg)
            np.testing.assert_array_equal(
                p2_dstl.reshape(G, C2, geom.ch2),
                np.asarray(ref.p2_dstl), err_msg=msg)


def test_flat_plan_cache_roundtrip(tmp_path, monkeypatch):
    """Flat plans round-trip the content-keyed cache: every schedule array
    is restored, and the fused step list (deliberately NOT cached) is
    rebuilt identically by _attach_fused at load."""
    monkeypatch.setenv("ROC_PLAN_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("ROC_PLAN_CACHE_MIN_EDGES", "0")
    rng = np.random.default_rng(3)
    n, e = 4000, 30_000
    src = rng.integers(0, n, e).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    p1 = B.build_binned_plan(src, dst, n, n, geom=GF)
    assert len([f for f in tmp_path.iterdir() if f.suffix == ".npz"]) == 1
    monkeypatch.setattr(B, "_build_binned_plan_numpy",
                        lambda *a, **k: pytest.fail("cache missed"))
    p2 = B.build_binned_plan(src, dst, n, n, geom=GF)
    assert p2.geom == GF and p2.bins_per_group == p1.bins_per_group
    assert (p1.f_meta is None) == (p2.f_meta is None)
    for f in B._PLAN_DATA_FIELDS:
        a, b = getattr(p1, f), getattr(p2, f)
        assert (a is None) == (b is None), f
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), f)
    # the flat bit is part of the key: same shape, flat=0, must MISS
    monkeypatch.setattr(B, "_build_binned_plan_numpy", _ORIG_NUMPY)
    p3 = B.build_binned_plan(src, dst, n, n, geom=GF2)
    assert p3.geom == GF2
    assert len([f for f in tmp_path.iterdir() if f.suffix == ".npz"]) == 2
    # ... and so is the staging unit: unit=16 (bf16) at the same windows
    # must MISS too — a cached fp32-unit plan served to a bf16 run would
    # stage through the wrong dtype
    p4 = B.build_binned_plan(src, dst, n, n, geom=GFB)
    assert p4.geom == GFB
    assert len([f for f in tmp_path.iterdir() if f.suffix == ".npz"]) == 3


def test_run_binned_warns_once_outside_jit():
    """The eager path is a silent ~9x dispatch-overhead footgun: exactly
    one process-wide warning, and none under jit."""
    import warnings as W
    src = np.array([0, 1], np.int64)
    dst = np.array([1, 0], np.int64)
    plan = B.build_binned_plan(src, dst, 8, 8, group_row_target=1 << 14)
    x = jnp.ones((8, 8), jnp.float32)
    B._EAGER_WARNED[0] = False
    with W.catch_warnings(record=True) as rec:
        W.simplefilter("always")
        B.run_binned(x, plan, interpret=True)
        B.run_binned(x, plan, interpret=True)
    assert len([w for w in rec if "outside a jit trace" in
                str(w.message)]) == 1
    B._EAGER_WARNED[0] = False
    with W.catch_warnings(record=True) as rec:
        W.simplefilter("always")
        jax.jit(lambda v: B.run_binned(v, plan, interpret=True))(x)
    assert not [w for w in rec if "outside a jit trace" in str(w.message)]
    assert not B._EAGER_WARNED[0]


def test_build_binned_plans_accepts_bare_geometry():
    """Regression: a bare Geometry (itself a NamedTuple) means 'both
    directions' — it must not be unpacked as a (fwd, bwd) pair."""
    src = np.array([0, 1, 2], np.int64)
    dst = np.array([1, 2, 0], np.int64)
    plans = ops.build_binned_plans(src, dst, 8, 8, geom=B.GEOM_SPARSE)
    assert plans.fwd.geom == B.GEOM_SPARSE
    assert plans.bwd.geom == B.GEOM_SPARSE
    plans2 = ops.build_binned_plans(src, dst, 8, 8,
                                    geom=(B.GEOM_SPARSE, B.GEOM_MID))
    assert plans2.fwd.geom == B.GEOM_SPARSE
    assert plans2.bwd.geom == B.GEOM_MID


def test_spmd_flat_env_flag(monkeypatch):
    """ROC_BINNED_FLAT=1 is the hardware A/B lever: the SPMD trainer's
    shard plans come out flat, and training still tracks the xla path."""
    monkeypatch.setenv("ROC_BINNED_FLAT", "1")
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gcn
    from roc_tpu.parallel.spmd import SpmdTrainer
    from roc_tpu.train.config import Config

    ds = datasets.synthetic("bf", 220, 4.0, 8, 4, n_train=40, n_val=40,
                            n_test=40, seed=3)
    base = dict(layers=[8, 8, 4], num_epochs=2, dropout_rate=0.0,
                eval_every=10 ** 9, num_parts=4, halo=True,
                edge_shard="off")
    tx = SpmdTrainer(Config(**base), ds, build_gcn(base["layers"], 0.0))
    tb = SpmdTrainer(Config(**base, aggregate_backend="binned"), ds,
                     build_gcn(base["layers"], 0.0))
    assert tb.gdata.backend == "binned"
    assert tb.gdata.plans.fwd.geom.flat == 1, tb.gdata.plans.fwd.geom
    for i in range(2):
        lx, lb = float(tx.run_epoch()), float(tb.run_epoch())
        np.testing.assert_allclose(lb, lx, rtol=5e-3, err_msg=f"epoch {i}")


_ORIG_NUMPY = B._build_binned_plan_numpy


def test_fused_plan_steps_match_built_plan():
    """The offline step predictor must equal the BUILT fused schedule's
    grid size, and decline exactly where _attach_fused does."""
    n, t, e, h = 1500, 2000, 30000, 64
    src, dst, _ = _int_graph(n, t, e, h, 21)
    plan = B.build_binned_plan(src, dst, n, t, geom=GF)
    assert plan.f_meta is not None
    cb, cn, cnt = B._cell_stats(src, dst, GF.sb, GF.rb)
    assert B.fused_plan_steps(cb, cn, cnt, GF, n, t, e) \
        == int(plan.f_blk.shape[0])
    assert B.fused_plan_steps(cb, cn, cnt, GF2, n, t, e) is None


def test_bf16_staging_units_are_flat_only():
    """The 16-row bf16 STAGING UNIT exists only on the flat schedule — a
    non-flat unit=16 geometry is a construction error (the slot-padded
    schedule's 8-row cells would tear the bf16 (16, 128) Mosaic tile).
    The slot schedule keeps its precision-keyed contract (bf16 fast /
    fp32 exact); the flat schedule's dtype is a pure function of the
    geometry."""
    with pytest.raises(AssertionError, match="flat"):
        B.Geometry(sb=256, ch=512, slot=128, rb=256, ch2=512,
                   unit=16).check()
    slot_geom = B.Geometry(sb=256, ch=512, slot=128, rb=256, ch2=512)
    assert B.staging_dtype(slot_geom, False) == jnp.bfloat16
    assert B.staging_dtype(slot_geom, True) == jnp.float32
    assert B.staging_dtype(GF, False) == jnp.float32    # 8-row unit
    assert B.staging_dtype(GFB, False) == jnp.bfloat16  # 16-row unit


def test_bf16_twopass_bitwise_vs_fp32_unit(monkeypatch):
    """With phase fusion OFF (two-pass flat schedule), bf16 16-row
    staging must still be bitwise the fp32 8-row unit's result on
    integer data — the staging dtype changes bytes moved, never sums."""
    monkeypatch.setenv("ROC_BINNED_NO_FUSE", "1")
    n, t, e, h = 700, 700, 5000, 64
    src, dst, x = _int_graph(n, t, e, h, 42)
    p32 = B.build_binned_plan(src, dst, n, t, geom=GF)
    p16 = B.build_binned_plan(src, dst, n, t, geom=GFB)
    o32 = np.asarray(B.run_binned(jnp.asarray(x), p32, interpret=True))
    o16 = np.asarray(B.run_binned(jnp.asarray(x), p16, interpret=True))
    np.testing.assert_array_equal(o16, o32)


def test_kernel_budget_table_is_current():
    """tools/kernel_budgets.json is what tools/check_kernel_budgets.py
    computes today, and its two claims (flat steps, bf16 staging bytes;
    the streamed bytes) hold: the preflight gate, as a test."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "check_kernel_budgets.py")
    spec = importlib.util.spec_from_file_location("check_kernel_budgets",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([]) == 0
