"""Hardware-gated kernel tests — run ONLY on a real TPU backend.

Interpret-mode tests have twice let Mosaic lowering bugs ship (commit
ced977f's sublane-tiling bug, then round-1's per-row HBM DMA slices that
cannot lower at all; docs/PERF.md).  These tests execute the compiled
kernels on the chip.  Under the repo's pytest conftest the platform is
pinned to CPU, so they skip there; run them on hardware with:

    JAX_PLATFORMS='' python -m pytest tests/test_tpu_hw.py -q -p no:cacheprovider \
        --override-ini= -o addopts=  # or simply: python tests/test_tpu_hw.py
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

# direct `python tests/test_tpu_hw.py`: the repo root is not on sys.path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

tpu = jax.default_backend() == "tpu"
pytestmark = pytest.mark.skipif(not tpu, reason="requires a real TPU backend")

from tests.test_binned import oracle_bf16 as _oracle_bf16


def _cases():
    rng = np.random.default_rng(0)
    # h=41 pins the lane-unaligned path (the GCN output layer): Mosaic
    # rejects DMA slices not aligned to the 128-lane tile, so run_binned
    # must pad H internally — only a hardware run can see that failure.
    for (n, t, e, h) in [(2000, 2000, 60000, 128),
                        (3000, 4000, 100000, 256),
                        (2000, 2000, 60000, 41)]:
        src = rng.integers(0, t, e).astype(np.int64)
        dst = rng.integers(0, n, e).astype(np.int64)
        dst[: e // 5] = 11                      # hub destination
        x = rng.standard_normal((t, h), dtype=np.float32)
        yield n, t, src, dst, x


def test_binned_compiles_and_matches_on_hw():
    from roc_tpu.ops.pallas.binned import build_binned_plan, run_binned
    for n, t, src, dst, x in _cases():
        plan = build_binned_plan(src, dst, n, t, group_row_target=1 << 17)
        out = np.asarray(run_binned(jnp.asarray(x), plan, interpret=False))
        ref = _oracle_bf16(x, src, dst, n)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=5e-2)


def test_binned_vjp_on_hw():
    from roc_tpu import ops
    n, t, src, dst, x = next(_cases())
    plans = ops.build_binned_plans(src, dst, n, t)
    g = np.random.default_rng(5).standard_normal((n, x.shape[1]),
                                                 dtype=np.float32)
    _, vjp = jax.vjp(lambda x: ops.scatter_gather_binned(x, plans, False),
                     jnp.asarray(x))
    (gx,) = vjp(jnp.asarray(g))
    ref = _oracle_bf16(g, dst, src, t)
    np.testing.assert_allclose(np.asarray(gx), ref, rtol=1e-4, atol=5e-2)


def test_matmul_backend_on_hw():
    from roc_tpu import ops
    n, t, src, dst, x = next(_cases())
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    plans = ops.build_aggregate_plans(src, dst, n, t)
    out = np.asarray(ops.scatter_gather_matmul(jnp.asarray(x), plans, n, t))
    ref = np.zeros((n, x.shape[1]), np.float32)
    np.add.at(ref, dst, x[src].astype(np.float32))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-3)


def test_matmul_fast_precision_on_hw():
    """fast precision (single-pass bf16 one-hot dots) must track the
    fp32-exact path to bf16 tolerance on real hardware — the rounding the
    CPU tests cannot exercise."""
    from roc_tpu import ops
    n, t, src, dst, x = next(_cases())
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    plans = ops.build_aggregate_plans(src, dst, n, t)
    exact = np.asarray(ops.scatter_gather_matmul(
        jnp.asarray(x), plans, n, t, "highest"))
    fast = np.asarray(ops.scatter_gather_matmul(
        jnp.asarray(x), plans, n, t, "default"))
    denom = np.maximum(np.abs(exact), 1.0)
    assert float(np.max(np.abs(fast - exact) / denom)) < 2e-2
    assert not np.allclose(fast, exact)   # bf16 rounding must be present


def test_binned_no_pipeline_fallback_on_hw():
    """The single-buffered phase-1 fallback (ROC_BINNED_NO_PIPELINE=1, the
    bisection baseline if the pipelined kernel misbehaves on a new Mosaic)
    must also compile and match on hardware."""
    import os

    from roc_tpu.ops.pallas import binned as B
    n, t, src, dst, x = next(_cases())
    plan = B.build_binned_plan(src, dst, n, t, group_row_target=1 << 17)
    os.environ["ROC_BINNED_NO_PIPELINE"] = "1"
    B._p1_run.clear_cache()                 # env is read at trace time
    try:
        out = np.asarray(B.run_binned(jnp.asarray(x), plan,
                                      interpret=False))
    finally:
        os.environ.pop("ROC_BINNED_NO_PIPELINE", None)
        B._p1_run.clear_cache()
    ref = _oracle_bf16(x, src, dst, n)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=5e-2)


def test_binned_avg_on_hw():
    """avg rides the binned sum backend divided by in-degree; check the
    full composition against the NumPy mean on the chip."""
    from roc_tpu import ops
    n, t, src, dst, x = next(_cases())
    plans = ops.build_binned_plans(src, dst, n, t)
    s = ops.scatter_gather_binned(jnp.asarray(x), plans, False)
    deg = np.zeros(n, np.float32)
    np.add.at(deg, dst, 1.0)
    out = np.asarray(ops.divide_by_degree(s, jnp.asarray(deg)))
    ref = _oracle_bf16(x, src, dst, n) / np.maximum(deg, 1.0)[:, None]
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=5e-2)


def test_binned_exact_on_hw():
    """precision="exact" (fp32 staging + 3-way split dots) compiled on the
    chip — the fp32 staging doubles the slot-DMA widths and the split adds
    two dots, both only provable under real Mosaic lowering.  Includes the
    lane-unaligned H=41 case."""
    from roc_tpu.ops.pallas.binned import build_binned_plan, run_binned
    for n, t, src, dst, x in _cases():
        plan = build_binned_plan(src, dst, n, t, group_row_target=1 << 17)
        out = np.asarray(run_binned(jnp.asarray(x), plan, interpret=False,
                                    precision="exact"))
        ref = np.zeros((n, x.shape[1]), np.float32)
        np.add.at(ref, dst, x[src])
        np.testing.assert_allclose(out, ref, rtol=2e-6, atol=1e-4)


def test_gat_plan_on_hw():
    """Plan-backend attention (scatter-free fwd+bwd) compiled on the chip:
    value + gradient against the dense oracle at a lane-unaligned F."""
    from roc_tpu import ops
    rng = np.random.default_rng(3)
    n, e, K, F = 3000, 90000, 4, 33          # F=33: lane-unaligned
    src = rng.integers(0, n, e).astype(np.int64)
    dst = np.sort(rng.integers(0, n, e).astype(np.int64))
    h = jnp.asarray(rng.standard_normal((n, K, F), dtype=np.float32))
    a_s = jnp.asarray(rng.standard_normal((K, F), dtype=np.float32))
    a_d = jnp.asarray(rng.standard_normal((K, F), dtype=np.float32))
    plans = ops.build_gat_plans(src, dst, n, n)
    es, ed = jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32)
    ref = ops.gat_attend(h, h, es, ed, n, a_s, a_d, 0.2)
    got = ops.gat_attend_plan(h, h, a_s, a_d, plans, (es, ed), 0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)

    def loss(fn):
        return lambda hh: jnp.sum(jnp.sin(fn(hh)))
    gr = jax.grad(loss(lambda hh: ops.gat_attend(
        hh, hh, es, ed, n, a_s, a_d, 0.2)))(h)
    gp = jax.grad(loss(lambda hh: ops.gat_attend_plan(
        hh, hh, a_s, a_d, plans, (es, ed), 0.2)))(h)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=1e-2, atol=1e-2)


def test_binned_sparse_geometries_on_hw():
    """Round-4 geometry presets compiled on the chip — slot-16 staging
    DMAs, the shrunken ch/ch2 chunks, and the 2048-row windows are new
    Mosaic surface that interpret mode cannot vet (two interpret-only
    escapes shipped before; docs/PERF.md).  Both precisions, incl. a
    lane-unaligned H."""
    from roc_tpu.ops.pallas.binned import (GEOM_MID, GEOM_SPARSE,
                                           GEOM_XSPARSE, build_binned_plan,
                                           run_binned)
    rng = np.random.default_rng(7)
    for geom in (GEOM_MID, GEOM_SPARSE, GEOM_XSPARSE):
        for (n, t, e, h) in [(3 * geom.rb, 2 * geom.sb + 1, 60000, 128),
                             (2000, 2000, 40000, 41)]:
            src = rng.integers(0, t, e).astype(np.int64)
            dst = rng.integers(0, n, e).astype(np.int64)
            x = rng.standard_normal((t, h), dtype=np.float32)
            plan = build_binned_plan(src, dst, n, t,
                                     group_row_target=1 << 17, geom=geom)
            msg = f"geom={tuple(geom)} n={n} t={t} h={h}"
            out = np.asarray(run_binned(jnp.asarray(x), plan,
                                        interpret=False))
            np.testing.assert_allclose(out, _oracle_bf16(x, src, dst, n),
                                       rtol=1e-4, atol=5e-2, err_msg=msg)
            out_e = np.asarray(run_binned(jnp.asarray(x), plan,
                                          interpret=False,
                                          precision="exact"))
            ref = np.zeros((n, h), np.float32)
            np.add.at(ref, dst, x[src])
            np.testing.assert_allclose(out_e, ref, rtol=2e-6, atol=1e-4,
                                       err_msg=msg + " exact")


def test_binned_flat_on_hw():
    """Flat compacted schedule + fused pipeline compiled on the chip — the
    8-row staging units, run-list size-classed DMAs, dual-block one-hot
    dots, and the interleaved fused grid are all new Mosaic surface that
    interpret mode cannot vet.  Covers the fused path, the scan fallback
    (ROC_BINNED_NO_FUSE), exact precision, and a lane-unaligned H."""
    import os

    from roc_tpu.ops.pallas.binned import (GEOM_FLAT, Geometry,
                                           build_binned_plan, run_binned)
    # GEOM_FLAT-shaped but small-window so the fused gate opens at test
    # scale; plus the shipped preset itself for the real staging widths.
    small = Geometry(sb=256, ch=512, slot=128, rb=256, ch2=512,
                     grt=1 << 17, flat=1)
    rng = np.random.default_rng(9)
    for geom in (small, GEOM_FLAT):
        for (n, t, e, h) in [(3 * geom.rb, 2 * geom.sb + 1, 60000, 128),
                             (2000, 2000, 40000, 41)]:
            src = rng.integers(0, t, e).astype(np.int64)
            dst = rng.integers(0, n, e).astype(np.int64)
            x = rng.standard_normal((t, h), dtype=np.float32)
            plan = build_binned_plan(src, dst, n, t,
                                     group_row_target=1 << 17, geom=geom)
            msg = f"geom={tuple(geom)} n={n} t={t} h={h}"
            out = np.asarray(run_binned(jnp.asarray(x), plan,
                                        interpret=False))
            np.testing.assert_allclose(out, _oracle_bf16(x, src, dst, n),
                                       rtol=1e-4, atol=5e-2, err_msg=msg)
            if plan.f_meta is not None:     # A/B the scan fallback
                os.environ["ROC_BINNED_NO_FUSE"] = "1"
                try:
                    out2 = np.asarray(run_binned(jnp.asarray(x), plan,
                                                 interpret=False))
                finally:
                    os.environ.pop("ROC_BINNED_NO_FUSE", None)
                np.testing.assert_array_equal(out, out2, err_msg=msg)
            out_e = np.asarray(run_binned(jnp.asarray(x), plan,
                                          interpret=False,
                                          precision="exact"))
            ref = np.zeros((n, h), np.float32)
            np.add.at(ref, dst, x[src])
            np.testing.assert_allclose(out_e, ref, rtol=2e-6, atol=1e-4,
                                       err_msg=msg + " exact")


def test_edge_gat_windowed_plans_on_hw():
    """edge_gat_attend's building blocks on the chip: _plan_max/_plan_sum
    over WINDOWED (base-shifted) plans — the per-block treatment the
    edge-sharded attention runs inside shard_map.  Single-chip here (the
    collectives are CPU-mesh-validated); this pins the compiled one-hot
    window machinery at a nonzero base."""
    from roc_tpu.ops import edge as em
    from roc_tpu.ops.edge import GatPlans, _aligned_position_plan
    rng = np.random.default_rng(11)
    NS, Eb, K = 4096, 30000, 3
    base = 1024                       # window base: rows [1024, 3072)
    span = 2048
    ed = np.sort(rng.integers(base, base + span, Eb).astype(np.int64))
    es = rng.integers(0, NS, Eb).astype(np.int64)
    s = rng.standard_normal((Eb, K), dtype=np.float32)
    d = _aligned_position_plan(ed - base, es, span)
    plans = GatPlans(*(jnp.asarray(a) for a in d + d), num_rows=span,
                     table_rows=span)
    m = np.asarray(em._plan_max(jnp.asarray(s.T), plans.dst_obi,
                                plans.dst_edst, plans.dst_pos, span)).T
    mo = np.full((span, K), -np.inf, np.float32)
    np.maximum.at(mo, ed - base, s)
    np.testing.assert_allclose(m, mo, rtol=1e-5, atol=1e-5)
    z = np.asarray(em._plan_sum(jnp.asarray(s.T), None, plans.dst_obi,
                                plans.dst_edst, plans.dst_pos,
                                plans.dst_nid, span, "highest", True)).T
    zo = np.zeros((span, K), np.float32)
    np.add.at(zo, ed - base, s)
    np.testing.assert_allclose(z, zo, rtol=1e-4, atol=1e-3)


if __name__ == "__main__":   # direct hardware run, no pytest/conftest
    if not tpu:
        raise SystemExit("no TPU backend")
    import traceback
    failed = []
    for name, fn in [(k, v) for k, v in globals().items()
                     if k.startswith("test_") and callable(v)]:
        try:
            fn()
            print(f"{name}: ok", flush=True)
        except Exception as e:  # every verdict is wanted, not the first
            failed.append(name)
            traceback.print_exc()
            print(f"{name}: FAILED {type(e).__name__}: {str(e)[:2000]}",
                  flush=True)
    print(f"tpu hardware tests: {len(failed)} failed {failed}")
    raise SystemExit(1 if failed else 0)
