"""End-to-end bf16 feature-storage pipeline (-bf16-storage) on the
8-virtual-device CPU mesh.

The contract under test: features may be STORED, STAGED, and EXCHANGED as
bf16 while every accumulation stays fp32 — so a bf16-storage run must
track the fp32 run's loss curve (parity gates below), the wire codec must
round each value exactly once (unit tests), and everything keyed on bytes
(step cache, plan cache) must key on the storage dtype (a cached fp32
program served to a bf16 run would silently move twice the bytes or
mis-decode the wire)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.graph import datasets
from roc_tpu.models import build_gat, build_gcn
from roc_tpu.parallel import spmd
from roc_tpu.parallel.mesh import PARTS_AXIS, make_mesh
from roc_tpu.parallel.spmd import SpmdTrainer
from roc_tpu.train.config import Config
from roc_tpu.train.driver import Trainer


def small_ds(seed=31):
    return datasets.synthetic("b16", 200, 3.0, 12, 4, n_train=50, n_val=50,
                              n_test=50, seed=seed)


BASE = dict(num_epochs=3, learning_rate=0.01, weight_decay=5e-4,
            dropout_rate=0.0, eval_every=10 ** 9)


def _loss(ds, cfg, model=None, n=3):
    tr = (Trainer if cfg.num_parts == 1 else SpmdTrainer)(
        cfg, ds, model or build_gcn(cfg.layers, 0.0))
    for _ in range(n):
        loss = float(tr.run_epoch())
    return loss


# -- parity gates ---------------------------------------------------------

@pytest.mark.parametrize("mode", [
    dict(num_parts=4, halo=True),
    dict(num_parts=4, halo=False),                      # allgather
    dict(num_parts=4, exchange="ring"),
    dict(num_parts=4, halo=True, aggregate_backend="matmul"),  # plan path
])
def test_gcn_bf16_matches_fp32(mode):
    """GCN final-loss parity within 1e-2 of the fp32 run on every exchange
    mode, plain nearest-rounded bf16 wire."""
    ds = small_ds()
    layers = [ds.in_dim, 8, ds.num_classes]
    l32 = _loss(ds, Config(layers=layers, **BASE, **mode))
    l16 = _loss(ds, Config(layers=layers, **BASE, **mode, bf16_storage=True))
    assert abs(l16 - l32) < 1e-2, (l16, l32)


def test_gcn_bf16_stochastic_and_single_device():
    """Stochastic rounding holds the same parity gate (unbiasedness makes
    it noisier per value, not worse on the loss), and a single-device
    bf16-storage run trains (the dtype threads through geometry choice,
    not the wire, there)."""
    ds = small_ds()
    layers = [ds.in_dim, 8, ds.num_classes]
    l32 = _loss(ds, Config(layers=layers, **BASE, num_parts=4, halo=True))
    lsr = _loss(ds, Config(layers=layers, **BASE, num_parts=4, halo=True,
                           bf16_storage=True, bf16_rounding="stochastic"))
    assert abs(lsr - l32) < 1e-2, (lsr, l32)
    l1 = _loss(ds, Config(layers=layers, **BASE, num_parts=1,
                          bf16_storage=True))
    assert np.isfinite(l1)


def test_gat_bf16_compensated_matches_fp32():
    """Attention is the bf16-sensitive consumer (softmax of feature dots):
    the compensated two-term wire must recover fp32 parity within 1e-2 —
    this is the option's reason to exist.  Plain bf16 gets a looser gate
    (it drifts ~2e-2 at this shape; still trains)."""
    ds = small_ds()
    layers = [ds.in_dim, 8, ds.num_classes]
    gat = lambda: build_gat(layers, 0.0, heads=2)  # noqa: E731
    kw = dict(layers=layers, **BASE, model="gat", heads=2, num_parts=4,
              halo=True)
    l32 = _loss(ds, Config(**kw), model=gat())
    lcp = _loss(ds, Config(**kw, bf16_storage=True,
                           bf16_exchange="compensated"), model=gat())
    lpl = _loss(ds, Config(**kw, bf16_storage=True), model=gat())
    assert abs(lcp - l32) < 1e-2, (lcp, l32)
    assert abs(lpl - l32) < 1e-1, (lpl, l32)


# -- wire codec unit tests ------------------------------------------------

class _GD:
    """Stub carrying just the static wire metadata the codec reads."""

    def __init__(self, dtype="bf16", rnd="nearest", comp="plain"):
        self.xch_dtype, self.xch_round, self.xch_comp = dtype, rnd, comp


def test_wire_codec_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    # fp32 wire: both directions are the identity
    gd = _GD(dtype="fp32")
    assert spmd._wire_down(x, gd) is x
    np.testing.assert_array_equal(
        np.asarray(spmd._wire_up(x, gd, jnp.float32, 32)), np.asarray(x))
    # plain bf16: error bounded by half a bf16 ulp of the magnitude
    gd = _GD()
    y = spmd._wire_up(spmd._wire_down(x, gd), gd, jnp.float32, 32)
    plain_err = float(jnp.max(jnp.abs(y - x)))
    assert 0 < plain_err < 2.0 ** -7
    # compensated: widens the last axis to 2H, decodes to ~fp32 accuracy
    gd = _GD(comp="compensated")
    down = spmd._wire_down(x, gd)
    assert down.shape == (64, 64) and down.dtype == jnp.bfloat16
    y2 = spmd._wire_up(down, gd, jnp.float32, 32)
    assert y2.shape == x.shape
    comp_err = float(jnp.max(jnp.abs(y2 - x)))
    assert comp_err < plain_err / 16, (comp_err, plain_err)
    # a bf16 input is already wire-format: encode is the identity, and
    # decode must NOT pair-split it (width H, not 2H)
    h = x.astype(jnp.bfloat16)
    assert spmd._wire_down(h, gd) is h
    assert spmd._wire_up(h, gd, jnp.bfloat16, 32).shape == h.shape


def test_stochastic_rounding_unbiased_and_straight_through():
    """_sr_bf16 inside a shard_map: every output is a bf16 neighbor of its
    input, the mean rounding error is ~0 (unbiased, unlike nearest on a
    skewed distribution), and the VJP is the straight-through identity."""
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh(4)
    f = jax.jit(jax.shard_map(spmd._sr_bf16, mesh=mesh,
                              in_specs=P(PARTS_AXIS),
                              out_specs=P(PARTS_AXIS)))
    x = jax.random.uniform(jax.random.PRNGKey(1), (4, 8192), jnp.float32,
                           1.0, 2.0)
    y = np.asarray(f(x), np.float32)
    xn = np.asarray(x)
    # in [1, 2) a bf16 ulp is 2^-7: SR must land on one of the two
    # neighbors, never further
    assert np.max(np.abs(y - xn)) < 2.0 ** -7
    # unbiased: |mean error| well under the ulp/sqrt(N) noise ceiling
    assert abs(float(np.mean(y - xn))) < 3 * (2.0 ** -7) / np.sqrt(y.size)
    # distinct per-shard fold_in keys: shards with identical inputs must
    # not round identically (decorrelated, or SR bias returns in aggregate)
    same = jnp.tile(x[:1], (4, 1))
    ys = np.asarray(f(same), np.float32)
    assert not np.array_equal(ys[0], ys[1])
    g = jax.grad(lambda v: jnp.sum(f(v).astype(jnp.float32)))(x)
    np.testing.assert_array_equal(np.asarray(g), np.ones_like(xn))


# -- dtype-keyed caching (the retrace-safety half of the feature) ---------

def test_step_cache_keys_on_storage_dtype():
    """xch_* ride ShardedGraphData as STATIC metadata: the pytree
    structures of an fp32 and a bf16 trainer's graph data must differ, so
    the step cache (keyed on tree_structure) can never serve a program
    traced for the other dtype."""
    ds = small_ds()
    layers = [ds.in_dim, 8, ds.num_classes]
    t32 = SpmdTrainer(Config(layers=layers, **BASE, num_parts=4, halo=True),
                      ds, build_gcn(layers, 0.0))
    t16 = SpmdTrainer(Config(layers=layers, **BASE, num_parts=4, halo=True,
                             bf16_storage=True), ds, build_gcn(layers, 0.0))
    s32 = jax.tree_util.tree_structure(t32.gdata)
    s16 = jax.tree_util.tree_structure(t16.gdata)
    assert s32 != s16
    assert t16.gdata.xch_dtype == "bf16" and t32.gdata.xch_dtype == "fp32"


def test_zero_retraces_with_bf16_storage():
    """Steady-state retrace proof with the bf16 wire active: epochs 2..N
    re-enter the SAME jitted step (the codec is trace-time static — no
    shape or dtype leaks into the carry that would force a re-trace)."""
    from roc_tpu.analysis import retrace
    from roc_tpu.analysis.retrace import RetraceGuard
    ds = small_ds()
    layers = [ds.in_dim, 8, ds.num_classes]
    tr = SpmdTrainer(Config(layers=layers, **BASE, num_parts=4, halo=True,
                            bf16_storage=True), ds, build_gcn(layers, 0.0))
    with RetraceGuard(warmup=1) as g:       # raises on any 2..N retrace
        tr.run_epoch()
        retrace.epoch_boundary(1)
        for _ in range(3):
            tr.run_epoch()
        assert g.counts.get("train_step", 0) >= 1


def test_edge_shard_keeps_fp32_wire():
    """Edge-sharded mode reduces with psum_scatter — the collective
    accumulates in-network, so a bf16 wire would round PARTIAL SUMS, not
    inputs.  _xch_meta must refuse the knob there."""
    ds = small_ds()
    layers = [ds.in_dim, 8, ds.num_classes]
    tr = SpmdTrainer(Config(layers=layers, **BASE, num_parts=4,
                            edge_shard="on", bf16_storage=True),
                     ds, build_gcn(layers, 0.0))
    assert tr._use_edge_shard
    assert tr._xch_meta() == ("fp32", "nearest", "plain")


# -- config knobs ---------------------------------------------------------

def test_config_bf16_knobs(monkeypatch):
    from roc_tpu.train.config import parse_args
    assert Config().bf16_storage is False
    cfg = parse_args(["-bf16-storage", "-bf16-rounding", "stochastic",
                      "-bf16-exchange", "compensated"])
    assert (cfg.bf16_storage, cfg.bf16_rounding, cfg.bf16_exchange) == \
        (True, "stochastic", "compensated")
    monkeypatch.setenv("ROC_BF16_STORAGE", "1")
    assert Config().bf16_storage is True
    monkeypatch.delenv("ROC_BF16_STORAGE")
    with pytest.raises(SystemExit):
        Config(bf16_storage=True, aggregate_precision="exact")
    with pytest.raises(SystemExit):
        Config(bf16_rounding="up")
    with pytest.raises(SystemExit):
        Config(bf16_exchange="kahan")


def test_choose_geometry_storage_dtype_validated():
    import roc_tpu.ops.pallas.binned as B
    rng = np.random.default_rng(0)
    src = rng.integers(0, 512, 4096).astype(np.int64)
    dst = rng.integers(0, 512, 4096).astype(np.int64)
    with pytest.raises(ValueError, match="storage_dtype"):
        B.choose_geometry(src, dst, 512, 512, storage_dtype="fp64")
    g, _ = B.choose_geometry(src, dst, 512, 512, force=True,
                             storage_dtype="bf16")
    assert g is not None
