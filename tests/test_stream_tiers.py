"""Giant-graph storage tiers (round 20, roc_tpu/stream/).

The contract under test mirrors ISSUE 20's acceptance gates:

- the bf16 slot tier is a STORAGE cut, not a different algorithm: on an
  integer fixture whose activations are all bf16-exact (power-of-2
  in-degrees so the GCN norm divides exactly, {0,1} features, sparse
  {0,1} params whose products never leave bf16's integer-exact range)
  the epoch-1 loss is BITWISE identical across every tier combination
  and equal to the in-core trainer's; on real-valued features the
  streamed-bf16 loss stays within 1e-3 (relative) of in-core;
- the NVMe spill tier is byte-lossless: spill combos match their
  RAM-tier twins bitwise, a CRC'd header survives a roundtrip, and a
  corrupt or torn store raises a TYPED error instead of feeding garbage
  activations into the backward;
- the pinned-host allocator degrades to plain numpy on backends without
  a pinned_host memory space (CPU CI) — writable buffers, counted
  fallback bytes, no crash;
- no tier combination retraces across rotations (the frozen padded
  shapes are the same contract test_stream.py pins for the fp32 tier);
- the in-core budget gate's refusal message teaches the spill flag, and
  the bf16 tier refuses the rounding/exchange modes whose extra wire
  terms would break the one-rounding-per-row contract.
"""

import os
import struct
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from roc_tpu.analysis import retrace as retrace_mod
from roc_tpu.analysis.retrace import RetraceGuard
from roc_tpu.graph import datasets, lux
from roc_tpu.graph.csr import add_self_edges, from_edges
from roc_tpu.graph.datasets import Dataset
from roc_tpu.models import build_model
from roc_tpu.stream import host as stream_host
from roc_tpu.stream import incore_resident_bytes, spill
from roc_tpu.train.config import Config
from roc_tpu.train.driver import make_trainer

pytestmark = pytest.mark.filterwarnings("ignore")


@pytest.fixture(autouse=True)
def _lock_order_witness(lock_witness):
    yield


# -- the integer fixture ---------------------------------------------------

def _int_dataset():
    """64 nodes, every in-degree exactly 4 (3 ring neighbors + the self
    edge), so the GCN norm divides by powers of two; {0,1} features."""
    n, F, C = 64, 8, 4
    src = np.concatenate([(np.arange(n) + k) % n for k in (1, 17, 33)])
    dst = np.tile(np.arange(n), 3)
    g = add_self_edges(from_edges(n, src, dst))
    assert np.unique(np.diff(g.row_ptr)).tolist() == [4]
    rng = np.random.default_rng(0)
    feats = rng.integers(0, 2, size=(n, F)).astype(np.float32)
    ids = rng.integers(0, C, size=n).astype(np.int64)
    mask = np.zeros(n, np.int32)          # every row MASK_TRAIN
    return Dataset("int-tiers", g, feats, lux.one_hot(ids, C), ids, mask,
                   F, C)


def _int_params(params):
    """Sparse {0,1} weights (one 1 per column), zero biases: every
    activation stays an exact small dyadic rational, so the bf16 slot
    downcast is lossless and bitwise claims are meaningful."""
    def f(x):
        x = np.asarray(x)
        if x.ndim == 2:
            w = np.zeros(x.shape, np.float32)
            w[np.arange(x.shape[1]) % x.shape[0],
              np.arange(x.shape[1])] = 1.0
            return jnp.asarray(w)
        return jnp.zeros_like(x)
    return jax.tree_util.tree_map(f, params)


def _stream_trainer(ds, tmp, *, bf16=False, spill_tier=False, **kw):
    cfg = Config(layers=[ds.in_dim, 8, ds.num_classes], num_epochs=1,
                 dropout_rate=0.0, eval_every=10**9, num_parts=4,
                 stream=True, stream_slots=2, bf16_storage=bf16,
                 stream_spill=str(tmp / f"spill_{bf16}") if spill_tier
                 else "", **kw)
    tr = make_trainer(cfg, ds, build_model("gcn", cfg.layers, 0.0, ""))
    tr.params = _int_params(tr.params)
    return tr


COMBOS = [("fp32", False, False), ("bf16", True, False),
          ("fp32+spill", False, True), ("bf16+spill", True, True)]


def test_tier_combos_bitwise_on_integer_fixture(tmp_path):
    """Epoch-1 loss bitwise across all four tier combos AND vs in-core;
    pre-training logits bitwise between the bf16 and fp32 wires (one
    rounding per row is a no-op on bf16-exact data)."""
    ds = _int_dataset()
    losses, logits = {}, {}
    for name, bf16, sp in COMBOS:
        tr = _stream_trainer(ds, tmp_path, bf16=bf16, spill_tier=sp)
        logits[name] = np.asarray(tr.predict_logits(), np.float32)
        losses[name] = float(tr.run_epoch())
    assert len(set(losses.values())) == 1, losses
    for name in ("bf16", "fp32+spill", "bf16+spill"):
        np.testing.assert_array_equal(logits["fp32"], logits[name],
                                      err_msg=name)
    # the ISSUE gate is <= 1e-3 vs in-core; on this fixture the measured
    # gap is exactly 0 (the sum of shard-wise CE partials reassociates
    # to the same fp32 value at this size)
    cfg = Config(layers=[ds.in_dim, 8, ds.num_classes], num_epochs=1,
                 dropout_rate=0.0, eval_every=10**9, num_parts=1)
    ref = make_trainer(cfg, ds, build_model("gcn", cfg.layers, 0.0, ""))
    ref.params = _int_params(ref.params)
    assert abs(float(ref.run_epoch()) - losses["fp32"]) <= 1e-3


def test_streamed_bf16_tracks_incore_on_real_features():
    """Real-valued features: the bf16 wire's rounding must keep every
    epoch's loss within 1e-3 (relative) of the in-core fp32 trainer
    (measured ~9e-5 on this fixture)."""
    ds = datasets.get("roc-audit", seed=1)

    def build(**kw):
        cfg = Config(layers=[ds.in_dim, 16, ds.num_classes], num_epochs=3,
                     dropout_rate=0.0, eval_every=10**9, **kw)
        return make_trainer(cfg, ds, build_model("gcn", cfg.layers, 0.0,
                                                 ""))
    ref = build(num_parts=1)
    tr = build(num_parts=4, stream=True, stream_slots=2, bf16_storage=True)
    for _ in range(3):
        want, got = float(ref.run_epoch()), float(tr.run_epoch())
        assert abs(want - got) <= 1e-3 * max(abs(want), 1.0)


def test_zero_retrace_every_tier_combo(tmp_path):
    """Rotations through every tier must reuse the warm programs — a
    spill read or a bf16 upcast is data movement, never a new trace."""
    ds = _int_dataset()
    for name, bf16, sp in COMBOS:
        tr = _stream_trainer(ds, tmp_path / name.replace("+", "_"),
                             bf16=bf16, spill_tier=sp)
        tr.run_epoch()                  # compile everything once
        tr.evaluate()
        with RetraceGuard(warmup=1, on_violation="raise"):
            retrace_mod.epoch_boundary(1)
            tr.run_epoch()
            tr.evaluate()


# -- the pinned-host allocator ---------------------------------------------

def test_pinned_allocator_counts_every_byte(monkeypatch):
    """alloc hands back a writable buffer and counts its bytes on exactly
    one side, whichever way the backend answers (jax 0.9's CPU client
    exposes a pinned_host space; older ones did not).  With no pinned
    space the plain-numpy fallback takes every byte."""
    src = np.arange(12, dtype=np.float32).reshape(4, 3)
    for pinned in (stream_host.pinned_supported(), False):
        monkeypatch.setattr(stream_host, "pinned_supported",
                            lambda p=pinned: p)
        stream_host.reset_stats()
        a = stream_host.alloc((4, 3), np.float32)
        a[:] = 7.0                                  # writable
        assert a.dtype == np.float32 and a.shape == (4, 3)
        np.testing.assert_array_equal(stream_host.to_store(src), src)
        st = stream_host.stats()
        assert st["pinned_bytes"] + st["fallback_bytes"] == 2 * 48
        if not pinned:
            assert st["pinned_bytes"] == 0
    stream_host.reset_stats()


# -- the spill store format ------------------------------------------------

def test_spill_roundtrip_both_dtypes(tmp_path):
    import ml_dtypes
    for dt in (np.dtype(np.float32), np.dtype(ml_dtypes.bfloat16)):
        p = str(tmp_path / f"s_{dt.name}.spill")
        m = spill.create_store(p, (6, 5), dt)
        vals = np.arange(30, dtype=np.float32).reshape(6, 5).astype(dt)
        m[:] = vals
        m.flush()
        del m
        back = spill.open_store(p)
        assert back.dtype == dt and back.shape == (6, 5)
        np.testing.assert_array_equal(np.asarray(back), vals)


def test_spill_corrupt_header_raises_typed(tmp_path):
    p = str(tmp_path / "c.spill")
    m = spill.create_store(p, (4, 4), np.dtype(np.float32))
    m[:] = 1.0
    m.flush()
    del m
    raw = bytearray(open(p, "rb").read())
    raw[9] ^= 0xFF                       # flip a byte inside the header
    with open(p, "wb") as f:
        f.write(raw)
    with pytest.raises(spill.SpillHeaderError):
        spill.open_store(p)


def test_spill_torn_store_raises_typed(tmp_path):
    # torn header: fewer bytes than the fixed header region
    p = str(tmp_path / "torn.spill")
    with open(p, "wb") as f:
        f.write(b"RSPL" + b"\0" * 10)
    with pytest.raises(spill.SpillError):
        spill.open_store(p)
    # torn data region: valid header, truncated payload
    p2 = str(tmp_path / "short.spill")
    m = spill.create_store(p2, (8, 8), np.dtype(np.float32))
    m.flush()
    del m
    with open(p2, "r+b") as f:
        f.truncate(spill.HEADER_BYTES + 16)
    with pytest.raises(spill.SpillError):
        spill.open_store(p2)


# -- gates -----------------------------------------------------------------

def test_budget_gate_teaches_spill_flag():
    """The in-core refusal must name the escape hatches, -stream-spill
    included."""
    ds = _int_dataset()
    need = incore_resident_bytes(ds)
    cfg = Config(layers=[ds.in_dim, 8, ds.num_classes], num_epochs=1,
                 dropout_rate=0.0, eval_every=10**9, num_parts=2,
                 stream_budget=str(max(need // 3, 1)))
    with pytest.raises(SystemExit, match="-stream-spill"):
        make_trainer(cfg, ds, build_model("gcn", cfg.layers, 0.0, ""))


def test_spill_flag_requires_stream():
    with pytest.raises(SystemExit, match="requires -stream"):
        Config(layers=[8, 8, 4], stream_spill="/tmp/nope")


@pytest.mark.parametrize("kw", [dict(bf16_rounding="stochastic"),
                                dict(bf16_exchange="compensated")])
def test_bf16_stream_requires_plain_nearest(kw, tmp_path):
    """The streamed bf16 wire implements exactly one rounding per row;
    stochastic rounding and the compensated two-term exchange would both
    break that contract silently, so the executor refuses them."""
    ds = _int_dataset()
    cfg = Config(layers=[ds.in_dim, 8, ds.num_classes], num_epochs=1,
                 dropout_rate=0.0, eval_every=10**9, num_parts=4,
                 stream=True, stream_slots=2, bf16_storage=True, **kw)
    with pytest.raises(SystemExit, match="bf16"):
        make_trainer(cfg, ds, build_model("gcn", cfg.layers, 0.0, ""))
