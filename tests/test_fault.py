"""Fault-tolerant runtime (roc_tpu/fault): chaos harness, retries, the
non-finite step guard, crash-consistent resume, serve overload policy.

The pins mirror ISSUE 14's acceptance gates:

- seeded chaos on the streamed path: the run completes, final loss
  within 1e-3 of its fault-free twin, zero retraces — and the SAME
  faults with ``retries=0`` fail loudly (the retries are load-bearing);
- a NaN-injected step is a true no-op: an (N+1)-epoch run whose first
  step was skipped equals an N-epoch clean run bitwise (dropout 0);
- kill -9 on either side of the checkpoint rename leaves a loadable
  checkpoint; corrupt/truncated files raise CheckpointError, never an
  opaque zipfile traceback;
- kill-and-resume reproduces the uninterrupted run's params to within
  32 ULPs (dropout ON, so the resumed RNG stream is exercised);
- the serve queue sheds with Overloaded at its depth cap, expires
  deadlined requests at drain, and close() strands no caller.
"""

import json
import signal
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.analysis import retrace as retrace_mod
from roc_tpu.analysis.retrace import RetraceGuard
from roc_tpu.fault import inject, retry
from roc_tpu.graph import datasets, lux
from roc_tpu.models import build_gcn, build_model
from roc_tpu.train import checkpoint
from roc_tpu.train.config import Config
from roc_tpu.train.driver import Trainer, make_trainer


@pytest.fixture(autouse=True)
def _disarm():
    """Every test leaves the process-global harness disarmed."""
    yield
    inject.configure("")
    inject.detach()
    retry.reset_retry_counts()


def _noop(*a, **k):
    pass


def _small_trainer(num_epochs, fault_spec="", dropout=0.0, **cfg_kw):
    ds = datasets.synthetic("t", 80, 3.0, 8, 3, n_train=20, n_val=20,
                            n_test=20, seed=13)
    cfg_kw.setdefault("eval_every", 10 ** 9)
    cfg = Config(layers=[8, 4, 3], num_epochs=num_epochs,
                 dropout_rate=dropout, fault=fault_spec, **cfg_kw)
    return Trainer(cfg, ds, build_gcn(cfg.layers, dropout)), cfg


# -- injection harness ----------------------------------------------------

def test_point_disarmed_is_noop():
    inject.configure("")
    assert not inject.armed()
    assert inject.point("never.registered") is False


def test_config_rejects_malformed_fault_spec():
    with pytest.raises(SystemExit):
        Config(layers=[4, 4, 2], fault="nonsense")


def test_seeded_probability_is_deterministic():
    def pattern():
        inject.configure("seed=11,p.nan@0.5")
        return [inject.point("p.nan") for _ in range(64)]
    a, b = pattern(), pattern()
    assert a == b and any(a) and not all(a)


def test_retry_recovery_emits_jsonl_counted_events():
    """Transient fault at a retried site: the caller sees success, and
    the obs sink sees one ``fault`` + one ``retry`` record per failed
    attempt with site/attempt/limit/error fields."""
    records = []
    inject.attach(lambda kind, **kw: records.append((kind, kw)))
    inject.configure("seed=2,io.flaky=2")

    def flaky():
        inject.point("io.flaky")
        return "ok"
    assert retry.retrying("io.flaky", flaky, base_s=0.001) == "ok"
    retries = [kw for kind, kw in records if kind == "retry"]
    assert [r["attempt"] for r in retries] == [1, 2]
    assert all(r["site"] == "io.flaky" and r["limit"] == 3
               and r["error"] == "InjectedFault" for r in retries)
    assert sum(1 for kind, _ in records if kind == "fault") == 2
    assert retry.retry_counts()["io.flaky"] == 2
    assert inject.counters()["io.flaky"] == {"calls": 3, "fired": 2}


def test_retry_exhaustion_and_kill_switch():
    inject.configure("seed=1,io.perm=perm")
    with pytest.raises(inject.InjectedFault):
        retry.retrying("io.perm", lambda: inject.point("io.perm"),
                       base_s=0.001)
    # retries=0 overrides every budget: first failure propagates
    inject.configure("seed=1,retries=0,io.once=1")
    tries = []

    def once():
        tries.append(1)
        inject.point("io.once")
    with pytest.raises(inject.InjectedFault):
        retry.retrying("io.once", once, base_s=0.001)
    assert len(tries) == 1


def test_lux_read_retried(tmp_path):
    ds = datasets.synthetic("luxf", 60, 3.0, 4, 3, n_train=10, n_val=10,
                            n_test=10, seed=7)
    path = str(tmp_path / ("g" + lux.LUX_SUFFIX))
    lux.write_lux(path, ds.graph)
    want = lux.read_rows_slice(path, 0, 10)
    inject.configure("seed=2,lux.read=2")
    got = lux.read_rows_slice(path, 0, 10)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert inject.counters()["lux.read"]["fired"] == 2
    inject.configure("seed=2,retries=0,lux.read=1")
    with pytest.raises(OSError):
        lux.read_rows_slice(path, 0, 10)


# -- streamed chaos parity (the ISSUE's headline pin) ---------------------

def _stream_trainer(ds):
    cfg = Config(layers=[ds.in_dim, 16, ds.num_classes], num_epochs=4,
                 dropout_rate=0.0, eval_every=10 ** 9, num_parts=4,
                 stream=True)
    m = build_model("gcn", cfg.layers, cfg.dropout_rate, "")
    return make_trainer(cfg, ds, m)


def test_streamed_chaos_parity_and_zero_retraces():
    """Seeded transient faults on every retried streaming boundary
    (prefetch, h2d staging, cotangent scatter pulls, plus injected
    slowness): the run completes with the fault-free twin's loss (the
    retries are semantically invisible) and never retraces."""
    ds = datasets.get("roc-audit", seed=1)
    free = _stream_trainer(ds)
    for _ in range(4):
        loss_free = free.run_epoch()
    tr = _stream_trainer(ds)
    # one ring.fetch + one device_put fault land on the same first fetch
    # (the staging point sits inside the fetch closure) — two of the
    # three attempts burned, the third lands; scatter faults burn their
    # own budget on the scatter worker
    inject.configure("seed=5,ring.fetch=1,stream.scatter=2,"
                     "stream.device_put=1,ring.fetch.slow@0.25,slow_ms=1")
    loss = tr.run_epoch()
    with RetraceGuard(warmup=1, on_violation="raise"):
        retrace_mod.epoch_boundary(1)
        for _ in range(3):
            loss = tr.run_epoch()
    c = inject.counters()
    assert c["ring.fetch"]["fired"] >= 1, "chaos leg never fired"
    assert c["stream.scatter"]["fired"] >= 1
    assert retry.retry_counts().get("ring.fetch", 0) >= 1
    assert abs(float(loss) - float(loss_free)) <= 1e-3


def test_streamed_chaos_fails_without_retries():
    """The same fault with the retry budget zeroed must kill the run —
    proof the survival above came from the retries, not from the faults
    never firing."""
    ds = datasets.get("roc-audit", seed=1)
    tr = _stream_trainer(ds)
    inject.configure("seed=5,retries=0,ring.fetch=1")
    with pytest.raises(OSError):
        jax.block_until_ready(tr.run_epoch())


# -- non-finite step guard ------------------------------------------------

def test_nan_step_skip_is_bitwise_noop():
    """dropout 0, no decay: a 4-epoch run whose first step was NaN-
    skipped must equal a 3-epoch clean run bitwise — params AND Adam
    moments, so the skipped step left no trace anywhere."""
    tr_a, _ = _small_trainer(4, fault_spec="seed=3,step.nan=1")
    tr_a.train(print_fn=_noop)
    assert tr_a._nf_skips == 1, "injected NaN step was not skipped"
    inject.configure("")
    tr_b, _ = _small_trainer(3)
    tr_b.train(print_fn=_noop)
    for a, b in zip(jax.tree.leaves(tr_a.params),
                    jax.tree.leaves(tr_b.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(tr_a.opt_state.m),
                    jax.tree.leaves(tr_b.opt_state.m)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_nonfinite_escalation_ladder(tmp_path):
    """The guard's host side, whole: one non-finite step counts a skip
    and a streak and changes nothing; a finite step ends the streak;
    NONFINITE_ESCALATE_AFTER skips in a row restore the last durable
    checkpoint (parameters and epoch), say so in one event, and start
    the count again."""
    from roc_tpu.train.driver import NONFINITE_ESCALATE_AFTER as K
    tr, cfg = _small_trainer(4, checkpoint_path=str(tmp_path / "ck.npz"))
    tr.train(print_fn=_noop)
    tr.save_checkpoint(cfg.checkpoint_path)
    saved_epoch, saved = tr.epoch, jax.device_get(tr.params)
    events = []
    inject.attach(lambda kind, **kw: events.append((kind, kw)))
    tr.run_epoch()                      # moves on from the checkpoint
    moved = jax.device_get(tr.params)
    assert any((np.asarray(a) != np.asarray(b)).any() for a, b in zip(
        jax.tree.leaves(saved), jax.tree.leaves(moved)))
    tr._last_nonfinite = jnp.asarray(True)
    for i in range(K - 1):
        tr._check_nonfinite(1, _noop)
        assert (tr._nf_streak, tr._nf_skips) == (i + 1, i + 1)
    tr._last_nonfinite = jnp.asarray(False)
    tr._check_nonfinite(1, _noop)       # recovered on its own
    assert tr._nf_streak == 0 and tr._nf_skips == K - 1 and not events
    for a, b in zip(jax.tree.leaves(moved), jax.tree.leaves(tr.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tr._last_nonfinite = jnp.asarray(True)
    tr.epoch = 99
    for _ in range(K):
        tr._check_nonfinite(2, _noop)
    assert [(k, e["stage"], e["streak"]) for k, e in events] == [
        ("nonfinite_escalation", "restore", K)]
    assert tr._nf_streak == 0
    assert tr.epoch == saved_epoch, "the checkpoint was not restored"
    for a, b in zip(jax.tree.leaves(saved), jax.tree.leaves(tr.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_nonfinite_escalation_without_checkpoint():
    from roc_tpu.train.driver import NONFINITE_ESCALATE_AFTER as K
    tr, _ = _small_trainer(4)
    events = []
    inject.attach(lambda kind, **kw: events.append(kw["stage"]))
    tr._last_nonfinite = jnp.asarray(True)
    for _ in range(2 * K):
        tr._check_nonfinite(0, _noop)
    # degraded, still alive, and it said so at every escalation
    assert tr._nf_skips == 2 * K and events == ["no_checkpoint"] * 2


def test_watchdog_nonfinite_and_state_roundtrip():
    from roc_tpu import obs
    wd = obs.PerfWatchdog()
    wd.observe_nonfinite(3, 1)
    alert = wd.observe_nonfinite(4, 2)
    assert wd.nonfinite_steps == 2
    assert alert["total"] == 2 and alert["consecutive"] == 2
    state = wd.state_dict()
    json.dumps(state)  # must fit the checkpoint's JSON extra record
    wd2 = obs.PerfWatchdog()
    wd2.load_state(state)
    assert wd2.nonfinite_steps == 2


# -- crash-consistent checkpointing ---------------------------------------

_P = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
_O = {"m": np.zeros(3, np.float32)}


def test_checkpoint_corrupt_and_truncated_raise_checkpoint_error(tmp_path):
    path = str(tmp_path / "ck.npz")
    checkpoint.save(path, _P, _O, 3, 0.05)
    blob = open(path, "rb").read()
    with open(path, "wb") as f:        # torn write: half the bytes
        f.write(blob[:len(blob) // 2])
    with pytest.raises(checkpoint.CheckpointError,
                       match="corrupt or truncated"):
        checkpoint.load(path, _P, _O)
    with open(path, "wb") as f:        # not even a zip
        f.write(b"definitely not an npz")
    with pytest.raises(checkpoint.CheckpointError,
                       match="corrupt or truncated"):
        checkpoint.load(path, _P, _O)


def test_checkpoint_crc_catches_bit_rot(tmp_path):
    path = str(tmp_path / "ck.npz")
    checkpoint.save(path, _P, _O, 3, 0.05)
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    arrays["p_leaf_0"] = arrays["p_leaf_0"] + 1.0  # payload drifts, stamp doesn't
    np.savez(path, **arrays)
    with pytest.raises(checkpoint.CheckpointError, match="CRC32"):
        checkpoint.load(path, _P, _O)


def test_checkpoint_kill_windows_leave_loadable_file(tmp_path):
    """SimulatedCrash on either side of the rename: before it, the old
    checkpoint survives untouched; after it, the new one is complete.
    Never garbage."""
    path = str(tmp_path / "ck.npz")
    checkpoint.save(path, _P, _O, 1, 0.1)
    p2 = {"w": _P["w"] * 2.0}
    inject.configure("ckpt.kill_tmp=1")
    with pytest.raises(inject.SimulatedCrash):
        checkpoint.save(path, p2, _O, 2, 0.1)
    inject.configure("")
    params, _, epoch, _, _ = checkpoint.load(path, _P, _O)
    assert epoch == 1
    np.testing.assert_array_equal(params["w"], _P["w"])
    inject.configure("ckpt.kill_rename=1")
    with pytest.raises(inject.SimulatedCrash):
        checkpoint.save(path, p2, _O, 2, 0.1)
    inject.configure("")
    params, _, epoch, _, _ = checkpoint.load(path, _P, _O)
    assert epoch == 2
    np.testing.assert_array_equal(params["w"], p2["w"])


def test_checkpoint_write_retried(tmp_path):
    path = str(tmp_path / "ck.npz")
    inject.configure("seed=4,ckpt.write=2")
    checkpoint.save(path, _P, _O, 5, 0.1)
    assert retry.retry_counts()["ckpt.write"] == 2
    _, _, epoch, _, _ = checkpoint.load(path, _P, _O)
    assert epoch == 5


def _max_ulp(a, b):
    a = np.asarray(a, np.float32).ravel()
    b = np.asarray(b, np.float32).ravel()
    ai = a.view(np.int32).astype(np.int64)
    bi = b.view(np.int32).astype(np.int64)
    ai = np.where(ai < 0, np.int64(-(2 ** 31)) - ai, ai)
    bi = np.where(bi < 0, np.int64(-(2 ** 31)) - bi, bi)
    return int(np.max(np.abs(ai - bi), initial=0))


def test_resume_exact_within_32_ulps(tmp_path):
    """Kill-at-epoch-5 + resume vs a straight 10-epoch run, dropout ON:
    the restored RNG key + epoch counter must reproduce the dropout
    stream, so the two parameter sets agree to <= 32 ULPs."""
    def mk(num_epochs, resume=False, ckpt=None):
        ds = datasets.synthetic("t", 80, 3.0, 8, 3, n_train=20, n_val=20,
                                n_test=20, seed=13)
        cfg = Config(layers=[8, 4, 3], num_epochs=num_epochs,
                     eval_every=10 ** 9, dropout_rate=0.3,
                     checkpoint_path=ckpt, resume=resume)
        return Trainer(cfg, ds, build_gcn(cfg.layers, 0.3))

    straight = mk(10)
    straight.train(print_fn=_noop)
    ckpt = str(tmp_path / "ck.npz")
    first = mk(5, ckpt=ckpt)
    first.train(print_fn=_noop)       # end-of-train save = the "kill" point
    resumed = mk(5, resume=True, ckpt=ckpt)
    assert resumed.epoch == 5
    resumed.train(print_fn=_noop)
    assert resumed.epoch == 10
    for a, b in zip(jax.tree.leaves(straight.params),
                    jax.tree.leaves(resumed.params)):
        assert _max_ulp(a, b) <= 32


# -- graceful shutdown ----------------------------------------------------

def test_sigterm_finishes_epoch_then_checkpoints(tmp_path):
    """SIGTERM mid-train: the in-flight epoch completes, the loop exits
    cleanly, the end-of-train checkpoint lands, and the previous signal
    disposition is restored."""
    ckpt = str(tmp_path / "ck.npz")
    tr, cfg = _small_trainer(8, checkpoint_path=ckpt, eval_every=1)
    lines = []

    def print_hook(msg):
        lines.append(str(msg))
        if len(lines) == 1:           # first eval print -> "operator" kill
            signal.raise_signal(signal.SIGTERM)

    orig = signal.getsignal(signal.SIGTERM)
    tr.train(print_fn=print_hook)
    assert signal.getsignal(signal.SIGTERM) is orig
    assert tr.epoch < 8, "SIGTERM did not stop the run early"
    assert any("SIGTERM" in ln and "exiting cleanly" in ln for ln in lines)
    _, _, epoch, _, extra = checkpoint.load(ckpt, tr.params, tr.opt_state)
    assert epoch == tr.epoch
    assert "rng_key" in extra


# -- serve overload policy ------------------------------------------------

def test_serve_queue_shed_deadline_and_drain():
    from roc_tpu.serve.queue import MicrobatchQueue, Overloaded
    release, started = threading.Event(), threading.Event()

    def serve_fn(ids):
        started.set()
        release.wait(5.0)
        return np.zeros((len(ids), 2), np.float32)

    q = MicrobatchQueue(serve_fn, batch=8, wait_ms=1.0, queue_max=2)
    f1 = q.submit([1])
    assert started.wait(5.0), "worker never picked up the first window"
    f2 = q.submit([2])
    f3 = q.submit([3, 4], deadline_s=0.0)   # dead on arrival
    with pytest.raises(Overloaded):
        q.submit([5])                       # depth cap: shed, not queue
    assert q.shed == 1
    release.set()
    q.close()                               # graceful drain serves f2
    assert f1.result(5.0).shape == (1, 2)
    assert f2.result(5.0).shape == (1, 2)
    with pytest.raises(Overloaded):
        f3.result(5.0)                      # expired at drain, not served
    assert q.expired == 1
    with pytest.raises(RuntimeError):
        q.submit([6])                       # closed queue refuses new work


def test_serve_close_strands_no_caller():
    """A close() racing queued work must resolve every future promptly —
    served or errored, never left to the caller's own timeout."""
    def serve_fn(ids):
        return np.zeros((len(ids), 2), np.float32)

    from roc_tpu.serve.queue import MicrobatchQueue
    q = MicrobatchQueue(serve_fn, batch=4, wait_ms=1.0)
    futs = [q.submit([i]) for i in range(6)]
    q.close()
    for f in futs:
        assert f.done() or f._event.wait(1.0)
        try:
            out = f.result(0.0)
        except RuntimeError:
            continue                        # closed-before-served is legal
        assert out.shape == (1, 2)
