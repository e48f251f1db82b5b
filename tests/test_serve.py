"""Inference serving path (roc_tpu/serve/).

The contract under test mirrors ISSUE 13's acceptance gates:

- served logits match the training-side eval forward to <= 32 ULPs,
  across matmul/binned backends, both precisions and fp32/bf16 storage (same
  params, same graph data, same model.apply — serving adds a gather,
  never a different forward);
- an arbitrary mixed-batch-size request stream never retraces after
  `warmup()` — queries are bucketed to the power-of-two ladder and
  padded, so at most len(buckets) serve_step variants ever compile;
- cold start from a warm content-keyed plan cache performs ZERO plan
  rebuilds (pinned by diffing the builder's process counter);
- the microbatch queue drains on batch-or-deadline, resolves errors to
  futures without killing the worker, and prices queueing delay into
  per-request latency;
- the observability edges hold: watchdog serve-latency EWMA, the
  serve-p50 calibration-ledger pair, the BENCH_SERVE.json schema gate,
  and roclint's serve host-sync rule.
"""

import json
import os
import time

import numpy as np
import pytest

from roc_tpu.graph import datasets
from roc_tpu.models import build_model
from roc_tpu.obs.watchdog import PerfWatchdog
from roc_tpu.serve import (MicrobatchQueue, ServeEngine, bucket_sizes,
                           max_ulp_diff, run_load)
from roc_tpu.serve.loadgen import percentile
from roc_tpu.train.config import Config


@pytest.fixture(autouse=True)
def _lock_order_witness(lock_witness):
    # every serve test runs under the armed lock-order witness; any
    # acquisition order outside threads.json fails at teardown
    yield


def _engine(ds, *, model="gcn", backend="matmul", bf16_storage=False,
            heads=2, start_queue=False, serve_batch=8,
            serve_wait_ms=1.0, precision="fast"):
    cfg = Config(layers=[ds.in_dim, 16, ds.num_classes], dropout_rate=0.0,
                 eval_every=10**9, model=model, heads=heads,
                 aggregate_backend=backend, bf16_storage=bf16_storage,
                 serve_batch=serve_batch,
                 serve_wait_ms=serve_wait_ms, aggregate_precision=precision)
    m = build_model(model, cfg.layers, cfg.dropout_rate, cfg.aggr,
                    heads=heads)
    return ServeEngine(cfg, ds, m, start_queue=start_queue)


# -- bucketing -------------------------------------------------------------

def test_bucket_ladder():
    assert bucket_sizes(1) == [1]
    assert bucket_sizes(8) == [1, 2, 4, 8]
    # a non-power-of-two cap still appears as the top bucket
    assert bucket_sizes(6) == [1, 2, 4, 6]
    assert bucket_sizes(64) == [1, 2, 4, 8, 16, 32, 64]


def test_bucket_for_maps_to_smallest_fitting():
    ds = datasets.get("roc-audit", seed=1)
    eng = _engine(ds, serve_batch=8)
    try:
        assert [eng.bucket_for(n) for n in (1, 2, 3, 5, 8)] == \
            [1, 2, 4, 8, 8]
        assert eng.bucket_for(100) == 8     # oversize chunks split at cap
    finally:
        eng.close()


# -- parity: served == eval forward, <= 32 ULPs ----------------------------

@pytest.mark.parametrize("backend,precision,bf16", [
    ("matmul", "fast", False),
    ("binned", "fast", False),
    ("binned", "exact", False),   # fp32 staging, 3-way bf16 split dots
    ("binned", "fast", True),     # bf16 storage / fp32 accumulation
])
def test_served_matches_eval_forward(backend, precision, bf16):
    """Every query row must equal the eval forward's row to <= 32 ULPs.

    The oracle is `FrozenBundle.predict_logits` — the SAME jitted program
    eval runs — so this pins that bucketing/padding/gather never perturb
    the forward, per backend, precision and storage mode."""
    ds = datasets.get("roc-audit", seed=1)
    eng = _engine(ds, backend=backend, precision=precision,
                  bf16_storage=bf16)
    try:
        ref = np.asarray(eng.bundle.predict_logits())
        rng = np.random.default_rng(7)
        # unsorted, duplicated, every bucket + an over-cap chunk
        for k in (1, 3, 8, 17):
            ids = rng.integers(0, ds.graph.num_nodes, size=k)
            got = eng._serve_rows(ids.astype(np.int32))
            assert got.shape == (k, ds.num_classes)
            assert max_ulp_diff(got, ref[ids]) <= 32
    finally:
        eng.close()


def test_served_bitwise_at_exact_precision():
    """At exact aggregation precision the served rows are BITWISE the
    eval forward's (0 ULPs) — serving is the same program plus a
    gather, and exact precision removes every reassociation excuse."""
    ds = datasets.get("roc-audit", seed=1)
    eng = _engine(ds, backend="binned", precision="exact")
    try:
        ref = np.asarray(eng.bundle.predict_logits())
        ids = np.arange(ds.graph.num_nodes, dtype=np.int32)
        assert max_ulp_diff(eng._serve_rows(ids), ref) == 0
    finally:
        eng.close()


def test_served_matches_eval_forward_gat():
    """Attention coefficients ride the same forward: GAT parity too."""
    ds = datasets.get("roc-audit", seed=1)
    eng = _engine(ds, model="gat", backend="binned", heads=2)
    try:
        ref = np.asarray(eng.bundle.predict_logits())
        ids = np.arange(ds.graph.num_nodes, dtype=np.int32)
        got = eng._serve_rows(ids)
        assert max_ulp_diff(got, ref) <= 32
    finally:
        eng.close()


def test_served_matches_eval_forward_gat_plan():
    """A gat model over the plan road of attention (ops.edge
    gat_attend_plan) serves what eval computes (<= 32 ULPs), from one
    trace."""
    ds = datasets.get("roc-audit", seed=1)
    eng = _engine(ds, model="gat", backend="binned")
    try:
        assert eng.bundle.gdata.gat_plans is not None
        assert eng.cold_start_stats["traces"] == 1
        ref = np.asarray(eng.bundle.predict_logits())
        ids = np.arange(ds.graph.num_nodes, dtype=np.int32)
        assert max_ulp_diff(eng._serve_rows(ids), ref) <= 32
    finally:
        eng.close()


def test_ulp_metric():
    a = np.float32([1.0, -2.0, 0.0])
    assert max_ulp_diff(a, a.copy()) == 0
    assert max_ulp_diff(np.float32([1.0]),
                        np.float32([np.nextafter(np.float32(1.0),
                                                np.float32(2.0))])) == 1
    # sign-crossing distance counts through zero, not bit-pattern delta
    tiny = np.nextafter(np.float32(0.0), np.float32(1.0))
    assert max_ulp_diff(np.float32([tiny]), np.float32([-tiny])) == 2
    # NaN matches NaN positionally; NaN-vs-number is maximally far
    nan = np.float32([np.nan])
    assert max_ulp_diff(nan, nan) == 0
    assert max_ulp_diff(nan, np.float32([1.0])) == np.iinfo(np.int64).max


# -- cold start: warm plan cache means ZERO plan rebuilds ------------------

def test_cold_start_zero_plan_builds(tmp_path, monkeypatch):
    from roc_tpu.ops.pallas import binned as B
    monkeypatch.setenv("ROC_PLAN_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("ROC_PLAN_CACHE_MIN_EDGES", "0")
    ds = datasets.get("roc-audit", seed=1)
    first = _engine(ds, backend="binned")
    builds_cold = first.cold_start_stats["plan_builds"]
    first.close()
    assert builds_cold >= 1                 # fresh cache: plans were built
    warm = _engine(ds, backend="binned")
    try:
        cs = warm.cold_start_stats
        assert cs["plan_builds"] == 0       # THE serving cold-start pin
        assert cs["traces"] == 1            # one jit trace, smallest bucket
        assert cs["cold_start_s"] > 0.0
        assert cs["buckets"] == [1, 2, 4, 8]
    finally:
        warm.close()


# -- zero retraces across a mixed-size request stream ----------------------

def test_zero_retrace_over_mixed_stream():
    """100 requests with sizes drawn across every bucket (and over the
    cap): after warmup() the guard must record zero new serve_step
    traces — the whole stream reuses the warm ladder."""
    ds = datasets.get("roc-audit", seed=1)
    eng = _engine(ds, backend="binned", start_queue=True)
    try:
        eng.warmup()
        assert sum(eng._guard.counts.values()) == len(eng.buckets)
        baseline = eng._guard.snapshot()
        rng = np.random.default_rng(11)
        sizes = [1, 2, 3, 5, 8, 13]
        futs = [eng.submit(rng.integers(0, ds.graph.num_nodes,
                                        size=sizes[i % len(sizes)]))
                for i in range(100)]
        for f in futs:
            assert f.result(timeout=60.0).shape[1] == ds.num_classes
        eng._guard.assert_no_new_traces(baseline)
        st = eng.stats()
        assert st["requests"] == 100 and st["windows"] >= 1
    finally:
        eng.close()


def test_query_rejects_out_of_range_ids():
    ds = datasets.get("roc-audit", seed=1)
    eng = _engine(ds, start_queue=True)
    try:
        with pytest.raises(IndexError):
            eng.query([ds.graph.num_nodes + 5], timeout=30.0)
        # the worker survived the error: the next request still serves
        assert eng.query([0], timeout=30.0).shape == (1, ds.num_classes)
    finally:
        eng.close()


def test_apply_delta_requires_enable_at_construction():
    # enabling deltas after warmup would change the plan treedef and
    # retrace; an engine built without delta support must say so, not
    # silently degrade (full delta coverage lives in tests/test_delta.py)
    from roc_tpu.serve import DeltaError
    ds = datasets.get("roc-audit", seed=1)
    eng = _engine(ds)
    try:
        with pytest.raises(DeltaError, match="delta_journal"):
            eng.apply_delta(add_edges=[(0, 1)])
    finally:
        eng.close()


# -- microbatch queue (no engine: a recording serve_fn) --------------------

def _echo_serve(ids):
    return ids.astype(np.float32)[:, None]


def test_queue_batches_and_slices_per_request():
    q = MicrobatchQueue(_echo_serve, batch=4, wait_ms=20.0)
    try:
        futs = [q.submit([i]) for i in range(4)]
        outs = [f.result(timeout=10.0) for f in futs]
        for i, out in enumerate(outs):
            np.testing.assert_array_equal(out, [[float(i)]])
        assert q.served == 4
        # latency prices queue wait + serve, never negative
        assert all(f.latency_s >= 0.0 for f in futs)
    finally:
        q.close()


def test_queue_deadline_drains_partial_window():
    """A lone sub-batch request must not wait forever: the wait_ms
    deadline drains it."""
    q = MicrobatchQueue(_echo_serve, batch=64, wait_ms=5.0)
    try:
        t0 = time.perf_counter()
        out = q.query([3], timeout=10.0)
        assert time.perf_counter() - t0 < 5.0   # deadline, not timeout
        np.testing.assert_array_equal(out, [[3.0]])
    finally:
        q.close()


def test_queue_resolves_errors_without_dying():
    calls = {"n": 0}

    def flaky(ids):
        calls["n"] += 1
        if calls["n"] == 1:
            raise ValueError("injected")
        return _echo_serve(ids)

    q = MicrobatchQueue(flaky, batch=1, wait_ms=1.0)
    try:
        with pytest.raises(ValueError, match="injected"):
            q.query([1], timeout=10.0)
        np.testing.assert_array_equal(q.query([2], timeout=10.0), [[2.0]])
    finally:
        q.close()


def test_queue_rejects_empty_and_closed():
    from roc_tpu.serve.queue import Closed
    q = MicrobatchQueue(_echo_serve, batch=2, wait_ms=1.0)
    with pytest.raises(AssertionError):
        q.submit([])
    q.close()
    # submit-after-close is TYPED: the fleet router tells this lifecycle
    # signal ("re-route to a sibling") apart from a depth-cap Overloaded
    with pytest.raises(Closed):
        q.submit([1])
    # ... while pre-taxonomy callers catching RuntimeError still work
    assert issubclass(Closed, RuntimeError)
    q.close()                        # idempotent: double close is a no-op


# -- load generator --------------------------------------------------------

def test_percentile_nearest_rank():
    vals = [float(i) for i in range(1, 101)]
    assert percentile(vals, 0.50) == 51.0
    assert percentile(vals, 0.99) == 99.0
    assert percentile(vals, 1.00) == 100.0
    assert percentile([7.0], 0.99) == 7.0
    assert percentile([], 0.5) == 0.0


def test_run_load_open_loop_stats():
    ds = datasets.get("roc-audit", seed=1)
    eng = _engine(ds, start_queue=True)
    try:
        eng.warmup()
        stats = run_load(eng, n_requests=12, qps=400.0, sizes=(1, 2))
        assert stats["n"] == 12
        assert stats["qps_offered"] == 400.0
        assert 0.0 < stats["p50_s"] <= stats["p99_s"]
        assert stats["qps_achieved"] > 0
    finally:
        eng.close()


# -- watchdog: serve-latency EWMA ------------------------------------------

def test_watchdog_serve_latency_alert_and_verdict():
    wd = PerfWatchdog(ratio=3.0, warmup=1)
    assert wd.observe_serve(0, 0.010) is None   # obs 0: warmup noise
    assert wd.observe_serve(1, 0.010) is None   # sets the EWMA baseline
    alert = wd.observe_serve(2, 0.050)          # 5x the tail: collapse
    assert alert is not None and alert["kind"] == "serve-latency"
    assert alert["ratio"] == pytest.approx(5.0)
    assert wd.verdict() == "serve-latency"
    # the outlier was clamped into the EWMA: baseline not poisoned
    assert wd.serve_ewma < 0.050


def test_watchdog_serve_quiet_on_noise():
    wd = PerfWatchdog(ratio=3.0, warmup=1)
    for w, p in enumerate([0.010, 0.011, 0.009, 0.012, 0.010]):
        assert wd.observe_serve(w, p) is None
    assert wd.verdict() == "ok"


# -- calibration ledger: the serve-p50 pair --------------------------------

def test_serve_p50_ledger_pair():
    """Each watchdog feed must land a joined prediction/measurement pair
    under the serve-p50 cost model (roofline forward bound vs observed
    p50) — the pair `python -m roc_tpu.obs calibration` reports."""
    from roc_tpu import obs
    ds = datasets.get("roc-audit", seed=1)
    eng = _engine(ds)
    try:
        led = obs.get_ledger()
        n0 = len(led.records)
        for _ in range(8):                  # one full feed window
            eng._note_window([0.002, 0.003, 0.004])
        recs = list(led.records)[n0:]
        ms = [r for kind, r in recs
              if kind == "measurement" and r["model"] == "serve-p50"]
        assert ms and "ratio" in ms[-1] and ms[-1]["predicted"] > 0
        assert ms[-1]["value"] == 0.003     # the window median
    finally:
        eng.close()


# -- BENCH_SERVE.json schema gate (tools/perf_ledger.py) -------------------

def _perf_ledger_mod():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "perf_ledger", os.path.join(os.path.dirname(__file__), "..",
                                    "tools", "perf_ledger.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _serve_payload(**over):
    d = {"metric": "serve_p50", "value": 0.002, "unit": "s",
         "p50_s": 0.002, "p99_s": 0.006, "qps_offered": 100.0,
         "cold_start_s": 0.8, "platform": "cpu",
         "delta": {"apply_p50_s": 0.001, "apply_p99_s": 0.004,
                   "batches": 40, "replans": 1},
         "measured_at": "2026-08-05T00:00:00Z"}
    d.update(over)
    return {k: v for k, v in d.items() if v is not None}


def test_perf_ledger_serve_artifact_schema(tmp_path):
    pl = _perf_ledger_mod()
    root = str(tmp_path)
    with open(os.path.join(root, pl.SERVE_ARTIFACT), "w") as f:
        json.dump(_serve_payload(), f)
    assert pl.check(root) == []


def test_perf_ledger_serve_artifact_malformed(tmp_path):
    pl = _perf_ledger_mod()
    root = str(tmp_path)
    with open(os.path.join(root, pl.SERVE_ARTIFACT), "w") as f:
        json.dump(_serve_payload(p99_s=None, measured_at=None,
                                 delta={"apply_p50_s": 0.001}), f)
    errs = pl.check(root)
    assert any("BENCH_SERVE.json" in e and "p99_s" in e for e in errs)
    assert any("measured_at" in e for e in errs)
    # the nested delta block is schema-gated too
    assert any("delta.apply_p99_s" in e for e in errs)
    assert any("delta.replans" in e for e in errs)


# -- roclint: serve host-sync rule -----------------------------------------

def test_lint_serve_sync_rule():
    from roc_tpu.analysis import lint
    src = "import numpy as np\ndef f(x):\n    return np.asarray(x)\n"
    fs = lint.lint_source(src, "roc_tpu/serve/fake.py")
    assert any(f.rule == "host-sync" for f in fs), fs
    # the same conversion outside roc_tpu/serve/ is not a finding
    assert not any(f.rule == "host-sync"
                   for f in lint.lint_source(src, "roc_tpu/train/fake.py"))
    # explicit device syncs are findings too
    src2 = "def g(y):\n    return y.block_until_ready()\n"
    assert any(f.rule == "host-sync"
               for f in lint.lint_source(src2, "roc_tpu/serve/fake.py"))


def test_lint_serve_sync_waiver():
    from roc_tpu.analysis import lint
    src = ("import numpy as np\ndef f(x):\n"
           "    return np.asarray(x)  # roclint: allow(host-sync)\n")
    assert lint.lint_source(src, "roc_tpu/serve/fake.py") == []


# -- config knobs ----------------------------------------------------------

def test_serve_config_knobs(monkeypatch):
    assert Config(layers=[4, 4]).serve_batch == 64
    monkeypatch.setenv("ROC_SERVE_BATCH", "16")
    monkeypatch.setenv("ROC_SERVE_WAIT_MS", "0.5")
    cfg = Config(layers=[4, 4])
    assert cfg.serve_batch == 16 and cfg.serve_wait_ms == 0.5
    monkeypatch.setenv("ROC_SERVE_BATCH", "junk")
    with pytest.raises(SystemExit):
        Config(layers=[4, 4])
    monkeypatch.delenv("ROC_SERVE_BATCH")
    monkeypatch.delenv("ROC_SERVE_WAIT_MS")
    with pytest.raises(SystemExit):
        Config(layers=[4, 4], serve_batch=0)
    with pytest.raises(SystemExit):
        Config(layers=[4, 4], serve_wait_ms=-1.0)
