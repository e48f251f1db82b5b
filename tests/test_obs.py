"""Observability tests (roc_tpu/obs): tracer schema + nesting, metrics
channel parity, zero retraces with obs on, watchdog behavior, the span
overhead bound, and the raw-timing lint rule.

The parity tests are the load-bearing ones: `-obs` must be a pure
*observer* — bitwise-identical losses/params vs an obs-off run, zero new
traces across epochs and a same-cut reshard — or the metrics channel is
changing the thing it measures.
"""

import json
import os

import jax
import numpy as np
import pytest

from roc_tpu import obs
from roc_tpu.analysis import AuditSpec, build_audit_trainer, lint
from roc_tpu.analysis.retrace import RetraceGuard
from roc_tpu.graph import datasets
from roc_tpu.models import build_gcn
from roc_tpu.obs import report as obs_report
from roc_tpu.obs.tracer import SpanTracer, validate_chrome_trace
from roc_tpu.obs.watchdog import PerfWatchdog, seed_for_graph
from roc_tpu.parallel.spmd import SpmdTrainer
from roc_tpu.train.config import Config
from roc_tpu.train.driver import Trainer


@pytest.fixture(autouse=True)
def _obs_reset():
    """Trainers with -obs flip the process-global tracer on; restore it so
    obs state never leaks across tests."""
    tr = obs.get_tracer()
    prev = tr.enabled
    prev_annotate = tr.annotate(False)     # each test arms what it needs
    yield
    tr.enabled = prev
    tr.annotate(prev_annotate)
    tr.clear()


def _dataset(n=80, deg=3.0, in_dim=8, classes=3, seed=13):
    return datasets.synthetic("t", n, deg, in_dim, classes, n_train=20,
                              n_val=20, n_test=20, seed=seed)


# -- tracer ----------------------------------------------------------------

def test_span_nesting_and_chrome_schema():
    tr = SpanTracer(capacity=16)
    tr.enabled = True
    with tr.span("outer", epoch=1):
        with tr.span("inner"):
            pass
        with tr.span("inner"):
            pass
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "inner", "outer"]
    assert [s.depth for s in spans] == [1, 1, 0]
    outer = spans[-1]
    assert outer.args == {"epoch": 1}
    assert outer.dur_ns >= sum(s.dur_ns for s in spans[:2])
    trace = tr.to_chrome_trace()
    assert validate_chrome_trace(trace) == []
    json.dumps(trace)  # Perfetto needs real JSON, not just a dict
    ev = trace["traceEvents"][-1]
    assert ev["ph"] == "X" and ev["name"] == "outer"
    assert ev["args"] == {"epoch": 1}


def test_disabled_span_times_but_records_nothing():
    tr = SpanTracer()
    assert not tr.enabled
    with tr.span("quiet") as sp:
        pass
    assert sp.dur_s > 0          # dur_s is the repo's timing primitive
    assert tr.spans() == []      # ...but nothing lands in the ring


def test_tracer_ring_capacity_bounds_memory():
    tr = SpanTracer(capacity=4)
    tr.enabled = True
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.spans()) == 4
    assert tr.spans()[-1].name == "s9"


def test_validate_chrome_trace_flags_bad_events():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": [{"ph": "X"}]}) != []
    assert validate_chrome_trace(
        {"traceEvents": [{"ph": "X", "name": "a", "ts": "oops", "dur": 1,
                          "pid": 1, "tid": 1}]}) != []


# -- the bridge to the profiler's clock ------------------------------------

def _host_events(trace_dir, prefix="roc."):
    """(name, start ns, duration ns, stats) of the host plane's events
    whose name starts with ``prefix``, by start."""
    import glob

    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.duration_ns,
                         dict(ev.stats)) for ev in line.events
                        if ev.name.startswith(prefix)]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _profiled(trace_dir, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()


def _nested_spans():
    for i in range(20):
        with obs.span("outer", i=i, backend="binned"):
            with obs.span("inner"):
                np.ones(2000).sum()


def test_enabled_spans_are_trace_annotations_on_the_profilers_clock(
        tmp_path):
    """Recording on + a live jax.profiler session: every span is also a
    `roc.<name>` event on the host plane of the .xplane.pb, nested as the
    spans nest, as long as the span (a median over 20, so that a
    pre-empted worker does not fail it), its args as the event's stats."""
    obs.get_tracer().clear()
    obs.enable(True)
    _profiled(tmp_path, _nested_spans)
    spans = sorted(obs.get_tracer().spans(), key=lambda s: s.start_ns)
    events = _host_events(tmp_path)
    assert [e[0] for e in events] == ["roc.outer", "roc.inner"] * 20
    assert [s.name for s in spans] == ["outer", "inner"] * 20
    off = []
    for (name, start, dur, stats), sp in zip(events, spans):
        assert name == "roc." + sp.name
        off.append(abs(dur - sp.dur_ns))
    assert sorted(off)[len(off) // 2] < 200e3           # ns
    for (_, o_start, o_dur, o_stats), (_, i_start, i_dur, _) in zip(
            events[0::2], events[1::2]):
        assert o_start <= i_start and i_start + i_dur <= o_start + o_dur
    assert events[3 * 2][3] == {"i": 3, "backend": "binned"}


def test_disabled_spans_leave_no_trace_annotation(tmp_path):
    obs.enable(False)
    _profiled(tmp_path, _nested_spans)
    assert _host_events(tmp_path) == []
    assert obs.get_tracer().spans() == []


def test_annotate_arms_the_bridge_without_recording(tmp_path):
    """What `-profile` without `-obs` uses: annotations in the trace, no
    span in the ring, and the previous state handed back."""
    obs.enable(False)
    assert obs.annotate(True) is False
    _profiled(tmp_path, _nested_spans)
    assert obs.annotate(False) is True
    assert len(_host_events(tmp_path)) == 40
    assert obs.get_tracer().spans() == [] and not obs.enabled()


def test_tracer_imports_without_jax_and_survives_its_absence():
    """Kernel modules import the tracer before JAX: importing it, and
    opening a disabled span, leaves `jax` out of sys.modules; where
    `jax.profiler` cannot be imported an armed tracer goes on span-only."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys\n"
        "import roc_tpu.obs.tracer as t\n"
        "with t.span('quiet'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'import pulled jax in'\n"
        "sys.modules['jax'] = None      # import jax now raises\n"
        "t.enable(True)\n"
        "with t.span('loud', k=1):\n"
        "    pass\n"
        "assert [s.name for s in t.get_tracer().spans()] == ['loud']\n"
        "assert t.get_tracer()._annotation is None\n"
        "print('ok')\n")
    env = {k: v for k, v in os.environ.items() if k != "ROC_OBS"}
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0 and p.stdout.strip() == "ok", p.stderr[-2000:]


# -- spans where the work happens ------------------------------------------

SETUP_METRICS = ("geometry_s", "plan_key_s", "plan_fetch_s", "plan_place_s",
                 "place_s")


def _setup_metric_spans():
    """The span names the five set-up metrics sum (their data files)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    names = set()
    for metric in SETUP_METRICS:
        with open(os.path.join(root, "benchmark", "layer_metrics",
                               metric + ".json"), encoding="utf-8") as f:
            names |= set(json.load(f)["spans"])
    return names


def test_make_trainer_is_attributed_cold_and_warm(tmp_path, monkeypatch):
    """A cold then a warm `make_trainer` on the binned backend with the plan
    cache on: each records the spans its road takes, and the spans that
    the set-up metrics sum are siblings: none opens inside another, so
    their sum can be held against the whole."""
    from roc_tpu.train.driver import make_trainer
    monkeypatch.setenv("ROC_PLAN_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("ROC_PLAN_CACHE_MIN_EDGES", "0")
    monkeypatch.delenv("ROC_PLAN_CACHE", raising=False)
    summed = _setup_metric_spans()
    ds = _dataset(n=300, deg=5.0)
    obs.enable(True)
    seen = {}
    for run in ("cold", "warm"):
        obs.get_tracer().clear()
        cfg = Config(layers=[8, 8, 3], num_epochs=1, eval_every=1000,
                     dropout_rate=0.0, aggregate_backend="binned")
        make_trainer(cfg, ds, build_gcn(cfg.layers, 0.0))
        spans = obs.get_tracer().spans()
        seen[run] = {s.name for s in spans}
        assert {"choose_geometry", "plan_key", "plan_to_device",
                "place_data", "init_params", "mem_plan", "step_build",
                "plan_build"} <= seen[run], run
        mine = sorted((s for s in spans if s.name in summed),
                      key=lambda s: s.start_ns)
        assert len({s.tid for s in mine}) == 1
        for a, b in zip(mine, mine[1:]):
            assert a.start_ns + a.dur_ns <= b.start_ns, (run, a.name, b.name)
        # ... and every one of them inside the plan build or beside it
        assert {s.depth for s in mine} <= {0, 1}
    assert seen["cold"] & {"plan_native_build", "plan_numpy_build"}
    assert "plan_cache_save" in seen["cold"]
    assert "plan_cache_load" not in seen["cold"]
    assert "plan_cache_load" in seen["warm"]
    assert not seen["warm"] & {"plan_native_build", "plan_numpy_build",
                               "plan_cache_save"}
    assert len(list(tmp_path.glob("binned_plan_*.npz"))) == 2


def test_fused_step_lists_have_a_span_of_their_own():
    """`_attach_fused` left `plan_cache_load`: a flat plan small enough to
    fuse records `plan_fused_steps`, and its arrays go to the device under
    `plan_to_device` like the rest."""
    from roc_tpu.ops.pallas import binned as B
    rng = np.random.default_rng(0)
    dst = np.sort(rng.integers(0, 600, 4000))
    src = rng.integers(0, 600, 4000)
    obs.enable(True)
    obs.get_tracer().clear()
    plan = B._build_binned_plan_numpy(src, dst, 600, 600, geom=B.GEOM_FLAT)
    assert plan.f_meta is not None
    names = [s.name for s in sorted(obs.get_tracer().spans(),
                                    key=lambda s: s.start_ns)]
    assert names == ["plan_numpy_build", "plan_to_device",
                     "plan_fused_steps", "plan_to_device"]


def test_every_epoch_of_train_is_attributed():
    """Three epochs of `train()`: what the host does for a step and between
    two steps has a span, once an epoch, none inside another."""
    tr = _trainer(False, num_epochs=3, eval_every=2)
    obs.enable(True)
    obs.get_tracer().clear()
    tr.train(print_fn=lambda *a, **k: None)
    spans = obs.get_tracer().spans()
    count = {}
    for s in spans:
        count[s.name] = count.get(s.name, 0) + 1
    for name in ("step_args", "step_call", "peak_hbm", "check_nonfinite",
                 "retrace_boundary", "epoch", "step_dispatch",
                 "device_sync"):
        assert count.get(name) == 3, (name, count)
    assert count["eval"] == count["eval_call"] == count["eval_fetch"] == 2
    assert "obs_epoch" not in count and "metrics_fetch" not in count
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    # step_args then step_call inside step_dispatch; the rest between epochs
    for args, call, disp in zip(by_name["step_args"], by_name["step_call"],
                                by_name["step_dispatch"]):
        assert disp.start_ns <= args.start_ns
        assert args.start_ns + args.dur_ns <= call.start_ns
        assert call.start_ns + call.dur_ns <= disp.start_ns + disp.dur_ns
    for ep, hbm in zip(by_name["epoch"], by_name["peak_hbm"]):
        assert ep.start_ns + ep.dur_ns <= hbm.start_ns and hbm.depth == 1
    for ev, call, fetch in zip(by_name["eval"], by_name["eval_call"],
                               by_name["eval_fetch"]):
        assert ev.start_ns <= call.start_ns <= fetch.start_ns
        assert fetch.start_ns + fetch.dur_ns <= ev.start_ns + ev.dur_ns


def test_sharded_setup_and_balance_rounds_are_attributed():
    """The SPMD trainer's set-up roads (partition, halo maps, per-shard
    plans, placement, steps) and the balancer's rounds between epochs."""
    ds = _dataset(n=400, deg=4.0, in_dim=16, classes=4, seed=3)
    cfg = Config(layers=[16, 16, 4], num_epochs=3, num_parts=4, halo=True,
                 eval_every=1000, dropout_rate=0.0, balance_every=1,
                 aggregate_backend="matmul")
    obs.enable(True)
    obs.get_tracer().clear()
    tr = SpmdTrainer(cfg, ds, build_gcn(cfg.layers, 0.0))
    setup = [s.name for s in sorted(obs.get_tracer().spans(),
                                    key=lambda s: s.start_ns)
             if s.depth == 0]
    assert setup == ["init_params", "partition", "halo_build", "plan_build",
                     "place_data", "init_params", "mem_plan", "step_build"]
    # the shards' chunk plans stay host arrays until `place_data` puts each
    # part's block on its own device: none is staged on the default one
    assert "plan_to_device" not in obs.get_tracer().span_types()
    assert all(isinstance(a, jax.Array) and len(a.sharding.device_set) == 4
               for a in jax.tree.leaves(tr.gdata.plans))
    assert tr.balancer is not None
    obs.get_tracer().clear()
    tr.train(print_fn=lambda *a, **k: None)
    spans = obs.get_tracer().spans()
    rounds = [s for s in spans if s.name == "balance"]
    assert len(rounds) == 2            # never after the last epoch
    probes = [s for s in spans if s.name == "probe"]
    assert probes and all(
        any(r.start_ns <= p.start_ns and p.start_ns + p.dur_ns
            <= r.start_ns + r.dur_ns for r in rounds) for p in probes)


def test_obs_epoch_span_only_runs_with_obs(tmp_path):
    tr = _trainer(True, tmp_path, num_epochs=2)
    obs.get_tracer().clear()
    tr.train(print_fn=lambda *a, **k: None)
    names = [s.name for s in obs.get_tracer().spans()]
    assert names.count("obs_epoch") == 2 == names.count("metrics_fetch")


def test_profile_without_obs_holds_the_spans_and_the_same_step(tmp_path):
    """`-profile DIR` without `-obs`: the profiled window's trace holds the
    program's spans as `roc.*` events, nothing is recorded in the ring,
    the metrics channel stays off, and the step is traced as often as
    without `-profile` (the same program)."""
    traces = {}
    for profile in ("", str(tmp_path / "prof")):
        tr = _trainer(False, num_epochs=4, eval_every=2,
                      profile_dir=profile, profile_epochs="1:3")
        obs.get_tracer().clear()
        with RetraceGuard(warmup=0, on_violation="record") as g:
            tr.train(print_fn=lambda *a, **k: None)
        traces[profile] = dict(g.counts)
        assert tr._metrics is None and tr._last_step_metrics is None
        assert obs.get_tracer().spans() == [] and not obs.enabled()
        assert obs.annotate(False) is False      # disarmed after the window
    assert traces[""] == traces[str(tmp_path / "prof")]
    assert traces[""]["train_step"] == 1
    names = [e[0] for e in _host_events(tmp_path / "prof")]
    for name in ("roc.step_args", "roc.step_call", "roc.peak_hbm",
                 "roc.check_nonfinite", "roc.epoch"):
        assert names.count(name) == 3, (name, names)
    assert names.count("roc.eval") == 1 == names.count("roc.eval_fetch")


# -- watchdog --------------------------------------------------------------

def test_watchdog_fires_on_injected_slow_epoch():
    wd = PerfWatchdog()
    for epoch in range(5):
        assert wd.observe_epoch(epoch, 0.1) is None
    alert = wd.observe_epoch(5, 0.3)
    assert alert is not None and alert["kind"] == "slow-epoch"
    assert alert["ratio"] == pytest.approx(3.0, rel=0.05)
    assert wd.verdict() == "regressed"
    # outlier clamping: the anomaly must not poison the EWMA it was
    # measured against — the next normal epoch stays quiet
    assert wd.observe_epoch(6, 0.1) is None


def test_watchdog_quiet_on_noise():
    wd = PerfWatchdog()
    noise = [0.1, 0.102, 0.098, 0.101, 0.099, 0.103, 0.097, 0.1]
    assert all(wd.observe_epoch(i, t) is None for i, t in enumerate(noise))
    assert wd.verdict() == "ok" and wd.alerts == []


def test_watchdog_seeded_is_armed_from_epoch_zero():
    wd = PerfWatchdog(seed_s=0.1)
    alert = wd.observe_epoch(0, 0.5)
    assert alert is not None and alert["ewma_s"] == pytest.approx(0.1)
    # unseeded: epoch 0 carries compile time and never trips the detector
    assert PerfWatchdog().observe_epoch(0, 99.0) is None


def test_watchdog_straggler_detection():
    wd = PerfWatchdog()
    assert wd.observe_shards(0, [0.1, 0.1, 0.1, 0.1]) == []
    alerts = wd.observe_shards(1, [0.1, 0.1, 0.1, 0.5])
    assert len(alerts) == 1 and alerts[0]["part"] == 3
    assert alerts[0]["kind"] == "straggler"
    assert wd.verdict() == "straggler"
    # degenerate inputs never fire
    assert wd.observe_shards(2, [0.1]) == []
    assert wd.observe_shards(3, [0.0, 0.0]) == []


def test_watchdog_budget_seed():
    """reddit_scaled is pinned in tools/kernel_budgets.json: the seed is
    its committed schedule (padded rows, steps per phase) at the binned
    cost model's own price."""
    from roc_tpu.ops.pallas.binned import (_binned_cost_model,
                                           _default_geom)
    seed = seed_for_graph(32768, 4194304)
    assert seed == pytest.approx(_binned_cost_model(
        4454144, _default_geom(), steps1=2240, steps2=1118))
    assert 0.01 < seed < 0.05       # 19 ms at the rates of PR 24
    assert seed_for_graph(17, 17) is None  # unpinned shape -> warmup EWMA


# -- metrics registry ------------------------------------------------------

def test_metrics_registry_shares_telemetry_schema(tmp_path):
    path = str(tmp_path / "m.jsonl")
    reg = obs.MetricsRegistry(jsonl_path=path)
    reg.emit("metrics", epoch=0, loss=1.5, grad_norm=2.0)
    reg.emit("metrics", epoch=1, loss=1.25, grad_norm=1.0)
    reg.emit("watchdog", kind="slow-epoch", epoch=1, ratio=3.0)
    recs = obs.load_jsonl(path)
    # every record rides the balance-telemetry envelope: {"type": kind, ...}
    assert [r["type"] for r in recs] == ["metrics", "metrics", "watchdog"]
    assert recs[1]["loss"] == 1.25
    assert reg.series("metrics", "loss") == [1.5, 1.25]
    assert reg.of_kind("watchdog")[0]["ratio"] == 3.0
    prom = str(tmp_path / "m.prom")
    assert reg.write_prometheus(prom)
    text = open(prom).read()
    assert "roc_metrics_loss 1.25" in text
    assert "roc_metrics_grad_norm 1" in text


def test_load_jsonl_skips_torn_lines(tmp_path):
    path = tmp_path / "torn.jsonl"
    path.write_text('{"type": "metrics", "epoch": 0}\n{"type": "me')
    assert obs.load_jsonl(str(path)) == [{"type": "metrics", "epoch": 0}]


# -- driver integration ----------------------------------------------------

def _trainer(obs_on, tmp_path=None, **kw):
    cfg = dict(layers=[8, 4, 3], num_epochs=4, eval_every=1000,
               dropout_rate=0.0, obs=obs_on)
    if obs_on:
        cfg["obs_dir"] = str(tmp_path / "obs") if tmp_path else ""
    cfg.update(kw)
    cfg = Config(**cfg)
    return Trainer(cfg, _dataset(), build_gcn(cfg.layers, 0.0))


def test_obs_is_a_pure_observer(tmp_path):
    """Losses and params of an obs-on run are bitwise identical to the
    obs-off run: the metrics channel observes the step, never changes it."""
    ta = _trainer(False)
    tb = _trainer(True, tmp_path)
    for _ in range(4):
        la = float(jax.device_get(ta.run_epoch()))
        lb = float(jax.device_get(tb.run_epoch()))
        assert la == lb  # bitwise, not approx
    for ka in ta.params:
        np.testing.assert_array_equal(np.asarray(ta.params[ka]),
                                      np.asarray(tb.params[ka]))


def test_metrics_channel_values(tmp_path):
    """The in-graph metrics match an independent host-side recompute."""
    from roc_tpu.obs import channel
    tr = _trainer(True, tmp_path)
    tr.run_epoch()
    vals = jax.device_get(tr._last_step_metrics)
    # param_norm was computed in-graph on the updated params — recompute
    # from the live (updated) param pytree
    expect = float(jax.jit(channel.global_norm)(tr.params))
    assert float(vals["param_norm"]) == pytest.approx(expect, rel=1e-6)
    assert float(vals["grad_norm"]) > 0.0
    assert float(vals["wire_bytes"]) == 0.0   # single device: no wire
    assert int(vals["edges"][0]) == int(
        np.asarray(jax.device_get(tr.gdata.in_degree)).sum())


def test_obs_train_artifacts_and_span_types(tmp_path):
    """A -obs run emits a Perfetto-loadable trace with >= 8 span types and
    the unified JSONL metrics stream."""
    obs.get_tracer().clear()
    tr = _trainer(True, tmp_path, num_epochs=4, eval_every=2,
                  aggregate_backend="matmul", checkpoint_every=2,
                  checkpoint_path=str(tmp_path / "ck.npz"))
    tr.train(print_fn=lambda *a, **k: None)
    types = obs.get_tracer().span_types()
    assert {"train", "epoch", "step_dispatch", "device_sync",
            "metrics_fetch", "eval", "checkpoint", "plan_build"} <= types
    assert len(types) >= 8
    trace = json.load(open(tmp_path / "obs" / "trace.json"))
    assert validate_chrome_trace(trace) == []
    recs = obs.load_jsonl(str(tmp_path / "obs" / "metrics.jsonl"))
    kinds = [r["type"] for r in recs]
    assert kinds.count("metrics") == 4 and kinds[-1] == "train"
    for r in recs:
        if r["type"] == "metrics":
            assert {"epoch", "wall_s", "loss", "grad_norm", "param_norm",
                    "wire_bytes", "edges_per_shard"} <= set(r)
    assert recs[-1]["watchdog_verdict"] in ("ok", "regressed", "straggler")
    assert (tmp_path / "obs" / "metrics.prom").exists()
    # the report CLI's renderer digests both artifacts
    text = obs_report.report(str(tmp_path / "obs" / "trace.json"),
                             str(tmp_path / "obs" / "metrics.jsonl"))
    assert "step_dispatch" in text and "verdict" in text


def test_spmd_obs_wire_bytes_and_shard_edges(tmp_path):
    """SPMD halo run: wire_bytes reflects the exchange accounting and
    edges land per-shard (out_spec P(PARTS_AXIS))."""
    ds = _dataset(n=400, deg=4.0, in_dim=16, classes=4, seed=3)
    cfg = Config(layers=[16, 16, 4], num_epochs=3, num_parts=4, halo=True,
                 eval_every=1000, dropout_rate=0.0, obs=True,
                 obs_dir=str(tmp_path / "obs"))
    tr = SpmdTrainer(cfg, ds, build_gcn(cfg.layers, 0.0))
    tr.train(print_fn=lambda *a, **k: None)
    recs = [r for r in obs.load_jsonl(str(tmp_path / "obs" / "metrics.jsonl"))
            if r["type"] == "metrics"]
    assert len(recs) == 3
    last = recs[-1]
    assert last["wire_bytes"] > 0
    assert len(last["edges_per_shard"]) == 4
    assert sum(last["edges_per_shard"]) > 0
    from roc_tpu.obs import channel
    gd = tr.gdata
    expect = channel.wire_bytes_per_step(
        "halo", 4, tr.part.shard_nodes, tr._aggregate_widths(),
        send_cols=gd.send_idx.shape[-1] if gd.send_idx is not None else 0,
        xch_dtype=gd.xch_dtype, xch_comp=gd.xch_comp)
    assert last["wire_bytes"] == expect


def test_zero_retraces_with_obs(monkeypatch, tmp_path):
    """The obs acceptance bar: 3 epochs + a same-cut reshard with the
    metrics channel riding the step add ZERO retraces (mirror of
    test_analysis.py::test_zero_retraces_across_epochs_and_reshard)."""
    monkeypatch.setenv("ROC_OBS", "1")
    monkeypatch.setenv("ROC_OBS_DIR", str(tmp_path / "obs"))
    spec = AuditSpec("gcn", 2, "matmul", "halo")
    tr = build_audit_trainer(spec)
    assert tr.config.obs
    tr.config.num_epochs = 3
    with RetraceGuard(warmup=1) as g:
        tr.train(print_fn=lambda *a, **k: None)
        assert g.counts["train_step"] >= 1
        snap = g.snapshot()
        step_ids = (id(tr._train_step), id(tr._eval_step))
        tr.reshard(tr.part.bounds)           # same cut, same shapes
        assert (id(tr._train_step), id(tr._eval_step)) == step_ids
        g.arm()
        tr.run_epoch()
        tr.evaluate()
        g.assert_no_new_traces(snap)


def test_obs_toggle_is_in_the_step_cache_key(monkeypatch, tmp_path):
    """Flipping obs on the same SPMD trainer rebuilds the step (4-tuple
    out) instead of aliasing the cached 3-tuple callable."""
    monkeypatch.setenv("ROC_OBS_DIR", str(tmp_path / "obs"))
    spec = AuditSpec("gcn", 2, "matmul", "halo")
    tr = build_audit_trainer(spec)
    assert not tr.config.obs
    off_step = tr._train_step
    tr.config.obs = True
    tr._obs_init()
    tr._build_steps(tr.gdata)
    assert tr._train_step is not off_step
    tr.run_epoch()
    assert tr._last_step_metrics is not None


# -- overhead gate ---------------------------------------------------------

def test_span_overhead_bound():
    """Per-span cost (the always-on steady state) stays under the report
    gate; obs measures itself — no raw clocks in this test.  Best-of-3:
    a scheduler hiccup on a loaded CI box can smear one probe loop, and
    the honest statistic for "what does a span cost" is the quiet run."""
    tr = SpanTracer()
    tr.enabled = True
    reps = 2000
    best = float("inf")
    for _ in range(3):
        with tr.span("gate") as gate:
            for _ in range(reps):
                with tr.span("probe"):
                    pass
        best = min(best, gate.dur_s / reps)
        if best < obs_report.MAX_SPAN_OVERHEAD_S:
            break
    assert best < obs_report.MAX_SPAN_OVERHEAD_S


def test_obs_epoch_overhead_is_a_count_and_a_size(tmp_path):
    """What -obs adds to an epoch, as counts: the spans it records (the
    ten of an untraced epoch plus obs_epoch and metrics_fetch, each once),
    one fetch of the in-graph metrics, a few dozen bytes in it, and a
    bounded record a span in trace.json.  The time of it is the chip's to
    say (PERF.md: 2.63 us a span recording, 25 us an epoch); a span's own
    cost against the report's gate is test_span_overhead_bound's."""
    epochs = 6
    ds = _dataset(n=400, deg=4.0, in_dim=16, classes=4, seed=5)
    cfg = Config(layers=[16, 16, 4], num_epochs=epochs, eval_every=1000,
                 dropout_rate=0.0, obs=True, obs_dir=str(tmp_path / "obs"))
    tr = Trainer(cfg, ds, build_gcn(cfg.layers, 0.0))
    obs.get_tracer().clear()
    tr.train(print_fn=lambda *a, **k: None)
    count = {}
    for s in obs.get_tracer().spans():
        count[s.name] = count.get(s.name, 0) + 1
    assert count.pop("train") == 1
    per_epoch = {"epoch", "step_dispatch", "step_args", "step_call",
                 "device_sync", "peak_hbm", "check_nonfinite",
                 "retrace_boundary", "obs_epoch", "metrics_fetch"}
    assert {n for n, c in count.items() if c == epochs} == per_epoch, count
    # nothing else grows with the epochs
    assert all(c <= 2 for n, c in count.items() if n not in per_epoch), count
    # the one fetch an epoch brings back scalars, not arrays
    fetched = jax.tree.leaves(tr._last_step_metrics)
    assert sum(int(a.size) * a.dtype.itemsize for a in fetched) <= 64
    # a span's record on disk stays a line of a few hundred bytes
    with open(tmp_path / "obs" / "trace.json", encoding="utf-8") as f:
        events = json.load(f)["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    assert len(spans) >= epochs * len(per_epoch)
    assert max(len(json.dumps(e)) for e in spans) <= 400


def test_selftest_passes():
    msgs = []
    assert obs_report.selftest(out=msgs.append) == 0
    assert any("ok" in m for m in msgs)


# -- config ----------------------------------------------------------------

def test_profile_window_parsing(monkeypatch):
    assert Config().profile_window() == (3, 3)
    assert Config(profile_epochs="0:1").profile_window() == (0, 1)
    with pytest.raises(SystemExit):
        Config(profile_epochs="nope")
    with pytest.raises(SystemExit):
        Config(profile_epochs="3")
    with pytest.raises(SystemExit):
        Config(profile_epochs="-1:2")
    monkeypatch.setenv("ROC_PROFILE_EPOCHS", "5:2")
    assert Config().profile_window() == (5, 2)


def test_obs_env_mirror(monkeypatch):
    monkeypatch.setenv("ROC_OBS", "1")
    cfg = Config()
    assert cfg.obs and cfg.obs_dir == "roc_obs"
    monkeypatch.setenv("ROC_OBS_DIR", "/tmp/elsewhere")
    assert Config().obs_dir == "/tmp/elsewhere"
    monkeypatch.setenv("ROC_OBS", "0")
    assert not Config().obs


# -- raw-timing lint rule --------------------------------------------------

_TIMING_SRC = ("import time\n"
               "def bench(fn):\n"
               "    t0 = time.perf_counter()\n"
               "    fn()\n"
               "    return time.perf_counter() - t0\n")


def test_lint_raw_timing_positive():
    fs = lint.lint_source(_TIMING_SRC, "roc_tpu/train/somefile.py")
    assert any(f.rule == "raw-timing" for f in fs), fs
    # perf_counter_ns windows count too
    src_ns = _TIMING_SRC.replace("perf_counter()", "perf_counter_ns()")
    fs = lint.lint_source(src_ns, "roc_tpu/train/somefile.py")
    assert any(f.rule == "raw-timing" for f in fs), fs
    # module-level windows (script idiom) count too
    src_mod = ("import time\nt0 = time.perf_counter()\nwork()\n"
               "dt = time.perf_counter() - t0\n")
    fs = lint.lint_source(src_mod, "tools/somescript.py")
    assert any(f.rule == "raw-timing" for f in fs), fs


def test_lint_raw_timing_exemptions():
    # roc_tpu/obs/ is the sanctioned clock site
    assert lint.lint_source(_TIMING_SRC, "roc_tpu/obs/tracer.py") == []
    # inline fixtures (non-.py paths) never fire the rule
    assert [f for f in lint.lint_source(_TIMING_SRC, "<string>")
            if f.rule == "raw-timing"] == []
    # a start with no `- t0` use is not a timing window
    src = "import time\ndef f():\n    t0 = time.perf_counter()\n    return 0\n"
    assert lint.lint_source(src, "roc_tpu/train/x.py") == []
    # waivers work like every other rule
    waived = _TIMING_SRC.replace(
        "t0 = time.perf_counter()",
        "t0 = time.perf_counter()  # roclint: allow(raw-timing)")
    assert lint.lint_source(waived, "roc_tpu/train/x.py") == []


# -- calibration ledger ----------------------------------------------------

def _fresh_ledger():
    from roc_tpu.obs.ledger import CalibrationLedger
    return CalibrationLedger()


def test_ledger_content_key_is_order_insensitive():
    from roc_tpu.obs.ledger import content_key
    assert content_key(rows=4, edges=9) == content_key(edges=9, rows=4)
    assert content_key(rows=4, edges=9) == "edges=9|rows=4"


def test_ledger_predict_measure_join_and_ratio():
    led = _fresh_ledger()
    led.predict("plan_steps", "e=9|n=4", 100, "steps")
    r = led.measure("plan_steps", "e=9|n=4", 150, "steps")
    assert r == pytest.approx(1.5)
    kinds = [k for k, _ in led.records]
    assert kinds == ["prediction", "measurement"]
    meas = led.records[-1][1]
    assert meas["predicted"] == 100.0 and meas["ratio"] == pytest.approx(1.5)
    # a different content key does NOT join
    assert led.measure("plan_steps", "e=7|n=4", 150, "steps") is None
    # re-predicting overwrites: the join pairs against the newest
    led.predict("plan_steps", "e=9|n=4", 300, "steps")
    assert led.measure("plan_steps", "e=9|n=4", 150, "steps") \
        == pytest.approx(0.5)


def test_ledger_emission_is_gated_on_attach(tmp_path):
    from roc_tpu.obs.metrics import MetricsRegistry
    led = _fresh_ledger()
    led.predict("x", "k=1", 1.0, "s")          # detached: no sink, no error
    reg = MetricsRegistry(jsonl_path=str(tmp_path / "m.jsonl"))
    led.attach(reg.emit)
    led.predict("step_time", "k=1", 2.0, "s")
    led.measure("step_time", "k=1", 3.0, "s")
    led.detach()
    led.measure("step_time", "k=1", 9.0, "s")  # detached again: not emitted
    kinds = [k for k, _ in reg.records]
    assert kinds == ["prediction", "measurement"]


def test_ledger_drain_ratios_feeds_and_clears():
    led = _fresh_ledger()
    led.predict("m", "k", 2.0, "s")
    led.measure("m", "k", 4.0, "s")
    assert led.drain_ratios() == [("m", 2.0)]
    assert led.drain_ratios() == []            # drained


def test_ledger_validate_and_offline_join():
    from roc_tpu.obs.ledger import calibration_report, join, validate_records
    stream = [
        {"type": "prediction", "model": "m", "key": "k", "value": 2.0,
         "units": "s"},
        {"type": "measurement", "model": "m", "key": "k", "value": 3.0,
         "units": "s"},                        # unpaired in-stream: re-joins
        {"type": "metrics", "wall_s": 0.1},    # foreign kinds pass through
    ]
    assert validate_records(stream) == []
    joined = join(stream)
    assert joined[0]["ratio"] == pytest.approx(1.5)
    rep = calibration_report(stream)
    assert rep["models"]["m"]["pairs"] == 1
    assert rep["models"]["m"]["ratio_mean"] == pytest.approx(1.5)
    # broken records are named, not crashed on
    bad = [{"type": "measurement", "model": "m", "key": "k", "value": 1.0,
            "units": "s", "ratio": 2.0}]       # ratio without predicted
    assert validate_records(bad)


def test_watchdog_calibration_drift_fires_and_quiet():
    wd = PerfWatchdog(warmup=2)
    # in-band ratios never alert, regardless of count
    for _ in range(6):
        assert wd.observe_calibration("plan_steps", 1.1) is None
    # out-of-band model: warmup pairs build the EWMA silently, then fire
    assert wd.observe_calibration("step_time", 5.0, epoch=0) is None
    assert wd.observe_calibration("step_time", 5.0, epoch=1) is None
    alert = wd.observe_calibration("step_time", 5.0, epoch=2)
    assert alert is not None and alert["kind"] == "calibration-drift"
    assert alert["model"] == "step_time"
    assert wd.verdict() == "calibration-drift"
    # a non-positive ratio is a broken pair, not drift
    assert wd.observe_calibration("peak_memory", 0.0) is None


def test_report_renders_unknown_span_and_alert_kinds():
    """The report is generic over span names and alert kinds: a kind
    invented after this renderer was written must show up, not fall into
    some slow-epoch-shaped else branch."""
    trace = {"traceEvents": [
        {"name": "never_seen_span", "ph": "X", "ts": 0, "dur": 1500.0,
         "pid": 1, "tid": 1}]}
    lines = "\n".join(obs_report.summarize_trace(trace))
    assert "never_seen_span" in lines
    records = [
        {"type": "somefuturekind", "x": 1},
        {"type": "watchdog", "kind": "flux-capacitor", "epoch": 3,
         "overcharge": 1.21},
    ]
    txt = "\n".join(obs_report.summarize_metrics(records))
    assert "somefuturekind x1" in txt          # census counts unknown kinds
    assert "flux-capacitor" in txt
    assert "overcharge=1.21" in txt            # numeric fields render generically


# -- Prometheus export format ----------------------------------------------

def test_prometheus_labeled_gauges_and_escaping(tmp_path):
    from roc_tpu.obs.metrics import MetricsRegistry
    reg = MetricsRegistry(jsonl_path="")
    reg.emit("epoch", wall_s=0.25)
    reg.set_gauge("calibration_ratio", 1.5, model="plan_steps")
    # label values with every escape-worthy character
    reg.set_gauge("calibration_ratio", 2.0, model='we"ird\\mo\ndel')
    path = str(tmp_path / "prom.txt")
    assert reg.write_prometheus(path)
    text = open(path, encoding="utf-8").read()
    assert 'roc_calibration_ratio{model="plan_steps"} 1.5' in text
    assert r'model="we\"ird\\mo\nmodel"' not in text  # name kept intact...
    assert r'we\"ird\\mo\ndel' in text                # ...escaped, not mangled
    assert "roc_epoch_wall_s 0.25" in text
    assert "\n\n" not in text.strip()


def test_prometheus_skips_nonfinite_and_updates_latest(tmp_path):
    from roc_tpu.obs.metrics import MetricsRegistry
    reg = MetricsRegistry(jsonl_path="")
    reg.emit("epoch", loss=float("nan"), wall_s=float("inf"), ok=3.0)
    reg.set_gauge("calibration_ratio", float("nan"), model="m")
    path = str(tmp_path / "prom.txt")
    assert reg.write_prometheus(path)
    text = open(path, encoding="utf-8").read()
    assert "nan" not in text and "inf" not in text
    assert "roc_epoch_ok 3" in text
    # a later finite value for the same series replaces the skip
    reg.emit("epoch", loss=0.5)
    reg.set_gauge("calibration_ratio", 1.25, model="m")
    assert reg.write_prometheus(path)
    text = open(path, encoding="utf-8").read()
    assert "roc_epoch_loss 0.5" in text
    assert 'roc_calibration_ratio{model="m"} 1.25' in text


def test_measurement_records_auto_export_calibration_gauge(tmp_path):
    """The registry turns ledger measurement records into per-model
    roc_calibration_ratio{model=...} gauges without extra wiring."""
    from roc_tpu.obs.metrics import MetricsRegistry
    led = _fresh_ledger()
    reg = MetricsRegistry(jsonl_path="")
    led.attach(reg.emit)
    led.predict("wire_bytes", "k=1", 100, "B")
    led.measure("wire_bytes", "k=1", 110, "B")
    led.detach()
    path = str(tmp_path / "prom.txt")
    assert reg.write_prometheus(path)
    text = open(path, encoding="utf-8").read()
    assert 'roc_calibration_ratio{model="wire_bytes"} 1.1' in text
