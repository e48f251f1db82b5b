"""Memory planner tests: estimator cross-check, DP optimality, policy
equivalence, retrace invariance, roclint remat rule.

Five layers of evidence, matching the subsystem's pipeline:
  * the analytic byte estimator agrees with XLA's own compiled-program
    buffer accounting within 10% across the audit matrix;
  * the DP planner is OPTIMAL — brute-force enumeration over {keep,remat}^L
    synthetic cases never beats it, and infeasible budgets degrade to the
    all-REMAT floor with the flag set;
  * an active plan changes memory, not math: a tight budget flips layers
    to remat and the one-epoch loss matches all-KEEP to float tolerance;
  * plans don't leak into trace churn: RetraceGuard stays at literal zero
    across epochs and a same-cut reshard with a plan active;
  * raw ``jax.checkpoint`` outside roc_tpu/memory/policy.py is a lint
    finding (waivable, path-exempt at the sanctioned site).
"""

import itertools
import os

import numpy as np
import pytest

from roc_tpu.analysis import lint
from roc_tpu.analysis.hlo_audit import (AuditSpec, build_audit_trainer,
                                        spec_key)
from roc_tpu.analysis.retrace import RetraceGuard
from roc_tpu.memory import (KEEP, REMAT, LayerEstimate, ModelEstimate,
                            estimate_model, feasible, fixed_bytes_for,
                            plan_memory, predict_peak, predict_time,
                            step_arg_bytes, xla_memory_stats)
from roc_tpu.models import build_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- estimator vs XLA -----------------------------------------------------

# A slice of the audit matrix covering model/parts/backend/exchange
# variation; the full 24-entry matrix compiles each train step and would
# dominate the lane's runtime for no extra signal.
_XLA_SPECS = [
    AuditSpec("gcn", 1, "matmul", "single"),
    AuditSpec("gcn", 1, "binned", "single"),
    AuditSpec("gcn", 2, "matmul", "halo"),
    AuditSpec("gcn", 4, "matmul", "allgather"),
    AuditSpec("gat", 1, "matmul", "single"),
    AuditSpec("gat", 2, "binned", "halo"),
]


@pytest.mark.parametrize("spec", _XLA_SPECS, ids=spec_key)
def test_step_arg_bytes_matches_xla(spec):
    """Analytic per-device argument bytes vs the compiled train step's
    XLA-reported argument (+ donation-aliased) buffer bytes: within 10%."""
    tr = build_audit_trainer(spec)
    analytic = step_arg_bytes(tr)
    stats = xla_memory_stats(tr)
    if not stats:
        pytest.skip("backend does not implement memory_analysis")
    xla = stats["argument_bytes"] + stats["alias_bytes"]
    assert xla > 0
    assert abs(analytic - xla) / xla <= 0.10, (analytic, xla)


def test_estimator_layer_structure():
    """Per-layer estimates track the op IR: one estimate per layer, saved
    <= full, the boundary tensor is part of the saved set, and elementwise
    interiors price into the cheap recompute."""
    model = build_model("gcn", [100, 256, 256, 47])
    est = estimate_model(model, rows=1000, edges=5000)
    assert len(est.layers) == model.num_layers == 3
    for l in est.layers:
        assert 0 < l.bytes_saved <= l.bytes_full
        assert 0 < l.bytes_boundary <= l.bytes_saved
        assert 0.0 < l.recompute_cheap_s < l.recompute_full_s
    assert est.base_step_s > 0.0


# -- DP optimality vs brute force -----------------------------------------

def _synthetic_estimate(rng, L, far=False):
    """``far``: every layer pins its boundary (a later segment's input,
    live under every verdict) and layer 0 a far output of its own size
    besides, as GCNII's H0 is: what an active plan never gives back."""
    layers = []
    for i in range(L):
        full = int(rng.integers(8, 100)) * 1024
        saved = int(full * rng.uniform(0.3, 0.9))
        fwd = float(rng.uniform(0.5, 5.0))
        pinned = 0 if not far else saved // 2 if i else saved
        layers.append(LayerEstimate(
            index=i, name=f"L{i}", bytes_full=full, bytes_saved=saved,
            bytes_boundary=saved // 2, recompute_full_s=fwd,
            recompute_cheap_s=fwd * float(rng.uniform(0.05, 0.4)),
            bytes_pinned=pinned))
    return ModelEstimate(layers=tuple(layers), fixed_bytes=16 * 1024,
                         base_step_s=3.0 * sum(l.recompute_full_s
                                               for l in layers),
                         rows=0, edges=0)


def _brute_force(est, budget):
    """(best feasible time, any feasible?) by full enumeration."""
    best, any_ok = None, False
    for dec in itertools.product((KEEP, REMAT), repeat=len(est.layers)):
        if not feasible(est, dec, budget):
            continue
        any_ok = True
        t = predict_time(est, dec)
        if best is None or t < best:
            best = t
    return best, any_ok


@pytest.mark.parametrize("name,kw,total", [
    ("gcn-reddit", dict(layers=[602, 256, 41], dropout_rate=0.5),
     2145141720),
    ("gat-reddit", dict(layers=[602, 8, 41], dropout_rate=0.6, heads=8),
     1836352035),
    ("tconv-reddit", dict(layers=[602, 128, 128, 41], dropout_rate=0.3,
                          heads=4), 3856353084),
    # PR 37: eight [N, 256] float32 outputs a GCNII layer, sixteen layers
    ("gcnii-reddit", dict(layers=[602] + [256] * 16 + [41],
                          dropout_rate=0.5), 31850042940),
])
def test_estimate_of_the_benchmarks_models_is_pinned(name, kw, total):
    """The estimator's all-KEEP bytes for the one-chip configurations of
    BENCHMARK.json at the cells' size (232,965 rows, 23,516,643 edges):
    the figures of the commit before the fused paths and their drops left
    the estimator (PR 27), so a change to what it counts shows here."""
    from roc_tpu.models import build_model
    model = build_model(name.split("-")[0], kw["layers"], kw["dropout_rate"],
                        "", heads=kw.get("heads", 8))
    est = estimate_model(model, 232965, 23516643)
    assert est.total_full_bytes() == total


@pytest.mark.parametrize("L,far", [(L, False) for L in range(2, 9)]
                         + [(4, True), (7, True), (10, True)])
def test_dp_matches_brute_force(L, far):
    """All 2^L plans, also on a model with a far input (pinned bytes that
    no verdict frees), up to L = 10."""
    rng = np.random.default_rng(100 + L)
    for trial in range(6):
        est = _synthetic_estimate(rng, L, far)
        keep_peak = predict_peak(est, [KEEP] * L)
        remat_peak = predict_peak(est, [REMAT] * L)
        for frac in (0.0, 0.35, 0.6, 0.85, 1.1):
            # budgets spanning infeasible .. trivially feasible
            budget = int(remat_peak + frac * (keep_peak - remat_peak)) \
                if frac else int(remat_peak * 0.9)
            plan = plan_memory(est, mode="auto", budget_bytes=budget)
            best, any_ok = _brute_force(est, budget)
            if not any_ok:
                # planner ships the all-REMAT floor and flags it
                assert not plan.feasible
                assert all(d != KEEP for d in plan.decisions)
                continue
            assert plan.feasible, (L, trial, frac, plan.decisions)
            got = predict_time(est, plan.decisions)
            assert got <= best + 1e-12, (L, trial, frac, got, best,
                                         plan.decisions)


def test_unbounded_budget_keeps_everything():
    rng = np.random.default_rng(7)
    est = _synthetic_estimate(rng, 4)
    plan = plan_memory(est, mode="auto", budget_bytes=0)
    assert plan.decisions == (KEEP,) * 4
    assert plan.predicted_step_s == est.base_step_s


def test_greedy_fallback_past_dp_max_layers():
    from roc_tpu.memory.planner import DP_MAX_LAYERS
    rng = np.random.default_rng(11)
    L = DP_MAX_LAYERS + 4
    est = _synthetic_estimate(rng, L)
    keep_peak = predict_peak(est, [KEEP] * L)
    plan = plan_memory(est, mode="auto", budget_bytes=int(keep_peak * 0.6))
    assert plan.planner == "greedy"
    assert plan.feasible and plan.any_remat()


def test_plan_json_deterministic():
    """Same estimate + budget -> byte-identical JSON (the plan is part of
    the step cache key; preflight pins the CLI flavor of this)."""
    rng1, rng2 = np.random.default_rng(3), np.random.default_rng(3)
    e1, e2 = _synthetic_estimate(rng1, 5), _synthetic_estimate(rng2, 5)
    budget = int(predict_peak(e1, [KEEP] * 5) * 0.7)
    p1 = plan_memory(e1, mode="auto", budget_bytes=budget)
    p2 = plan_memory(e2, mode="auto", budget_bytes=budget)
    assert p1.to_json() == p2.to_json()
    assert p1.key() == p2.key()


# -- plan semantics on a real trainer -------------------------------------

def _one_epoch_loss(tr):
    import jax
    return float(jax.device_get(tr.run_epoch()))


def test_tight_budget_flips_layers_and_preserves_loss(monkeypatch):
    """A budget below the all-KEEP peak flips >= 1 layer off KEEP, and the
    planned train step computes the same loss as the unplanned one."""
    spec = AuditSpec("gcn", 1, "matmul", "single")
    tr_keep = build_audit_trainer(spec)
    assert tr_keep.mem_plan.decisions == (KEEP,) * len(
        tr_keep.mem_plan.decisions)
    # midway between the all-REMAT floor and the all-KEEP peak: forces a
    # flip, guaranteed satisfiable
    budget = (tr_keep.mem_plan.keep_peak_bytes +
              tr_keep.mem_plan.remat_peak_bytes) // 2
    monkeypatch.setenv("ROC_MEM_PLAN", "auto")
    monkeypatch.setenv("ROC_MEM_BUDGET", str(budget))
    tr_auto = build_audit_trainer(spec)
    assert tr_auto.config.mem_plan == "auto"
    assert tr_auto.mem_plan.any_remat(), tr_auto.mem_plan.summary()
    assert tr_auto.mem_plan.feasible
    assert tr_auto.mem_plan.predicted_peak_bytes <= budget
    loss_keep = _one_epoch_loss(tr_keep)
    loss_auto = _one_epoch_loss(tr_auto)
    assert abs(loss_keep - loss_auto) <= 1e-3, (loss_keep, loss_auto)


def test_remat_mode_preserves_loss_spmd(monkeypatch):
    """All-REMAT on the sharded trainer: same loss as the default plan."""
    spec = AuditSpec("gcn", 2, "matmul", "halo")
    loss_keep = _one_epoch_loss(build_audit_trainer(spec))
    monkeypatch.setenv("ROC_MEM_PLAN", "remat")
    tr = build_audit_trainer(spec)
    assert all(d != KEEP for d in tr.mem_plan.decisions)
    assert abs(loss_keep - _one_epoch_loss(tr)) <= 1e-3


def test_zero_retraces_with_active_plan(monkeypatch):
    """With a plan active: 3 epochs + a same-cut reshard re-trace nothing
    (the plan key participates in the step cache, so the cached callables
    survive the reshard)."""
    monkeypatch.setenv("ROC_MEM_PLAN", "remat")
    spec = AuditSpec("gcn", 2, "matmul", "halo")
    tr = build_audit_trainer(spec)
    tr.config.num_epochs = 3
    with RetraceGuard(warmup=1) as g:
        tr.train(print_fn=lambda *a, **k: None)
        assert g.counts["train_step"] >= 1
        snap = g.snapshot()
        step_ids = (id(tr._train_step), id(tr._eval_step))
        tr.reshard(tr.part.bounds)
        assert (id(tr._train_step), id(tr._eval_step)) == step_ids
        g.arm()
        tr.run_epoch()
        tr.evaluate()
        g.assert_no_new_traces(snap)


# -- a deep model with a far input: GCNII, 18 closed layers -----------------

GCNII_LAYERS = [12] + [8] * 16 + [3]
GCNII_ROWS, GCNII_EDGES = 1000, 6000


def _gcnii_estimate():
    model = build_model("gcnii", GCNII_LAYERS, 0.5)
    fixed = fixed_bytes_for(model, GCNII_ROWS, GCNII_LAYERS[0],
                            GCNII_LAYERS[-1], GCNII_EDGES)
    return model, estimate_model(model, GCNII_ROWS, GCNII_EDGES,
                                 fixed_bytes=fixed)


@pytest.mark.parametrize("kept", [0, 1, 5, 11, 16])
def test_auto_fits_a_deep_gcnii_into_a_budget_for_k_layers(kept):
    """At a budget that admits ``kept`` of the 16 GCNII layers beside
    every pinned boundary, H0 and one layer's transient, `auto` (the exact
    DP: 18 <= DP_MAX_LAYERS) returns a feasible plan that keeps exactly
    that many of them."""
    from roc_tpu.memory.planner import DP_MAX_LAYERS
    model, est = _gcnii_estimate()
    assert len(est.layers) == model.num_layers == 18 <= DP_MAX_LAYERS
    inner = est.layers[1]
    assert all(l.bytes_saved == inner.bytes_saved
               and l.bytes_pinned == inner.bytes_pinned == l.bytes_boundary
               for l in est.layers[1:17])
    floor = predict_peak(est, [REMAT] * 18)
    # the two dense layers' own tagged extras first: they are the cheapest
    dense = sum(l.bytes_saved - l.bytes_pinned
                for l in (est.layers[0], est.layers[17]))
    budget = floor + dense + kept * (inner.bytes_saved - inner.bytes_pinned)
    plan = plan_memory(est, mode="auto", budget_bytes=budget)
    assert plan.planner == "dp" and plan.feasible
    assert predict_peak(est, plan.decisions) <= budget
    assert plan.predicted_peak_bytes == predict_peak(est, plan.decisions)
    assert sum(d == KEEP for d in plan.decisions[1:17]) == kept
    # H0 is pinned: layer 0's boundary and nothing else of the model is
    # read beyond the next layer
    assert model.far_outputs() == {3: 0}
    assert est.layers[0].bytes_pinned == est.layers[0].bytes_boundary


@pytest.mark.parametrize("mode", ["auto", "remat"])
def test_every_plan_saves_h0_and_no_segment_recomputes_layer_0(mode):
    """The names an active plan holds from forward to backward include
    H0's producer whatever layer 0's verdict (it is an input of every
    later segment), and every layer's boundary."""
    from roc_tpu.memory import saved_names
    model, est = _gcnii_estimate()
    budget = (predict_peak(est, [REMAT] * 18) + 2 * est.layers[1].bytes_saved
              if mode == "auto" else 0)
    plan = plan_memory(est, mode=mode, budget_bytes=budget)
    assert plan.any_remat() and plan.decisions[0] != KEEP
    names = saved_names(model, plan)
    h0 = model.ops[2]
    assert h0.kind == "activation" and h0.out == 3
    assert h0.attrs["ckpt"] in names
    boundaries = [op.attrs["ckpt"] for op in model.ops
                  if op.attrs.get("ckpt_boundary")]
    assert len(boundaries) == 18 and set(boundaries) <= set(names)
    # a REMAT layer keeps nothing else
    for i, verdict in enumerate(plan.decisions):
        inner = [op.attrs["ckpt"] for op in model.ops
                 if op.attrs["layer"] == i and op.kind == "aggregate"]
        assert all((n in names) == (verdict == KEEP) for n in inner)
    # every GCNII segment takes H0 in; none makes it again
    for layer, indices, ins, outs in model.layer_segments()[1:17]:
        assert 3 in ins and 3 not in outs


def _gcnii_trainer(mode, budget=""):
    from roc_tpu.graph import datasets
    from roc_tpu.train.config import Config
    from roc_tpu.train.driver import Trainer
    ds = datasets.synthetic("t", 300, 3.0, GCNII_LAYERS[0], GCNII_LAYERS[-1],
                            n_train=60, n_val=60, n_test=60, seed=41)
    cfg = Config(layers=GCNII_LAYERS, model="gcnii", num_epochs=1,
                 dropout_rate=0.5, eval_every=10**9, mem_plan=mode,
                 mem_budget=budget, learning_rate=0.01, weight_decay=5e-4)
    return Trainer(cfg, ds, build_model("gcnii", GCNII_LAYERS, 0.5))


def test_keep_auto_and_remat_give_one_loss_and_one_set_of_gradients():
    """Remat changes no arithmetic: from one key the three plans' steps
    give the same loss bit for bit and gradients equal to float32 rounding
    (a recomputed fusion may associate a product another way)."""
    import jax

    from roc_tpu.train.driver import make_gctx
    out = {}
    for mode, budget in (("keep", ""), ("auto", "600k"), ("remat", "")):
        tr = _gcnii_trainer(mode, budget)
        if mode == "auto":      # some layers kept, some not
            kinds = set(tr.mem_plan.decisions)
            assert KEEP in kinds and len(kinds) > 1, tr.mem_plan.summary()
        n = tr.num_nodes

        @jax.jit
        def step(params, x, labels, mask, gdata, key, loss_fn=tr._loss_fn()):
            return jax.value_and_grad(loss_fn)(
                params, x, labels, mask, make_gctx(gdata, n), key=key,
                train=True)

        out[mode] = jax.device_get(step(
            tr.params, tr.x, tr.labels, tr.mask, tr.gdata,
            jax.random.PRNGKey(5)))
    loss, grads = out["keep"]
    for mode in ("auto", "remat"):
        assert out[mode][0] == loss, mode
        for name, g in grads.items():
            scale = float(np.abs(g).max())
            assert scale > 0, name
            assert float(np.abs(out[mode][1][name] - g).max()) \
                <= 1e-5 * scale, (mode, name)


def test_a_recomputed_sweep_is_told_from_a_first_one_by_its_pass():
    """In the compiled train step of an all-REMAT plan every GCNII layer's
    aggregate runs three times under its own `roc.` scope: forward,
    recomputed (pass "remat", read off `rematted_computation`) and
    transposed (pass "bwd"); all-KEEP has no "remat" instruction."""
    found = {}
    for mode in ("keep", "remat"):
        tr = _gcnii_trainer(mode)
        tr.run_epoch()
        scopes_ = tr.device_scopes()["train"].values()
        found[mode] = {(op, pass_) for op, pass_, _ in scopes_
                       if op and op.endswith("_aggregate")}
    ops = {op for op, _ in found["keep"]}
    assert len(ops) == 16
    assert found["keep"] == {(op, p) for op in ops for p in ("fwd", "bwd")}
    assert found["remat"] == {(op, p) for op in ops
                              for p in ("fwd", "remat", "bwd")}


@pytest.mark.parametrize("mode", ["keep", "auto", "remat"])
def test_announce_sets_the_four_plan_gauges_under_every_mode(mode):
    from roc_tpu import obs
    from roc_tpu.memory import saved_bytes
    tr = _gcnii_trainer(mode, "600k" if mode == "auto" else "")
    tr._metrics = obs.MetricsRegistry()
    try:
        tr.announce()
        gauges = {name: value for (name, labels), value
                  in tr._metrics.gauges.items() if not labels}
    finally:
        tr._metrics = None
    plan, est = tr.mem_plan, tr.mem_estimate
    assert gauges["mem_plan_remat_layers"] == plan.num_remat()
    assert gauges["mem_plan_kept_layers"] == 18 - plan.num_remat()
    assert gauges["mem_plan_predicted_peak_bytes"] \
        == plan.predicted_peak_bytes > 0
    assert gauges["mem_plan_saved_bytes"] \
        == saved_bytes(est, plan.decisions) > 0
    pinned = sum(l.bytes_pinned for l in est.layers)
    if mode == "keep":      # unwrapped: priced at every op's output
        assert gauges["mem_plan_remat_layers"] == 0
        assert gauges["mem_plan_saved_bytes"] == est.total_full_bytes()
    elif mode == "remat":   # the pinned boundaries and H0, nothing else
        assert gauges["mem_plan_kept_layers"] == 0
        assert gauges["mem_plan_saved_bytes"] == pinned
    else:
        assert pinned < gauges["mem_plan_saved_bytes"] \
            < est.total_full_bytes()


def test_trainstats_carry_peak_hbm(monkeypatch):
    monkeypatch.setenv("ROC_MEM_PLAN", "remat")
    tr = build_audit_trainer(AuditSpec("gcn", 1, "matmul", "single"))
    tr.config.num_epochs = 2
    stats = tr.train(print_fn=lambda *a, **k: None)
    assert len(stats.peak_hbm_bytes) == 2
    # CPU has no allocator stats; the estimator prediction stands in
    assert stats.peak_hbm_source in ("measured", "estimated")
    assert all(b > 0 for b in stats.peak_hbm_bytes)


def test_measured_peak_is_in_use_plus_reserved(monkeypatch):
    """What the v5e reported for gcn-reddit.regular (PERF.md, PR 22,
    finding 4): 1.29 GB of live arrays and 4.39 GB of program scratch
    reserved beside them.  The program's counter reports their sum on the
    fullest device, as the benchmark's `peak_hbm_gib` does."""
    import jax

    from roc_tpu import memory

    class Dev:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    chip = {"peak_bytes_in_use": 1287452160,
            "peak_bytes_reserved": 4394631168}
    devs = [Dev({"peak_bytes_in_use": 1000, "peak_bytes_reserved": 2000}),
            Dev(chip)]
    monkeypatch.setattr(jax, "local_devices", lambda: devs)
    assert memory.measured_peak_bytes() == 5682083328
    # a backend that reports no reserve: in use alone, as before
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [Dev({"peak_bytes_in_use": 7})])
    assert memory.measured_peak_bytes() == 7
    monkeypatch.setattr(jax, "local_devices", lambda: [Dev(None)])
    assert memory.measured_peak_bytes() is None


# -- CPU acceptance criterion (products shape) ----------------------------

def test_products_shape_peak_reduction():
    """3-layer GCN at the products/4-shard shape: the DP finds >= 30%
    predicted peak reduction at <= 15% predicted step-time cost."""
    layers = [100, 256, 256, 47]
    rows, edges = 612_258, 31_250_000
    model = build_model("gcn", layers)
    fixed = fixed_bytes_for(model, rows, layers[0], layers[-1], edges)
    est = estimate_model(model, rows, edges, fixed_bytes=fixed)
    plan = plan_memory(est, mode="auto", budget_bytes=8 << 30)
    assert plan.any_remat() and plan.feasible
    reduction = 1.0 - plan.predicted_peak_bytes / plan.keep_peak_bytes
    cost = plan.predicted_step_s / plan.keep_step_s - 1.0
    assert reduction >= 0.30, plan.summary()
    assert cost <= 0.15, plan.summary()


# -- roclint: remat rule --------------------------------------------------

_REMAT_SRC = ("import jax\ndef f(g, x):\n"
              "    return jax.checkpoint(g)(x)\n")


def test_lint_flags_raw_checkpoint():
    for call in ("jax.checkpoint", "jax.remat",
                 "jax.ad_checkpoint.checkpoint"):
        src = _REMAT_SRC.replace("jax.checkpoint", call)
        fs = lint.lint_source(src, "<remat>")
        assert any(f.rule == "remat" for f in fs), (call, fs)


def test_lint_remat_waiver_and_exemption():
    waived = _REMAT_SRC.replace(
        "(x)\n", "(x)  # roclint: allow(remat)\n")
    assert lint.lint_source(waived, "<remat>") == []
    # the one sanctioned call site
    path = os.path.join("roc_tpu", "memory", "policy.py")
    assert [f for f in lint.lint_source(_REMAT_SRC, path)
            if f.rule == "remat"] == []
    # ...but only that exact suffix
    other = os.path.join("roc_tpu", "memory", "policy_py", "x.py")
    assert any(f.rule == "remat"
               for f in lint.lint_source(_REMAT_SRC, other))


def test_lint_remat_clean_near_misses():
    for src in (
            # the train checkpoint subsystem's save/load is unrelated
            "from roc_tpu.train import checkpoint\n"
            "checkpoint.save('p', {}, {}, 0, 0.1)\n",
            # method spellings on other objects are not the jax entry
            "def f(tr, x):\n    tr.save_checkpoint('p')\n"
            "    return tr.checkpoint_every + x\n",
    ):
        assert [f for f in lint.lint_source(src, "<clean>")
                if f.rule == "remat"] == [], src
