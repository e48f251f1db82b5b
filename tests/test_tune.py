"""Autotuner (roc_tpu/tune) acceptance pins — ISSUE round 12.

What this file proves, in dependency order:

  * the candidate lattice is deterministic, sorted, and admissible-only;
  * the tuned store round-trips, validates, and rejects garbage;
  * two identical CPU sweeps write BYTE-IDENTICAL tuned.json files (the
    seeded-surrogate closed-world contract);
  * ``choose_geometry`` consumes a tuned entry at the swept graphs
    (every swept shape — deterministic, so the >=90% policy bar is met
    at 100%), falls back to the analytic model off-key and for an
    unswept variant, and the tuned pick changes NOTHING numerically
    (output parity vs the analytic plan and segment_sum);
  * swapping a tuned geometry in under the same content key costs ZERO
    retraces (the plan is a pytree with static schedule fields — a
    rebuilt identical plan must hit the jit cache);
  * plan-cache hygiene both orders: plan cached first then a tuned
    entry appears, and tuned entry first then a stale explicit geometry
    — both warn once and build the tuned winner; tuned_ok=False is the
    forced-A/B escape that builds exactly what was asked;
  * refit recovers the generating surrogate constants within 5% from
    the sweep's own trial records (TrialRecord path) AND from raw
    ledger-style dicts (JSONL path), and update_budgets refuses to
    commit an interpret table as rates (measured_calibration contract);
  * surrogate.analytic_seconds is a faithful mirror of binned's
    _binned_cost_model at default constants.

The sweep runs ONCE per session (module fixture) at two small synthetic
shapes; everything downstream shares its entries/trials.
"""

import json
import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import roc_tpu.ops.pallas.binned as B  # noqa: E402
from roc_tpu.tune import lattice, refit, search, store  # noqa: E402
from roc_tpu.tune import surrogate as S  # noqa: E402

# two CI-sized synthetic graphs = the policy test's "grid"
_SHAPE_SPECS = [("mega_shard_scaled", 1024, 8192, 2),
                ("tiny", 512, 4096, 3)]


@pytest.fixture(autouse=True)
def _fresh_store_cache():
    """The store memoizes per (path, mtime) and warns once per key;
    tests monkeypatch env paths, so both caches must reset around each
    test or a prior test's warn-once eats this test's warning."""
    store.clear_cache()
    yield
    store.clear_cache()


@pytest.fixture(scope="module")
def swept():
    """One surrogate sweep over the test grid, shared by every
    consumer: (shapes, entries, trials)."""
    shapes = [search.synth_shape(*spec) for spec in _SHAPE_SPECS]
    entries, trials = search.sweep(shapes, seed=0)
    return shapes, entries, trials


def _winner(shapes, entries, i=0, vkey="fp32"):
    sh = shapes[i]
    gkey = store.graph_key(sh.edge_src, sh.edge_dst, sh.num_rows,
                           sh.table_rows)
    return sh, B.Geometry(*entries[gkey][vkey]["geom"])


# ---------------------------------------------------------------- lattice

def test_lattice_deterministic_sorted_admissible():
    a = lattice.candidate_lattice()
    b = lattice.candidate_lattice()
    assert a == b
    assert [c.label for c in a] == sorted(c.label for c in a)
    assert len({c.label for c in a}) == len(a)      # labels are keys
    for c in a:
        c.geom.check()                               # admissible only
        assert B._vmem_bytes(c.geom) <= B._VMEM_NOMINAL_CAP
    # bf16 storage adds the 16-row-unit flat family
    bf = lattice.candidate_lattice("bf16")
    assert any(c.geom.unit == 16 for c in bf)
    assert not any(c.geom.unit == 16 for c in a)


def test_refit_probes_admissible_and_identify_every_rate(swept):
    """The designed experiment must exercise every rate refit solves,
    with its counts pulled apart enough for least squares: the probes'
    column-scaled count matrix at the CI shape has full rank and a
    modest condition number."""
    probes = search.refit_probes()
    assert len(probes) >= 5
    assert any(cfg.geom.flat for cfg in probes)      # the flat columns
    _, _, trials = swept
    rows = [tr.terms for tr in trials if tr.stage == "probe"]
    assert len(rows) == len(probes) * len(_SHAPE_SPECS)
    A = np.asarray([[r[k] for k in refit.RATE_NAMES] for r in rows],
                   dtype=np.float64)
    assert (A.max(axis=0) > 0).all(), refit.RATE_NAMES
    A = A / A.max(axis=0)
    assert np.linalg.matrix_rank(A) == len(refit.RATE_NAMES)
    assert np.linalg.cond(A) < 200, np.linalg.cond(A)


# ------------------------------------------------------------------ store

def test_store_roundtrip_and_validation(tmp_path):
    p = str(tmp_path / "tuned.json")
    doc = {"version": store.VERSION, "interpret": True, "seed": 0,
           "entries": {"rows=8|table_rows=8|edges=1|sha=00": {
               "fp32": {"geom": list(B.GEOM_MID), "knobs": {},
                        "modeled_s": 1e-3, "trial_s": 1.1e-3,
                        "source": "surrogate"}}}}
    assert store.validate_store(doc) == []
    store.save_store(p, doc)
    assert store.load_store(p) == doc
    # negatives: each corruption must be named, and save must refuse
    bad = json.loads(json.dumps(doc))
    bad["version"] = 99
    assert store.validate_store(bad)
    bad = json.loads(json.dumps(doc))
    bad["entries"]["rows=8|table_rows=8|edges=1|sha=00"]["fp32"]["geom"] \
        = [1, 2]
    assert store.validate_store(bad)
    with pytest.raises(ValueError):
        store.save_store(p, bad)
    bad = json.loads(json.dumps(doc))
    bad["entries"]["rows=8|table_rows=8|edges=1|sha=00"]["fp32"][
        "source"] = "vibes"
    assert store.validate_store(bad)
    assert store.validate_store("not a dict")
    # unreadable/absent files read as "no store", never raise
    assert store.load_store(str(tmp_path / "absent.json")) is None
    (tmp_path / "torn.json").write_text("{")
    assert store.load_store(str(tmp_path / "torn.json")) is None


def test_tuned_store_path_env(tmp_path, monkeypatch):
    monkeypatch.setenv("ROC_PLAN_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("ROC_NO_TUNED", raising=False)
    monkeypatch.delenv("ROC_TUNED_PATH", raising=False)
    assert store.tuned_store_path() == str(tmp_path / "tuned.json")
    monkeypatch.setenv("ROC_TUNED_PATH", str(tmp_path / "elsewhere.json"))
    assert store.tuned_store_path() == str(tmp_path / "elsewhere.json")
    monkeypatch.setenv("ROC_NO_TUNED", "1")
    assert store.tuned_store_path() == ""
    monkeypatch.delenv("ROC_NO_TUNED")
    monkeypatch.delenv("ROC_TUNED_PATH")
    monkeypatch.setenv("ROC_PLAN_CACHE", "0")
    assert store.tuned_store_path() == ""


# ------------------------------------------------------------ determinism

def test_sweep_byte_identical(tmp_path, swept):
    """Same seed, same shapes -> byte-identical tuned.json (acceptance:
    the CI surrogate is a closed deterministic world)."""
    shapes, entries, _ = swept
    entries2, _ = search.sweep(
        [search.synth_shape(*spec) for spec in _SHAPE_SPECS], seed=0)
    pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    store.merge_entries(pa, entries, interpret=True, seed=0)
    store.merge_entries(pb, entries2, interpret=True, seed=0)
    ba = open(pa, "rb").read()
    assert ba == open(pb, "rb").read()
    assert len(ba) > 0
    # and a different seed draws different surrogate noise: the
    # recorded trial timings must move even if the winner holds
    entries3, _ = search.sweep(
        [search.synth_shape(*_SHAPE_SPECS[0])], seed=7)
    (gkey,) = entries3
    assert entries3[gkey]["fp32"]["trial_s"] \
        != entries[gkey]["fp32"]["trial_s"]


# ----------------------------------------------------------- tuned policy

def test_choose_geometry_tuned_policy_grid(tmp_path, monkeypatch, swept):
    """With tuned.json present, choose_geometry returns the stored
    winner at EVERY swept shape (>= the 90% policy bar) and provably
    stays analytic off-key and for the unswept bf16 variant."""
    shapes, entries, _ = swept
    p = str(tmp_path / "tuned.json")
    store.merge_entries(p, entries, interpret=True, seed=0)
    monkeypatch.setenv("ROC_TUNED_PATH", p)
    monkeypatch.delenv("ROC_NO_TUNED", raising=False)
    hits = 0
    for i in range(len(shapes)):
        sh, win = _winner(shapes, entries, i)
        g, t = B.choose_geometry(sh.edge_src, sh.edge_dst, sh.num_rows,
                                 sh.table_rows)
        assert np.isfinite(t) and t > 0
        hits += tuple(g) == tuple(win)
    assert hits / len(shapes) >= 0.9, (hits, len(shapes))
    # off-key graph / unswept variant: the tuned tier must NOT engage
    monkeypatch.setattr(
        B, "_priced_tuned",
        lambda *a, **k: pytest.fail("tuned tier engaged off-key"))
    other = search.synth_shape("other", 2048, 4096, 7)
    B.choose_geometry(other.edge_src, other.edge_dst, other.num_rows,
                      other.table_rows)
    sh = shapes[0]
    B.choose_geometry(sh.edge_src, sh.edge_dst, sh.num_rows,
                      sh.table_rows, storage_dtype="bf16")
    # explicit candidate lists (forced A/Bs) never consult the tier
    g, _ = B.choose_geometry(sh.edge_src, sh.edge_dst, sh.num_rows,
                             sh.table_rows, candidates=[B.GEOM_MID],
                             force=True)
    assert tuple(g) == tuple(B.GEOM_MID)
    # kill switch
    monkeypatch.setenv("ROC_NO_TUNED", "1")
    B.choose_geometry(sh.edge_src, sh.edge_dst, sh.num_rows,
                      sh.table_rows)


def test_tuned_parity_and_zero_retrace(tmp_path, monkeypatch, swept):
    """The tuned pick is a SCHEDULE choice, not a numeric one: its plan
    reproduces segment_sum exactly as the analytic plan does.  And a
    rebuild under the same content key — the reshard that swaps the
    tuned geometry in — costs zero retraces: the plan is a pytree with
    static schedule fields, so an identical rebuilt plan must hit the
    jit cache."""
    shapes, entries, _ = swept
    sh, win = _winner(shapes, entries)
    p = str(tmp_path / "tuned.json")
    store.merge_entries(p, entries, interpret=True, seed=0)
    monkeypatch.setenv("ROC_TUNED_PATH", p)
    monkeypatch.delenv("ROC_NO_TUNED", raising=False)

    n, h = sh.num_rows, 16
    x = jnp.asarray(np.random.default_rng(0)
                    .standard_normal((n, h), dtype=np.float32))
    ref = jax.ops.segment_sum(x[sh.edge_src], jnp.asarray(sh.edge_dst),
                              num_segments=n)

    plan = B.build_binned_plan(sh.edge_src, sh.edge_dst, n, n)
    assert tuple(plan.geom) == tuple(win)

    traces = []

    def _step(v, pl):
        traces.append(1)
        return B.run_binned(v, pl, True, precision="exact")

    step = jax.jit(_step)
    out = step(x, plan)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)
    assert len(traces) == 1
    # reshard: rebuild under the same content key -> identical plan,
    # zero new traces
    plan2 = B.build_binned_plan(sh.edge_src, sh.edge_dst, n, n)
    assert tuple(plan2.geom) == tuple(win)
    out2 = step(x, plan2)
    assert len(traces) == 1, "tuned-geometry rebuild retraced"
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out))
    # parity against the analytic pick (tuned tier off)
    monkeypatch.setenv("ROC_NO_TUNED", "1")
    plan_an = B.build_binned_plan(sh.edge_src, sh.edge_dst, n, n)
    out_an = jax.jit(
        lambda v: B.run_binned(v, plan_an, True, precision="exact"))(x)
    np.testing.assert_allclose(np.asarray(out_an), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


# ------------------------------------------------------ plan-cache hygiene

def _stale_preset(win):
    for g in (B.GEOM_MID, B.GEOM_SPARSE, B.GEOM_WIDE):
        if tuple(g) != tuple(win):
            return g
    raise AssertionError("no preset differs from the winner")


def test_plan_cache_hygiene_plan_first(tmp_path, monkeypatch, swept):
    """Order A: a plan is cached BEFORE the tuned entry exists.  When
    the store appears, the next build of the stale geometry warns once
    and builds (and caches) the tuned winner instead."""
    shapes, entries, _ = swept
    sh, win = _winner(shapes, entries)
    stale = _stale_preset(win)
    monkeypatch.setenv("ROC_PLAN_CACHE_DIR", str(tmp_path / "plans"))
    monkeypatch.setenv("ROC_PLAN_CACHE_MIN_EDGES", "0")
    monkeypatch.setenv("ROC_TUNED_PATH", str(tmp_path / "tuned.json"))
    monkeypatch.setenv("ROC_NO_TUNED", "1")   # pre-tuner era
    p0 = B.build_binned_plan(sh.edge_src, sh.edge_dst, sh.num_rows,
                             sh.table_rows, geom=stale)
    assert tuple(p0.geom) == tuple(stale)
    # the tuner runs; the store appears
    monkeypatch.delenv("ROC_NO_TUNED")
    store.merge_entries(str(tmp_path / "tuned.json"), entries,
                        interpret=True, seed=0)
    with pytest.warns(UserWarning, match="disagrees with the tuned"):
        p1 = B.build_binned_plan(sh.edge_src, sh.edge_dst, sh.num_rows,
                                 sh.table_rows, geom=stale)
    assert tuple(p1.geom) == tuple(win)
    # warn-once: the second stale request swaps silently
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        p2 = B.build_binned_plan(sh.edge_src, sh.edge_dst, sh.num_rows,
                                 sh.table_rows, geom=stale)
    assert tuple(p2.geom) == tuple(win)
    assert not [w for w in rec if "disagrees" in str(w.message)]


def test_plan_cache_hygiene_tuned_first(tmp_path, monkeypatch, swept):
    """Order B: the tuned entry exists BEFORE any plan is cached.  An
    explicit stale geometry yields (with the warning); tuned_ok=False
    is the forced-A/B escape and builds exactly what was asked; a
    request that already matches the winner is silent."""
    shapes, entries, _ = swept
    sh, win = _winner(shapes, entries)
    stale = _stale_preset(win)
    monkeypatch.setenv("ROC_PLAN_CACHE_DIR", str(tmp_path / "plans"))
    monkeypatch.setenv("ROC_PLAN_CACHE_MIN_EDGES", "0")
    monkeypatch.setenv("ROC_TUNED_PATH", str(tmp_path / "tuned.json"))
    monkeypatch.delenv("ROC_NO_TUNED", raising=False)
    store.merge_entries(str(tmp_path / "tuned.json"), entries,
                        interpret=True, seed=0)
    with pytest.warns(UserWarning, match="disagrees with the tuned"):
        p1 = B.build_binned_plan(sh.edge_src, sh.edge_dst, sh.num_rows,
                                 sh.table_rows, geom=stale)
    assert tuple(p1.geom) == tuple(win)
    # forced A/B escape
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        pf = B.build_binned_plan(sh.edge_src, sh.edge_dst, sh.num_rows,
                                 sh.table_rows, geom=stale,
                                 tuned_ok=False)
    assert tuple(pf.geom) == tuple(stale)
    assert not [w for w in rec if "disagrees" in str(w.message)]
    # agreeing request: no warning, no swap needed
    store.clear_cache()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        pw = B.build_binned_plan(sh.edge_src, sh.edge_dst, sh.num_rows,
                                 sh.table_rows, geom=win)
    assert tuple(pw.geom) == tuple(win)
    assert not [w for w in rec if "disagrees" in str(w.message)]


# ------------------------------------------------------------------ refit

def test_refit_recovers_constants(swept):
    """Acceptance: the refit's rates land within 5% of the generating
    surrogate constants on the CI sweep's own records."""
    _, _, trials = swept
    out = refit.refit_rates(trials)
    assert out["n_agg"] > 0 and out["n_mm"] > 0
    for name, ratio in out["vs_constants"].items():
        assert abs(ratio - 1.0) <= 0.05, (name, ratio, out)


def test_refit_from_ledger_dicts(swept):
    """The JSONL path: raw ledger measurement dicts (model + schedule
    extras) refit to the same rates as the TrialRecords they mirror."""
    _, _, trials = swept
    dicts = []
    for tr in trials:
        model = {"trial": "tune_trial", "confirm": "tune_confirm",
                 "probe": "tune_probe",
                 "matmul": "tune_trial"}[tr.stage]
        dicts.append({"model": model, "value": tr.trial_s,
                      "steps": tr.steps, **tr.terms,
                      "flat": int(tr.geom[7]) if len(tr.geom) > 7 else 0,
                      "default_knobs": tr.default_knobs,
                      "matmul": tr.stage == "matmul",
                      "stage": tr.stage, "variant": tr.variant,
                      "shape": tr.shape})
    a = refit.refit_rates(trials)
    b = refit.refit_rates(dicts)
    for k in (*refit.RATE_NAMES, "mm_chunk_s"):
        if a[k] is None:
            assert b[k] is None
        else:
            np.testing.assert_allclose(b[k], a[k], rtol=1e-9)
    # records without schedule facts are skipped, not crashed on
    assert refit.refit_rates([{"model": "geom_time", "value": 1.0}]
                             )["n_agg"] == 0


def test_update_budgets_refuses_interpret(tmp_path, swept):
    """The measured_calibration contract: interpret timings never
    become rate tables."""
    _, _, trials = swept
    table = refit.to_measured_table(trials, interpret=True,
                                    platform="cpu")
    with pytest.raises(SystemExit):
        refit.update_budgets(table, path=str(tmp_path / "budgets.json"))
    # the device path commits and measured_calibration-style readers
    # can see the rows
    dev = refit.to_measured_table(trials, interpret=False,
                                  platform="tpu")
    p = str(tmp_path / "budgets.json")
    refit.update_budgets(dev, path=p)
    with open(p, encoding="utf-8") as f:
        doc = json.load(f)
    assert doc["measured"]["interpret"] is False
    assert doc["measured"]["shapes"]


def test_measure_seconds_refuses_cpu(swept):
    """Hardware trials refuse to run on interpret backends — the same
    refusal measured_calibration enforces on its input tables."""
    shapes, _, _ = swept
    sh = shapes[0]
    cfg = lattice.KernelConfig(geom=B.GEOM_MID)
    with pytest.raises(SystemExit, match="refusing"):
        S.measure_seconds(cfg, sh.edge_src, sh.edge_dst, sh.num_rows,
                          sh.table_rows)


# -------------------------------------------------------------- surrogate

def test_analytic_seconds_mirrors_cost_model(monkeypatch):
    """surrogate.analytic_seconds at default constants must equal
    binned._binned_cost_model (measured tables off) — the property that
    makes the refit's recovered rates commensurable with the shipped
    constants."""
    monkeypatch.setenv("ROC_NO_MEASURED_CAL", "1")
    for geom in (B.GEOM_MID, B.GEOM_SPARSE, B.GEOM_FLAT,
                 B.GEOM_FLAT_SPARSE, B.GEOM_WIDE):
        for padded, s1, s2 in ((1 << 16, 40, 20), (1 << 20, 700, 350)):
            for copies in (None, padded // 24):
                np.testing.assert_allclose(
                    S.analytic_seconds(padded, geom, s1, s2,
                                       copies=copies),
                    B._binned_cost_model(padded, geom, steps1=s1,
                                         steps2=s2, copies=copies),
                    rtol=1e-12, err_msg=str(tuple(geom)))


def test_noise_is_deterministic_and_bounded():
    e1 = S.noise_eps(0, "trial", "some-label")
    e2 = S.noise_eps(0, "trial", "some-label")
    assert e1 == e2
    assert abs(e1) <= S.NOISE
    assert S.noise_eps(1, "trial", "some-label") != e1
    assert S.noise_eps(0, "confirm", "some-label") != e1
