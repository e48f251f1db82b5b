"""roc-verify tests: collective auditor, retrace guard, roclint.

Three layers of evidence, matching the subsystem's three passes:
  * the audit matrix is CLEAN against the committed budgets.json, and
    seeded mutations (a replicated input that should be parts-sharded; an
    exchange-mode flip audited against the halo budget) are flagged;
  * the retrace guard proves literal-zero retraces across steady-state
    epochs AND across a same-cut balancer reshard (the frozen-shape
    invariant as an enforced property);
  * roclint fires on positive fixture snippets, stays silent on clean
    near-misses, honors waivers, and reports zero findings on the tree.
"""

import os

import numpy as np
import pytest

from roc_tpu.analysis import (AuditSpec, audit_spec, audit_specs,
                              audit_trainer, build_audit_trainer,
                              check_invariants, compare_report,
                              load_budgets, spec_key)
from roc_tpu.analysis import lint, retrace
from roc_tpu.analysis.retrace import RetraceError, RetraceGuard

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def budgets():
    b = load_budgets()
    assert b, "budgets.json missing; run tools/roclint.py --update-budgets"
    return b


# -- collective auditor ---------------------------------------------------

def test_manifest_covers_matrix(budgets):
    assert set(budgets) == {spec_key(s) for s in audit_specs()}


@pytest.mark.parametrize("spec", audit_specs(), ids=spec_key)
def test_audit_clean_tree(spec, budgets):
    """Every model x parts x backend x exchange entry lowers to exactly
    its budgeted collectives, with no f64 and unchanged shardings.
    `audit_spec` dispatches: trainer steps for training entries, the
    serving engine's bucketed serve_step for the `serve` rows."""
    rep = audit_spec(spec, key=spec_key(spec))
    assert compare_report(rep, budgets[spec_key(spec)]) == []
    assert check_invariants(rep) == []


def test_audit_flags_replicated_input(budgets):
    """Seeded mutation: re-place x replicated (the 'dropped
    with_sharding_constraint' analog) — the entry-arg sharding signature
    diff catches it before any op count moves."""
    import jax
    spec = AuditSpec("gcn", 4, "matmul", "halo")
    tr = build_audit_trainer(spec)
    key = spec_key(spec)
    assert compare_report(audit_trainer(tr, key=key), budgets[key]) == []
    tr.x = jax.device_put(np.asarray(tr.x), tr._repl_spec)
    viol = compare_report(audit_trainer(tr, key=key), budgets[key])
    assert any("sharding" in v for v in viol), viol


def test_audit_flags_exchange_flip(budgets):
    """Seeded mutation: lower the allgather-exchange program but audit it
    against the halo budget — the halo all_to_all quota and the uninvited
    all_gather/reduce_scatter both fire."""
    spec = AuditSpec("gcn", 2, "matmul", "halo")
    tr = build_audit_trainer(spec, exchange="allgather")
    viol = compare_report(audit_trainer(tr, key=spec_key(spec)),
                          budgets[spec_key(spec)])
    assert any("all_to_all" in v for v in viol), viol
    assert any("all_gather" in v for v in viol), viol


# -- retrace guard --------------------------------------------------------

def test_retrace_guard_mechanics():
    with RetraceGuard(warmup=1) as g:
        retrace.note_trace("train_step")      # first-epoch trace: allowed
        retrace.epoch_boundary(1)             # warmup boundary -> armed
        with pytest.raises(RetraceError):
            retrace.note_trace("train_step")
    assert retrace.active() is None
    with RetraceGuard(on_violation="record") as g:
        g.arm()
        retrace.note_trace("eval_step")
        assert len(g.violations) == 1
        with pytest.raises(RetraceError):
            g.assert_clean()
    assert g.counts["eval_step"] == 1


def test_zero_retraces_across_epochs_and_reshard():
    """3-epoch run + a same-cut reshard: the step cache returns the SAME
    jitted callables and nothing re-traces."""
    spec = AuditSpec("gcn", 2, "matmul", "halo")
    tr = build_audit_trainer(spec)
    tr.config.num_epochs = 3
    with RetraceGuard(warmup=1) as g:        # raises on any 2..N retrace
        tr.train(print_fn=lambda *a, **k: None)
        assert g.counts["train_step"] >= 1
        snap = g.snapshot()
        step_ids = (id(tr._train_step), id(tr._eval_step))
        tr.reshard(tr.part.bounds)           # same cut, same shapes
        assert (id(tr._train_step), id(tr._eval_step)) == step_ids
        g.arm()
        tr.run_epoch()                       # post-reshard epoch
        tr.evaluate()
        g.assert_no_new_traces(snap)


# -- roclint --------------------------------------------------------------

_POSITIVE = {
    "host-sync": [
        "import jax\n@jax.jit\ndef f(x):\n    return x.sum().item()\n",
        "import jax\ndef inner(x):\n    return float(x)\n"
        "g = jax.jit(inner)\n",
        "import jax, numpy as np\n@jax.jit\ndef f(x):\n"
        "    return np.asarray(x) + 1\n",
        "import jax\n@jax.jit\ndef f(x):\n    return jax.device_get(x)\n",
        "import time\ndef bench(fn, x):\n    t0 = time.perf_counter()\n"
        "    fn(x).block_until_ready()\n"
        "    return time.perf_counter() - t0\n",
    ],
    "tracer-branch": [
        "import jax, jax.numpy as jnp\n@jax.jit\ndef f(x):\n"
        "    if jnp.any(x > 0):\n        return x\n    return -x\n",
    ],
    "unkeyed-rand": ["import numpy as np\ni = np.random.randint(0, 9)\n"],
    "mutable-default": ["def f(x, acc=[]):\n    acc.append(x)\n"
                        "    return acc\n"],
    "closure-capture": ["fns = []\nfor i in range(3):\n"
                        "    fns.append(lambda: i + 1)\n"],
    "unledgered-prediction": [
        # ad-hoc prediction dict key
        "row = {'predicted_step_s': 0.1, 'nodes': 4}\n",
        # measurement-shaped field emitted around the ledger
        "def f(reg, t):\n"
        "    reg.emit('epoch', measured_step_s=t)\n",
        # record_event kwarg spelling
        "def f(buf, t):\n"
        "    buf.record_event('probe', predicted_time_s=t)\n",
    ],
    "silent-swallow": [
        # error dropped on the floor: no log, no counter, no comment
        "try:\n    sync()\nexcept OSError:\n    pass\n",
        "for p in paths:\n    try:\n        load(p)\n"
        "    except Exception:\n        continue\n",
    ],
    "hand-rolled-geometry": [
        "from roc_tpu.ops.pallas.binned import Geometry\n"
        "g = Geometry(512, 2048, 128, 512, 4096)\n",
        "import roc_tpu.ops.pallas.binned as B\n"
        "plan = build(B.Geometry(sb=512, ch=2048, slot=32, rb=512,"
        " ch2=4096))\n",
    ],
}

_CLEAN = [
    # host syncs OUTSIDE jitted code / timing windows are fine
    "def log(x):\n    return x.item()\n",
    # static-python branch inside jit is fine
    "import jax\n@jax.jit\ndef f(x, mode='sum'):\n"
    "    if mode == 'sum':\n        return x.sum()\n    return x.max()\n",
    # seeded generator API is the sanctioned randomness
    "import numpy as np\nrng = np.random.default_rng(0)\n"
    "i = rng.integers(0, 9)\n",
    "def f(x, acc=None):\n    return (acc or []) + [x]\n",
    # loop var bound through a default arg: no late binding
    "fns = []\nfor i in range(3):\n    fns.append(lambda i=i: i + 1)\n",
    # long timing window (a whole epoch loop): syncs inside are the
    # workload, not the measurement artifact
    "import time\ndef run(fn, x):\n    t0 = time.perf_counter()\n"
    + "    x = fn(x)\n" * 14
    + "    x.block_until_ready()\n    return time.perf_counter() - t0\n",
    # prediction-FLAVORED names that don't match the prefix are fine, as
    # are plain emit kwargs without the predicted_/measured_ shape
    "row = {'prediction': 0.1, 'measure': 2}\n"
    "def f(reg, t):\n    reg.emit('epoch', step_s=t)\n",
    # a deliberate grid point rides the waiver (the sweep-harness idiom)
    "from roc_tpu.ops.pallas.binned import Geometry\n"
    "# roclint: allow(hand-rolled-geometry)\n"
    "g = Geometry(512, 2048, 128, 512, 4096)\n",
]


def test_lint_unledgered_prediction_obs_exempt():
    """roc_tpu/obs/ IS the ledger — the rule must not flag the sanctioned
    sink itself (mirrors the raw-timing exemption)."""
    src = "row = {'predicted_step_s': 0.1}\n"
    assert lint.lint_source(src, "roc_tpu/obs/ledger.py") == []
    assert any(f.rule == "unledgered-prediction"
               for f in lint.lint_source(src, "roc_tpu/train/manager.py"))


def test_lint_unledgered_prediction_waiver():
    src = ("stamp = {\n"
           "    # roclint: allow(unledgered-prediction)\n"
           "    'predicted_peak_bytes': 1,\n"
           "}\n")
    assert lint.lint_source(src) == []


@pytest.mark.parametrize("rule", sorted(_POSITIVE))
def test_lint_positive(rule):
    for src in _POSITIVE[rule]:
        fs = lint.lint_source(src, f"<{rule}>")
        assert any(f.rule == rule for f in fs), (rule, src, fs)


def test_lint_clean_snippets():
    for src in _CLEAN:
        assert lint.lint_source(src) == [], src


def test_lint_waiver():
    src = ("import jax\n@jax.jit\ndef f(x):\n"
           "    return x.sum().item()  # roclint: allow(host-sync)\n")
    assert lint.lint_source(src) == []
    # a waiver for a different rule does not silence it
    src2 = src.replace("allow(host-sync)", "allow(unkeyed-rand)")
    assert len(lint.lint_source(src2)) == 1


def test_lint_silent_swallow_waiver_and_exemptions():
    """A handler that actually does something is clean; a waiver with a
    rationale silences the rule; test files are exempt (fixtures
    legitimately swallow expected errors)."""
    assert lint.lint_source(
        "try:\n    sync()\nexcept OSError as e:\n    log(e)\n") == []
    waived = ("try:\n    sync()\nexcept OSError:\n"
              "    pass  # roclint: allow(silent-swallow) — best-effort\n")
    assert lint.lint_source(waived) == []
    bad = "try:\n    sync()\nexcept OSError:\n    pass\n"
    assert any(f.rule == "silent-swallow" for f in lint.lint_source(bad))
    assert lint.lint_source(bad, "tests" + os.sep + "test_x.py") == []
    assert lint.lint_source(bad, "test_x.py") == []


def test_lint_zero_false_positives_on_tree():
    paths = [os.path.join(ROOT, "roc_tpu"), os.path.join(ROOT, "tools"),
             os.path.join(ROOT, "bench.py")]
    assert lint.lint_paths(paths) == []


def test_lint_closure_capture_ignores_decorator_names():
    """A loop variable used ONLY in a decorator expression is bound at def
    time (decorators evaluate eagerly) — the pl.when(c == i) closure idiom
    in the Pallas kernels must not be flagged as late capture."""
    src = ("import pallas as pl\nfns = []\nfor ci in range(3):\n"
           "    @pl.when(c == ci)\n"
           "    def _(csz=8):\n        return csz\n"
           "    fns.append(_)\n")
    assert [f for f in lint.lint_source(src)
            if f.rule == "closure-capture"] == [], lint.lint_source(src)
    # ...but using it in the BODY still flags
    src2 = src.replace("return csz", "return ci")
    assert any(f.rule == "closure-capture" for f in lint.lint_source(src2))


# -- mosaic-align lint ----------------------------------------------------

_MOSAIC_FIXTURE = """\
import jax.experimental.pallas as pl
from jax.experimental import pallas

UNIT = 8
H = 41

def kernel(x_ref, o_ref):
    a = x_ref[pl.ds(0, 41)]              # sublane 41 % 8 != 0: flag
    b = x_ref[pl.ds(0, 3 * UNIT)]        # 24 % 8 == 0: clean
    c = x_ref[pl.ds(s, csz * UNIT)]      # runtime * aligned factor: clean
    return a, b, c

spec_bad = pl.BlockSpec((8, H), lambda i: (i, 0))        # lane 41: flag
spec_bad2 = pl.BlockSpec((12, 128), lambda i: (i, 0))    # sublane 12: flag
spec_ok = pl.BlockSpec((8, 128), lambda i: (i, 0))
spec_col = pl.BlockSpec((512, 1), lambda i: (i, 0))      # (N, 1): exempt
spec_smem = pl.BlockSpec((8, 4), lambda i: (i, 0),
                         memory_space=pltpu.SMEM)        # SMEM: exempt
spec_dyn = pl.BlockSpec((n, h), lambda i: (i, 0))        # unresolvable
"""


def test_mosaic_lint_flags_fixture():
    from roc_tpu.analysis import mosaic
    fs = mosaic.lint_source(_MOSAIC_FIXTURE, "<fixture>")
    assert len(fs) == 3, fs
    assert all(f.rule == "mosaic-align" for f in fs)
    lines = sorted(f.line for f in fs)
    # the ds(0,41) and the two bad BlockSpecs
    assert lines == [8, 13, 14], fs


def test_mosaic_lint_waiver():
    from roc_tpu.analysis import mosaic
    src = _MOSAIC_FIXTURE.replace(
        "# sublane 41 % 8 != 0: flag", "# roclint: allow(mosaic-align)")
    fs = mosaic.lint_source(src, "<fixture>")
    assert len(fs) == 2 and all(f.line > 8 for f in fs), fs


def test_mosaic_lint_clean_on_tree():
    """Zero findings on the shipped kernels — the conservative-resolution
    contract (unresolvable dims are skipped, not flagged)."""
    from roc_tpu.analysis import mosaic
    paths = [os.path.join(ROOT, "roc_tpu"), os.path.join(ROOT, "tools"),
             os.path.join(ROOT, "bench.py")]
    assert mosaic.lint_paths(paths) == []


def test_analyze_flag_parses():
    from roc_tpu.train.config import parse_args
    cfg = parse_args(["-dataset", "x", "-layers", "8-4", "-analyze"])
    assert cfg.analyze and not parse_args(["-layers", "8-4"]).analyze
