"""The device scopes (roc_tpu/obs/scopes.py): one `roc.` name an IR op, a
pass and a part on every heavy op of the lowered steps and every `while` of
the compiled ones, for each model family the tests build; the parts held to
what the trainer announces; the scopes as metadata only; the compile-cache
trap; the `-profile` report; and no lowering on the training path."""

import contextlib
import gzip
import json
import os
import re
import shutil

import jax
import pytest

from roc_tpu import obs
from roc_tpu.analysis import hlo_audit
from roc_tpu.graph import datasets
from roc_tpu.models import build_model
from roc_tpu.obs import report, scopes
from roc_tpu.ops import aggregate as agg
from roc_tpu.ops import edge as em
from roc_tpu.train.config import Config
from roc_tpu.train.driver import make_trainer

# family -> (model, backend, parts, layers, heads)
FAMILIES = {
    "gcn-binned": ("gcn", "binned", 1, [8, 8, 4], 1),
    "gcn-matmul": ("gcn", "matmul", 1, [8, 8, 4], 1),
    "gcn-xla": ("gcn", "xla", 1, [8, 8, 4], 1),
    "gat": ("gat", "matmul", 1, [8, 4, 4], 8),        # K = 8, then K = 1
    "tconv": ("tconv", "matmul", 1, [8, 8, 8, 4], 2),
    "gatv2": ("gatv2", "matmul", 1, [8, 4, 4], 8),    # K = 8, then K = 1
    "gcn3-p4": ("gcn", "matmul", 4, [8, 8, 8, 4], 1),  # tiny-gcn3.p4's kind
}
ATTENTION = ("gat", "tconv", "gatv2")


def _dataset():
    return datasets.synthetic("t", 200, 4.0, 8, 4, n_train=30, n_val=30,
                              n_test=30, seed=3)


def _trainer(family, **kw):
    model, backend, parts, layers, heads = FAMILIES[family]
    cfg = Config(layers=layers, num_epochs=1, eval_every=10**9,
                 dropout_rate=0.3, model=model, heads=heads,
                 aggregate_backend=backend, weight_decay=0.0,
                 num_parts=parts, **kw)
    return make_trainer(cfg, _dataset(), build_model(
        model, cfg.layers, cfg.dropout_rate, heads=cfg.heads))


@pytest.fixture(scope="module")
def small_steps():
    """Several steps a scan on a 200-node graph, so that the compiler keeps
    every scan a `while` (it unrolls a loop of one trip)."""
    names = {em: ("_PLAN_CB_BLOCKS", "_PLAN_CB_SUM", "_PLAN_CB_MAX"),
             agg: ("_MM_CB",)}
    was = {(mod, n): getattr(mod, n) for mod, ns in names.items()
           for n in ns}
    for mod, n in was:
        setattr(mod, n, 8)
    yield
    for (mod, n), v in was.items():
        setattr(mod, n, v)


@pytest.fixture(scope="module")
def built(small_steps):
    """family -> (trainer, lowered steps, compiled train step's text),
    made on first use."""
    cache = {}

    def get(family):
        if family not in cache:
            tr = _trainer(family)
            lowered = hlo_audit.lower_steps(tr)
            cache[family] = (tr, lowered,
                             scopes.compile_uncached(lowered["train"]))
        return cache[family]
    return get


_WHILE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = .*?\swhile\(", re.M)


def _whiles(hlo_text):
    """{name: (op, pass, part)} of the module's `while` instructions."""
    scope_of = scopes.describe_module(hlo_text)["scopes"]
    return {name: scope_of[name] for name in _WHILE.findall(hlo_text)}


# -- (i) every heavy op and every while has its name ------------------------

@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_no_heavy_op_of_the_lowered_steps_is_unscoped(family, built):
    _, lowered, _ = built(family)
    for name in ("train", "eval"):
        counts = scopes.lowered_counts(lowered[name])
        assert counts["heavy"] > 0, (name, counts)
        assert counts["heavy_unscoped"] == 0, (name, counts)
        assert 0 <= counts["whiles"] <= counts["heavy"]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_while_of_the_compiled_step_has_op_pass_and_part(family,
                                                               built):
    tr, _, text = built(family)
    whiles = _whiles(text)
    assert whiles, "the compiler kept no scan: the fixture's steps"
    kinds = {scopes.op_scope(i, op.kind): op.kind
             for i, op in enumerate(tr.model.ops)}
    bare = {}
    for name, (op, pass_, part) in whiles.items():
        assert op is not None and op.startswith("roc."), name
        assert pass_ in scopes.PASSES, (name, pass_)
        if kinds.get(op) == "gat" and part is None:
            bare[op] = bare.get(op, 0) + 1
        else:
            assert part is None or part in scopes.PARTS, (name, part)
    # an attention op's scans are each one part of its rule (the mask's
    # random bits loop under `edge`); the one loop outside the rule folds
    # the op's dropout slot into the step's key, in Model._apply_op
    assert all(n == 1 for n in bare.values()), bare
    if family in ATTENTION:
        parts = {(p, part) for op, p, part in whiles.values()
                 if kinds.get(op) == "gat"}
        assert {("fwd", "bcast"), ("bwd", "src")} <= parts
    if family == "gatv2":
        assert {("fwd", "max"), ("fwd", "norm"), ("fwd", "u")} <= parts
    if family == "gat":
        assert {("bwd", "de"), ("bwd", "dq"), ("bwd", "bcast")} <= parts
    if family in ("gat", "tconv"):
        # the score, its max, the normaliser and u are ONE scan forward
        # (gat's source half of the score rides u's rows: no lane gather)
        assert ("fwd", "su") in parts and not parts & {
            ("fwd", "score"), ("fwd", "max"), ("fwd", "norm"), ("fwd", "u"),
            ("fwd", "lanes")}
    if family == "gatv2":
        assert ("fwd", "score") in parts        # both rows, forward
    if family in ("tconv", "gatv2"):
        # de, dz's broadcast and dq (gatv2: dxr and da) are ONE scan of the
        # backward
        assert ("bwd", "dedq") in parts and not parts & {
            ("bwd", "de"), ("bwd", "dq"), ("bwd", "bcast")}
    if family == "gcn-matmul":
        assert {(p, part) for _, p, part in whiles.values()} >= {
            ("fwd", "mm"), ("bwd", "mm")}
    if family == "gcn3-p4":
        ops = {op for op, _, _ in
               scopes.describe_module(text)["scopes"].values()}
        assert {"roc.exchange", "roc.allreduce", "roc.rng", "roc.adam",
                "roc.loss"} <= ops


def test_the_binned_kernels_are_parts_of_their_pass(built):
    _, _, text = built("gcn-binned")
    found = set(scopes.describe_module(text)["scopes"].values())
    for pass_ in ("fwd", "bwd"):
        parts = {part for op, p, part in found
                 if p == pass_ and op and op.endswith("_aggregate")}
        assert "p2" in parts and parts & {"p1", "p1_flat"}, (pass_, parts)


def test_the_evaluation_step_has_the_metrics_scope(built):
    tr, lowered, _ = built("gcn-xla")
    ops = {op for op, _, _ in scopes.describe_module(
        scopes.compile_uncached(lowered["eval"]))["scopes"].values()}
    assert "roc.metrics" in ops and "roc.loss" not in ops
    assert set(tr.device_scopes()) == {"train", "eval"}


# -- (ii) the parts come apart, held to what the trainer announces ----------

@pytest.mark.parametrize("family", ATTENTION)
def test_src_scans_of_the_compiled_step_are_what_the_trainer_says(family,
                                                                  built):
    tr, _, text = built(family)
    info = tr.attention_info()
    by_op = {}
    for op, pass_, part in _whiles(text).values():
        if (pass_, part) == ("bwd", "src"):
            by_op[op] = by_op.get(op, 0) + 1
    assert sum(by_op.values()) == info["src_scans"]
    if family == "gat":     # one layer each side of gat_src_scans' rule
        heads = [op.attrs["heads"] for op in tr.model.ops
                 if op.kind == "gat"]
        assert heads == [8, 1] and sorted(by_op.values()) == [1, 2]
        assert [em.gat_src_scans(k) for k in heads] == [2, 1]
    else:
        ops = sum(op.kind == "gat" for op in tr.model.ops)
        assert set(by_op.values()) == {1} and len(by_op) == ops


def test_fwd_scans_of_the_compiled_gat_step_are_what_the_trainer_says(
        built):
    """`fwd_scans`: gat's forward walks the plans twice an op, `su` (the
    score, its max, the normaliser and u) and the max's `bcast` for the
    backward's e; the mask's random bits (a loop on the CPU, under `edge`)
    and the op's dropout slot (no part) walk none."""
    tr, _, text = built("gat")
    info = tr.attention_info()
    kinds = {scopes.op_scope(i, op.kind): op.kind
             for i, op in enumerate(tr.model.ops)}
    fwd = [part for op, pass_, part in _whiles(text).values()
           if kinds.get(op) == "gat" and pass_ == "fwd"
           and part not in (None, "edge")]
    assert sorted(fwd) == ["bcast", "bcast", "su", "su"]
    assert len(fwd) == info["fwd_scans"] == 4


def test_row_scans_of_the_compiled_gatv2_step(built):
    """The dynamic score's rule gathers node rows in `row_scans` scans, 4
    an op: `score` and `u` forward, `dedq` (xl again) and `src` ([xr | du])
    backward; one of them a layer over the src-keyed plan."""
    tr, _, text = built("gatv2")
    info = tr.attention_info()
    rows = [s for s in _whiles(text).values()
            if s[2] in ("score", "u", "de", "dq", "dedq", "src")]
    assert len(rows) == info["row_scans"] == 8
    assert sorted({s[2] for s in rows}) == ["dedq", "score", "src", "u"]
    assert sum(s[2] == "src" for s in rows) == info["src_scans"] == 2


def test_row_passes_of_the_compiled_tconv_step(built):
    """Node tables read by row, 6 an op, in `row_scans` scans, 3 an op: two
    side by side in each, for `su` and `dedq` ([k | v]) and for the src
    scan ([q | du])."""
    tr, _, text = built("tconv")
    info = tr.attention_info()
    rows = [s for s in _whiles(text).values()
            if s[2] in ("score", "u", "de", "dq", "dedq", "su", "src")]
    assert len(rows) == info["row_scans"] == 9
    assert sorted({s[2] for s in rows}) == ["dedq", "src", "su"]
    assert len(rows) + sum(s[2] in ("dedq", "src", "su") for s in rows) \
        == info["row_passes"]


_TRIPS = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = .*?\swhile\(.*"
                    r'"known_trip_count":\{"n":"(\d+)"\}', re.M)


@pytest.mark.parametrize("family", ATTENTION)
def test_short_scans_are_the_row_sums_of_the_shortened_trip_count(
        family, built, monkeypatch):
    """`short_scans`: the train step's row-gathering sums (`u`, `src`)
    whose `while` runs more trips than the cap's step gives.  With a block
    budget of 8 chunks at 128 lanes (the cap of `small_steps`), rows of
    256 lanes step at 4 and of 384 at 2: a tconv of hidden 2 x 64 (src 256
    lanes) then 2 x 80 (src 320) has two (its u rides the `su` scan, which
    steps as the block-landing scans do, and so does gat's); gat's rows are
    128 lanes or narrower and keep the cap."""
    from roc_tpu.ops.pallas.segment_sum import EB
    monkeypatch.setattr(em, "_PLAN_SUM_BLOCK_BYTES", 8 * EB * 128 * 4)
    if family == "tconv":
        cfg = Config(layers=[8, 128, 160, 4], num_epochs=1,
                     eval_every=10**9, dropout_rate=0.3, model="tconv",
                     heads=2, aggregate_backend="matmul", weight_decay=0.0)
        tr = make_trainer(cfg, _dataset(), build_model(
            "tconv", cfg.layers, cfg.dropout_rate, heads=cfg.heads))
        text = scopes.compile_uncached(hlo_audit.lower_steps(tr)["train"])
    else:
        tr, _, text = built(family)
    plans = tr.gdata.gat_plans
    cap = {"u": -(-plans.dst_obi.shape[0] // 8),
           "src": -(-plans.src_obi.shape[0] // 8)}
    trips = dict(_TRIPS.findall(text))
    rows = [(part, int(trips[name])) for name, (_, pass_, part)
            in _whiles(text).items()
            if (pass_, part) in (("fwd", "u"), ("bwd", "src"))]
    assert {p for p, _ in rows} == ({"u", "src"} if family == "gatv2"
                                    else {"src"})
    assert all(n >= cap[p] for p, n in rows)
    short = sum(n > cap[p] for p, n in rows)
    assert short == tr.attention_info()["short_scans"]
    assert short == (2 if family == "tconv" else 0)


# -- (iii) metadata only ----------------------------------------------------

@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_lowered_text_is_the_null_contexts_byte_for_byte(
        family, built, monkeypatch):
    _, lowered, _ = built(family)
    monkeypatch.setattr(scopes, "scope",
                        lambda *names: contextlib.nullcontext())
    twin = hlo_audit.lower_steps(_trainer(family))
    for name in ("train", "eval"):
        assert lowered[name].as_text() == twin[name].as_text(), name
        assert "roc." not in twin[name].as_text(debug_info=True)
    assert "roc." in lowered["train"].as_text(debug_info=True)
    assert "roc." not in lowered["train"].as_text()


# -- (iv) the compile-cache trap --------------------------------------------

@pytest.fixture
def own_cache(tmp_path):
    """JAX's persistent cache in a directory of this test's, every program
    kept; as it was afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path / "jaxcache"), 0.0, -1)):
        jax.config.update(k, v)
    cc.reset_cache()
    yield tmp_path / "jaxcache"
    for k, v in was.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _instruction_names(hlo_text):
    return set(scopes.describe_module(hlo_text)["scopes"])


def test_a_cached_executable_has_its_first_compilers_names(own_cache,
                                                           monkeypatch):
    """A checkout whose cache the parent filled: the executable the step
    loads carries no `roc.` scope, `device_scopes()` compiles for itself
    and has them, under the very instruction names the cache serves."""
    with monkeypatch.context() as mp:
        mp.setattr(scopes, "scope", lambda *names: contextlib.nullcontext())
        twin = hlo_audit.lower_steps(_trainer("gcn-matmul"))["train"]
        assert "roc." not in twin.compile().as_text()   # fills the cache
    assert os.listdir(own_cache)
    tr = _trainer("gcn-matmul")
    served = hlo_audit.lower_steps(tr)["train"].compile().as_text()
    assert "roc." not in served, "the cache did not serve the twin's"
    mine = tr.device_scopes()["train"]
    assert {op for op, _, _ in mine.values()} >= {
        "roc.03_aggregate", "roc.adam", "roc.loss"}
    assert set(mine) == _instruction_names(served)
    # and the cache is read again afterwards, as before
    assert "roc." not in hlo_audit.lower_steps(tr)[
        "train"].compile().as_text()


# -- (v) the one reader of an op_name ---------------------------------------

@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/jvp(roc.07_gat)/fwd/u/while/body/closed_call/mul",
     ("roc.07_gat", "fwd", "u")),
    ("jit(train_step)/transpose(jvp(roc.07_gat))/bwd/src/while",
     ("roc.07_gat", "bwd", "src")),
    # JAX's own transpose of an op without a rule
    ("jit(train_step)/transpose(jvp(roc.01_linear))/transpose",
     ("roc.01_linear", "bwd", None)),
    ("jit(train_step)/jvp(roc.01_linear)/dot_general",
     ("roc.01_linear", "fwd", None)),
    # a primitive called like a part is none without an explicit pass
    ("jit(eval_step)/roc.05_activation/max", ("roc.05_activation", "fwd",
                                              None)),
    ("jit(train_step)/jvp(roc.03_aggregate)/fwd/while/body/p1/"
     "jit(_p1_run)/pallas_call", ("roc.03_aggregate", "fwd", "p1")),
    # a rematted forward, and the backward under the same checkpoint
    ("jit(s)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "roc.02_gat/fwd/max/while", ("roc.02_gat", "remat", "max")),
    ("jit(s)/transpose(jvp(jvp()))/checkpoint/roc.02_gat/bwd/de/while",
     ("roc.02_gat", "bwd", "de")),
    # the exchange inside an aggregate op: the innermost `roc.` scope
    ("jit(step_shard)/shard_map/jvp(roc.03_aggregate)/roc.exchange/wire/"
     "all_to_all", ("roc.exchange", "fwd", "wire")),
    ("jit(step_shard)/shard_map/transpose(jvp(roc.03_aggregate))/"
     "roc.exchange/down/gather", ("roc.exchange", "bwd", "down")),
    ("jit(step_shard)/shard_map/roc.allreduce/psum",
     ("roc.allreduce", "fwd", None)),
    ("jit(train_step)/mul", (None, "fwd", None)),
    ("", (None, "fwd", None)),
])
def test_parse(op_name, want):
    assert scopes.parse(op_name) == want


def test_an_instruction_without_metadata_takes_its_whiles_scope():
    text = """HloModule jit_f, entry_computation_layout={()->f32[]}

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %a = f32[4]{0} add(%p, %p), metadata={op_name="jit(f)/roc.00_linear/add"}
  ROOT %m = f32[4]{0} multiply(%a, %a), metadata={op_name="jit(f)/roc.01_gat/fwd/edge/mul"}
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %made = f32[4]{0} copy(%x)
  %fusion.1 = f32[4]{0} fusion(%made), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/roc.01_gat/fwd/edge/mul"}
  ROOT %r = (s32[], f32[4]{0}) tuple(%i, %fusion.1)
}

%cond (t: (s32[], f32[4])) -> pred[] {
  %t.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt = pred[] compare(%i.1, %n), direction=LT
}

ENTRY %main () -> f32[] {
  %c = f32[4]{0} constant({1, 2, 3, 4})
  %while.3 = (s32[], f32[4]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(f)/roc.01_gat/fwd/max/while"}
  ROOT %out = f32[] reduce(%gte, %zero), dimensions={0}, to_apply=%sum, metadata={op_name="jit(f)/roc.loss/reduce_sum"}
}
"""
    described = scopes.describe_module(text)
    got = described["scopes"]
    assert described["module"] == scopes.module_name(text) == "jit_f"
    assert got["while.3"] == ("roc.01_gat", "fwd", "max")
    assert got["made"] == got["lt"] == ("roc.01_gat", "fwd", "max")
    assert got["fusion.1"] == ("roc.01_gat", "fwd", "edge")
    assert got["c"] == (None, "fwd", None) and got["out"][0] == "roc.loss"
    assert "a" not in got and "m" not in got    # fused: no events of a trace
    assert described["mixed_fusions"] == 1


# -- (vi) the report ---------------------------------------------------------

def test_the_profile_report_of_a_tiny_tconv_run(built, tmp_path):
    tr, _, _ = built("tconv")
    cfg = tr.config
    profile = str(tmp_path / "profile")
    lines = []
    cfg.num_epochs = 1
    tr.train(print_fn=lines.append)                 # warm up
    cfg.profile_dir, cfg.profile_epochs = profile, "0:3"
    cfg.num_epochs, cfg.eval_every = 3, tr.epoch + 1
    try:
        tr.train(print_fn=lines.append)
    finally:
        cfg.profile_dir, cfg.eval_every = "", 10**9
    assert any(f"device scopes written to {profile}" in ln for ln in lines)
    with open(os.path.join(profile, report.SCOPES_FILE)) as f:
        record = json.load(f)
    assert set(record["programs"]) == {"train", "eval"}
    assert record["jax"] == jax.__version__
    assert [o["kind"] for o in record["ops"]] == [
        op.kind for op in tr.model.ops]
    chips, runs, annotations = report.read_device_events(
        report.find_xplane(profile))
    train = record["programs"]["train"]
    assert runs[0][train["module"]] == 3
    assert runs[0][record["programs"]["eval"]["module"]] == 1
    times = report.scope_times(chips[0], train)
    busy = sum(e[4] for line in chips[0] for e in line
               if e[1] == train["module"])
    assert busy > 0 and sum(times.values()) == pytest.approx(busy, rel=1e-9)
    # every part of the rule has a line of its own, a layer and pass
    got = {(p, part) for op, p, part in times if op == "roc.05_gat"}
    assert {("fwd", "su"), ("fwd", "norm"), ("fwd", "edge"),
            ("fwd", "bcast"), ("bwd", "dedq"), ("bwd", "src"),
            ("bwd", "edge")} <= got
    assert not got & {("fwd", "score"), ("fwd", "max"), ("fwd", "u"),
                      ("bwd", "de"), ("bwd", "dq"), ("bwd", "bcast")}
    # no instruction the trace names is missing from the map
    assert all(e[0] in train["scopes"] for line in chips[0] for e in line
               if e[1] == train["module"])
    gaps = report.idle_gaps(chips[0], annotations)
    # a 3 ms CPU epoch leaves gaps between two spans among the longest too
    names = [name for _, name in gaps]
    assert sum(n.startswith("roc.") for n in names) >= 3, names
    assert all(n.startswith("roc.") or n == "outside roc.*" for n in names)
    text = report.device_report(profile)
    assert "program train (jit_train_step), chip 0" in text
    assert "a epoch over 3 epoch(s)" in text and "program eval" in text
    assert re.search(r"roc\.05_gat +L1 +bwd +src ", text)
    from roc_tpu.obs.__main__ import main
    assert main(["report", "-profile", profile]) == 0
    assert main(["report", "-profile", str(tmp_path / "none")]) == 2


def test_the_reader_on_the_recorded_v5e_trace(tmp_path):
    """The chip's own format: the ops line's self times add up to its busy
    time, the module line names the programs, and a map by instruction
    name splits the train step's time."""
    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "benchmark", "data",
                       "gcn-reddit.regular.v5e.xplane.pb.gz")
    with gzip.open(src, "rb") as f, open(d / "chip.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    chips, runs, annotations = report.read_device_events(
        report.find_xplane(str(tmp_path)))
    assert list(chips) == [0] and len(chips[0]) == 1
    assert runs[0]["jit_train_step"] == 3 and runs[0]["jit_eval_step"] == 1
    events = chips[0][0]
    busy = sum(b - a for a, b in report._busy_intervals(events))
    assert sum(e[4] for e in events) == pytest.approx(busy, rel=1e-6)
    assert annotations == []            # recorded before PR 23's bridge
    names = {e[0] for e in events if e[1] == "jit_train_step"}
    assert "_p1_flat_run.12" in names and "while.4" in names
    program = {"module": "jit_train_step", "scopes": {
        n: ("roc.03_aggregate", "fwd", "p1_flat") for n in names
        if n.startswith("_p1_flat_run")}}
    times = report.scope_times(chips[0], program)
    assert set(times) == {("roc.03_aggregate", "fwd", "p1_flat"),
                          report.NO_SCOPE}
    assert 0.3 < times[("roc.03_aggregate", "fwd", "p1_flat")] / sum(
        times.values()) < 0.9
    gaps = report.idle_gaps(chips[0], annotations)
    assert len(gaps) == 5 and {n for _, n in gaps} == {"outside roc.*"}


# -- (vii) nothing on the training path lowers ------------------------------

def test_only_a_trainer_with_a_registry_lowers_its_step(monkeypatch,
                                                        tmp_path):
    calls = []
    real = hlo_audit.lower_train_step
    monkeypatch.setattr(hlo_audit, "lower_train_step",
                        lambda tr: calls.append(tr) or real(tr))
    tr = _trainer("gcn-xla")
    tr.config.num_epochs = 2
    tr.train(print_fn=lambda line: None)
    tr.announce()                       # no registry: nothing to tell
    assert calls == []
    # the benchmark's traced run lends one after its window, and asks by
    # the private name the announcements had before `announce()`
    registry = tr._metrics = obs.MetricsRegistry()
    try:
        tr._announce_attention()
    finally:
        tr._metrics = None
    assert calls == [tr]
    gauges = {name: v for (name, labels), v in registry.gauges.items()
              if not labels}
    # the memory plan's four verdict gauges come with every announcement
    # (all-KEEP here: two layers kept, none recomputed)
    plan = {k: v for k, v in gauges.items() if k.startswith("mem_plan_")}
    assert sorted(plan) == ["mem_plan_kept_layers",
                            "mem_plan_predicted_peak_bytes",
                            "mem_plan_remat_layers", "mem_plan_saved_bytes"]
    assert (plan["mem_plan_kept_layers"], plan["mem_plan_remat_layers"]) \
        == (2, 0)
    assert {k: v for k, v in gauges.items() if k not in plan} == {
        "step_unscoped_share": 0.0,
        "step_whiles": scopes.lowered_counts(real(tr))["whiles"]}
    assert gauges["step_whiles"] > 0
    # under -obs: once, when train() first ends (at start-up it would
    # trace the step ahead of its first call)
    was = obs.enabled()
    try:
        under_obs = _trainer("gcn-xla", obs=True, obs_dir=str(tmp_path))
        assert calls == [tr]
        under_obs.config.num_epochs = 2
        under_obs.train(print_fn=lambda line: None)
        assert calls == [tr, under_obs]
        assert ("step_whiles", ()) in under_obs._metrics.gauges
        with open(tmp_path / "metrics.prom") as f:
            assert "roc_step_unscoped_share 0" in f.read()
        under_obs.train(print_fn=lambda line: None)
        assert calls == [tr, under_obs]
    finally:
        obs.enable(was)
        obs.get_tracer().clear()


def test_the_sharded_trainer_announces_its_exchange_once(capsys):
    """`announce()` is the exchange, then the base trainer's; the harness's
    private name for the latter says nothing of the exchange again."""
    tr = _trainer("gcn3-p4")
    assert capsys.readouterr().err.count("# exchange:") == 1
    tr._announce_attention()
    assert "# exchange:" not in capsys.readouterr().err
    tr.announce()
    assert capsys.readouterr().err.count("# exchange:") == 1


def test_a_failed_scopes_compile_leaves_the_run_and_its_checkpoint(
        monkeypatch, tmp_path):
    """`-profile` with a checkpoint path: the map is written after the
    checkpoint, and whatever its compile raises is one printed line."""
    def refuse(lowered):
        raise RuntimeError("compiler_options refused")
    monkeypatch.setattr(scopes, "compile_uncached", refuse)
    ckpt = tmp_path / "ckpt.npz"
    tr = _trainer("gcn-xla", profile_dir=str(tmp_path / "prof"),
                  profile_epochs="1:2", checkpoint_path=str(ckpt))
    tr.config.num_epochs = 4
    lines = []
    stats = tr.train(print_fn=lines.append)
    assert stats.epochs == 4 and os.path.exists(ckpt)
    said = [ln for ln in lines if ln.startswith("# device scopes")]
    assert len(said) == 1 and "not written" in said[0]
    assert "RuntimeError: compiler_options refused" in said[0]
    assert not os.path.exists(tmp_path / "prof" / "roc_scopes.json")


def test_scopes_imports_no_jax():
    import subprocess
    import sys
    code = ("import sys; import roc_tpu.obs.scopes as s; "
            "assert s.parse('a/roc.x/fwd/u') == ('roc.x', 'fwd', 'u'); "
            "assert 'jax' not in sys.modules, 'jax imported'")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert p.returncode == 0, p.stderr[-800:]
