"""The five set-up metrics that read the program's set-up spans (PR 23):
each data file against a hand-made run, and one cold and one warm traced
rehearsal run on the binned backend with the program's plan cache on, whose
`run.json` lists every span the files name that a CPU run can reach."""

import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import layer_metrics
from benchmark import manifest as mf

SPANS = {
    "geometry_s": ["choose_geometry"],
    "plan_key_s": ["plan_key"],
    "plan_fetch_s": ["plan_cache_load", "plan_native_build",
                     "plan_numpy_build", "plan_cache_save"],
    "plan_place_s": ["plan_to_device"],
    "place_s": ["place_data", "init_params", "mem_plan", "step_build"],
}
BENCH = mf.load(os.path.join(mf.ROOT, "BENCHMARK.json"))
# the native builder takes over above 2**20 edges only: no rehearsal graph
UNREACHED_ON_CPU = {"plan_native_build"}


def _spec(name):
    return mf.layer_metric_spec(BENCH, mf.cell(BENCH, "gcn-reddit.skewed"),
                                name)


def _run_with(spans):
    return layer_metrics.TracedRun(None, [], spans, {}, {}, "TPU v5 lite")


@pytest.mark.parametrize("name", sorted(SPANS))
def test_metric_sums_its_spans(name):
    spec = _spec(name)
    assert spec["spans"] == SPANS[name]
    assert (spec["kind"], spec["source"], spec["reduce"], spec["unit"],
            spec["better"], spec["moves"]) == (
        "program_span", "host_span", "seconds", "s", "lower", "setup_s")
    mine = {s: [0.25 * (i + 1), 0.5] for i, s in enumerate(SPANS[name])}
    others = {s: [100.0] for other, ss in SPANS.items() if other != name
              for s in ss}
    want = sum(sum(v) for v in mine.values())
    assert layer_metrics.read(_run_with({**mine, **others, "plan_build": [
        7.0]}), spec) == pytest.approx(want)
    # any one of its spans is enough: a warm run has no build and no save
    first = SPANS[name][0]
    assert layer_metrics.read(_run_with({first: [1.5]}), spec) == 1.5


@pytest.mark.parametrize("name", sorted(SPANS))
def test_metric_is_left_out_without_its_spans(name):
    """The parent commit has none of the spans: the reader returns None
    and the harness leaves the metric out of the line."""
    assert layer_metrics.read(_run_with(
        {"plan_build": [12.2], "halo_build": [0.1], "epoch": [1.9]}),
        _spec(name)) is None


@pytest.mark.parametrize("name", sorted(SPANS))
def test_metric_is_reported_in_the_skewed_cell_alone(name):
    entry = next(e for e in BENCH["per_layer"] if e["name"] == name)
    assert entry["workloads"] == ["gcn-reddit.skewed"]
    assert entry["source"] == "program_span" and entry["moves"] == "setup_s"
    layers = {e["layer"] for e in BENCH["per_layer"][:10]}
    assert entry["layer"] in layers         # an accepted layer's own string


def test_no_span_is_summed_twice():
    """The spans are siblings and each belongs to one metric, so the five
    add up to a share of the `trainer` phase; `plan_build`, which
    `plan_build_s` reads, encloses some of them and is in none."""
    named = [s for ss in SPANS.values() for s in ss]
    assert len(named) == len(set(named))
    assert not set(named) & set(_spec("plan_build_s")["spans"])


@pytest.fixture(scope="module")
def binned_rehearsal(tmp_path_factory):
    """A rehearsal cell on the binned backend (new files beside a copy of
    the rehearsal manifest, the five metrics appended for it), run traced
    twice in one plan-cache directory: cold, then warm."""
    tmp = tmp_path_factory.mktemp("setup_spans")
    m = copy.deepcopy(mf.load(os.path.join(
        mf.ROOT, "benchmark", "rehearsal", "manifest.json")))
    for sub in ("configs", "traffic", "plans"):
        (tmp / sub).mkdir()
    conf = mf.load(os.path.join(mf.ROOT, m["configs"][0]["file"]))
    conf.update(name="tiny-gcn-binned", aggregate_backend="binned")
    (tmp / "configs" / "tiny-gcn-binned.json").write_text(json.dumps(conf))
    (tmp / "traffic" / "tiny-power.json").write_text(json.dumps(
        {"nodes": 600, "avg_degree": 5, "degree_law": "power", "skew": 2.0,
         "communities": 3, "structure_seed": 4,
         "splits": {"train": 300, "val": 100, "test": 100}, "job": {}}))
    m["configs"].append({"name": "tiny-gcn-binned", "source": "a test",
                         "file": str(tmp / "configs" / "tiny-gcn-binned.json"),
                         "reduced": [], "why": "a test"})
    cell = "tiny-gcn-binned.power"
    m["workloads"].append({"name": cell, "config": "tiny-gcn-binned",
                           "traffic": "tiny-power", "chips": 1,
                           "why": "a test"})
    for e in BENCH["per_layer"]:
        if e["name"] in SPANS:
            m["per_layer"].append(dict(e, workloads=[cell]))
    (tmp / "manifest.json").write_text(json.dumps(m))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", ROC_PLAN_CACHE_DIR=str(tmp / "plans"),
               ROC_PLAN_CACHE_MIN_EDGES="0")
    env.pop("ROC_PLAN_CACHE", None)
    runs = {}
    for which, seed in (("cold", "3000000021"), ("warm", "3000000022")):
        out = tmp / which
        p = subprocess.run(
            [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
             "--workload", cell, "--seed", seed, "--seconds", "1", "--trace",
             "1", "--rehearse-cpu", "--manifest", str(tmp / "manifest.json"),
             "--out", str(out)], cwd=mf.ROOT, env=env, capture_output=True,
            text=True, timeout=300)
        assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
        with open(out / "run.json", encoding="utf-8") as f:
            runs[which] = (json.loads(p.stdout.strip().splitlines()[-1]),
                           json.load(f))
    return runs


@pytest.mark.parametrize("which", ["cold", "warm"])
def test_rehearsal_run_lists_the_spans_and_reports_the_five(
        binned_rehearsal, which):
    result, info = binned_rehearsal[which]
    assert info["program"]["backend"] == "binned"
    spans = info["traced"]["spans"]         # name -> [count, seconds]
    every = {s for ss in SPANS.values() for s in ss}
    not_on_this_road = {"plan_cache_load"} if which == "cold" else {
        "plan_numpy_build", "plan_cache_save"}
    assert every - UNREACHED_ON_CPU - not_on_this_road <= set(spans)
    assert not not_on_this_road & set(spans)
    assert spans["plan_build"][0] == 1
    # all five read a number on a cold and on a warm run alike ...
    got = result["metrics"]
    assert set(SPANS) <= set(got)
    for name, names in SPANS.items():
        assert got[name]["unit"] == "s"
        assert got[name]["value"] == pytest.approx(
            sum(spans[s][1] for s in names if s in spans))
    # ... and stay inside the phase they split
    assert sum(got[n]["value"] for n in SPANS) <= \
        info["setup_phases"]["trainer"]
    # in the trace the program's spans sit beside the benchmark's own
    assert {"step_args", "step_call", "peak_hbm", "check_nonfinite",
            "eval_call"} <= set(spans)
