"""The two per-layer metrics that read the program's own count of its device
scopes (PR 35): `step_whiles` and `step_unscoped_share`, gauges the trainer
sets from the lowered train step when the traced run lends it a registry
(`run.program_gauges` -> `BaseTrainer._announce_attention`, the harness's
name for what is `announce()` now).  Each data
file against a hand-made run, their entries by membership, and traced
rehearsal runs on the CPU that walk the door, a one-chip GCN's included.
The rehearsal's own manifest is a file the benchmark already had and does
not list the two metrics: the runs here read a copy of it, grown by the
two entries, through `--manifest`."""

import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import layer_metrics
from benchmark import manifest as mf

BENCH = mf.load(os.path.join(mf.ROOT, "BENCHMARK.json"))
REHEARSAL = mf.load(os.path.join(mf.ROOT, "benchmark", "rehearsal",
                                 "manifest.json"))
METRICS = {"step_whiles": ("count", "peak_hbm_gib"),
           "step_unscoped_share": ("%", "epoch_s")}
# `gcn-reddit.regular` sets the same gauges (they are in its `counters`)
# and is not listed: test_benchmark_trace_reduce.py reads every per-layer
# metric of that cell from the recorded trace with two hand-made counters
CELLS = ("gcn-reddit.skewed", "gat-reddit.skewed", "gcn-products.p4",
         "tconv-reddit.skewed")


def grown_rehearsal():
    """The rehearsal's manifest with this PR's two entries appended, each
    listing the rehearsal's own cells."""
    m = copy.deepcopy(REHEARSAL)
    cells = [w["name"] for w in m["workloads"]]
    for name in METRICS:
        entry = next(e for e in BENCH["per_layer"] if e["name"] == name)
        m["per_layer"].append(dict(entry, workloads=list(cells)))
    return m


def check_entries(m):
    """What these metrics need of a manifest, by membership: cells and
    metrics that later PRs append change nothing here."""
    by_name = {e["name"]: e for e in m["per_layer"]}
    e2e = {e["name"] for e in m["end_to_end"]}
    layers = {e["layer"] for e in m["per_layer"] if e["name"] not in METRICS}
    for name, (unit, moves) in METRICS.items():
        entry = by_name[name]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["moves"]) == (unit, "lower", "program_counter", moves)
        assert moves in e2e
        assert entry["layer"] in layers     # an accepted layer's own string
        for cell in CELLS:
            assert entry in mf.metrics_for(m, "per_layer", cell), (name, cell)


def test_the_entries():
    check_entries(BENCH)
    assert mf.problems_in(BENCH) == []


def test_the_entries_hold_in_a_manifest_that_has_grown():
    m = copy.deepcopy(BENCH)
    m["configs"].append({
        "name": "dummy-sage", "source": "a test",
        "file": "benchmark/rehearsal/configs/tiny-gcn.json", "reduced": [],
        "why": "a test"})
    m["workloads"].append({
        "name": "dummy-sage.skewed", "config": "dummy-sage",
        "traffic": "tiny-skewed", "chips": 1, "why": "a test"})
    m["per_layer"].append({
        "name": "dummy_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": "epoch_s"})
    for name in METRICS:    # a later cell appends itself to the lists
        next(e for e in m["per_layer"] if e["name"] == name)[
            "workloads"].append("dummy-sage.skewed")
    assert mf.problems_in(m) == []
    check_entries(m)


def _spec(name, manifest=BENCH, cell="gcn-reddit.skewed"):
    return mf.layer_metric_spec(manifest, mf.cell(manifest, cell), name)


def _run_with(counters):
    return layer_metrics.TracedRun(None, [], {}, counters, {}, "TPU v5 lite")


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_file_reads_the_programs_gauge(name):
    spec = _spec(name)
    entry = next(e for e in BENCH["per_layer"] if e["name"] == name)
    assert (spec["kind"], spec["unit"], spec["better"], spec["moves"],
            spec["layer"]) == (entry["source"], entry["unit"],
                               entry["better"], entry["moves"],
                               entry["layer"])
    assert (spec["source"], spec["counter"], spec["reduce"]) == (
        "counter", name, "value")
    counters = {"step_whiles": 27.0, "step_unscoped_share": 0.0,
                "graph_s": 6.0}
    assert layer_metrics.read(_run_with(counters), spec) == counters[name]


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_is_left_out_where_the_program_sets_no_such_gauge(name):
    """The parent commit's trainer: the reader finds nothing, returns None,
    and the line leaves the metric out."""
    assert layer_metrics.read(_run_with({"graph_s": 6.0, "compile_s": 1.5}),
                              _spec(name)) is None


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_rehearsal_reads_the_same_file(name):
    m = grown_rehearsal()
    assert mf.problems_in(m) == []
    assert _spec(name, m, "tiny-gcn3.p4") == _spec(name)
    for w in m["workloads"]:    # its own three cells, by name
        assert name in {e["name"] for e in mf.metrics_for(
            m, "per_layer", w["name"])}
    # and the file the benchmark had is as it was: neither metric in it
    assert name not in {e["name"] for e in REHEARSAL["per_layer"]}


@pytest.mark.parametrize("cell", ["tiny-gcn.skewed", "tiny-gcn3.p4"])
def test_a_traced_rehearsal_walks_the_door(cell, tmp_path):
    """A one-chip GCN has no attention and no exchange to announce, and its
    traced run still reports both metrics: the name the harness calls is
    the base trainer's whole `announce()`.  The sharded trainer too."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    manifest = tmp_path / "manifest.json"
    manifest.write_text(json.dumps(grown_rehearsal()))
    p = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "35", "--seconds", "1", "--trace",
         "1", "--rehearse-cpu", "--manifest", str(manifest), "--out",
         str(tmp_path / "out")],
        cwd=mf.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    whiles = out["metrics"]["step_whiles"]
    assert whiles["unit"] == "count"
    assert whiles["value"] > 0 and whiles["value"] == int(whiles["value"])
    assert out["metrics"]["step_unscoped_share"] == {"value": 0.0,
                                                     "unit": "%"}
    counters = next(json.loads(ln.split("counters: ", 1)[1]) for ln in lines
                    if ln.startswith("# bench: counters: "))
    assert counters["step_whiles"] == whiles["value"]
    assert counters["step_unscoped_share"] == 0.0
