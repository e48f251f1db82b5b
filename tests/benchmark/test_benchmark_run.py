"""Every cell's code path end to end on the tiny rehearsal recipes: one
process per run, as the driver starts it, on (virtual) CPU devices."""

import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest as mf

REHEARSAL = mf.load(os.path.join(mf.ROOT, "benchmark", "rehearsal",
                                 "manifest.json"))
CELLS = [w["name"] for w in REHEARSAL["workloads"]]
CHECKS_TRUE_ON_CPU = {
    "one_part_per_device", "no_compile_in_window", "no_retrace_in_window",
    "losses_finite", "loss_fell", "no_epoch_failed",
    "logits_match_reference_initial", "logits_match_reference_final"}


def _run(args, tmp_path, manifest=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    argv = [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
            "--seconds", "1", "--rehearse-cpu", "--out", str(tmp_path / "out")
            ] + args
    if manifest:
        argv += ["--manifest", manifest]
    p = subprocess.run(argv, cwd=mf.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    checks = next(json.loads(ln.split("checks: ", 1)[1]) for ln in lines
                  if ln.startswith("# bench: checks: "))
    return json.loads(lines[-1]), checks, lines


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_run_reports_the_end_to_end_metrics(cell, tmp_path):
    out, checks, lines = _run(["--workload", cell, "--seed", "11",
                               "--trace", "0"], tmp_path)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device"]
    want = {e["name"]: e["unit"]
            for e in mf.metrics_for(REHEARSAL, "end_to_end", cell)}
    assert {k: v["unit"] for k, v in out["metrics"].items()} == want
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    chips = mf.cell(REHEARSAL, cell)["chips"]
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == chips
    # a CPU never reports correct; every other check holds
    assert out["correct"] is False and not checks["tpu_with_the_cells_chips"]
    assert {k for k, v in checks.items() if v} == CHECKS_TRUE_ON_CPU
    assert out["failed"] == 0
    # whole cycles of eval_every epochs
    assert out["attempted"] > 0 and out["attempted"] % 5 == 0
    assert out["metrics"]["epoch_s"]["value"] > 0
    assert out["metrics"]["edges_per_s_per_chip"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert any(ln.startswith("# bench: graph: ") for ln in lines)
    assert any(ln.startswith("# bench: program: ") for ln in lines)
    with open(tmp_path / "out" / "run.json", encoding="utf-8") as f:
        info = json.load(f)
    assert info["graph"]["in_degree_max"] >= info["graph"]["in_degree_min"]
    assert info["result"] == out
    if chips == 4:
        shards = info["program"]["shards"]
        assert shards["parts"] == 4 and shards["halo_rows_per_peer"] > 0
        assert shards["padded_max_tax"] >= 0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_the_per_layer_metrics(cell, tmp_path):
    out, checks, _ = _run(["--workload", cell, "--seed", "12",
                           "--trace", "1"], tmp_path)
    assert list(out) == ["correct", "attempted", "failed", "breakdown",
                         "metrics", "device"]
    allowed = {e["name"]: e["unit"]
               for e in mf.metrics_for(REHEARSAL, "per_layer", cell)}
    got = {k: v["unit"] for k, v in out["metrics"].items()}
    assert got.items() <= allowed.items()
    # the readers that need no chip all found something
    assert {"graph_s", "plan_build_s", "compile_s", "host_gap_ms",
            "dense_ms", "device_idle_share"} <= set(got)
    chips = mf.cell(REHEARSAL, cell)["chips"]
    assert ("exchange_ms" in got) == (chips == 4)
    assert out["attempted"] == 3 and out["failed"] == 0
    dev = out["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    bd = out["breakdown"]
    assert 1 <= len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s >= 0 for n, s in bd["device_ops"])
    assert all(n.startswith("bench.") or n == "outside bench.*"
               for n, _ in bd["idle_gaps"])
    assert {k for k, v in checks.items() if v} == CHECKS_TRUE_ON_CPU
    # the trace is not left behind unless asked for
    assert not os.path.exists(tmp_path / "out" / "trace")


def test_a_dummy_cell_runs_from_new_files_alone(tmp_path):
    """A configuration, a recipe (with a job option), a cell and a layer
    metric, all as new files beside a copy of the rehearsal manifest's
    entries: the harness runs the cell with no edit to a file that is
    there."""
    m = copy.deepcopy(REHEARSAL)
    for sub in ("configs", "traffic", "layer_metrics"):
        (tmp_path / sub).mkdir()
    conf = mf.load(os.path.join(mf.ROOT, m["configs"][0]["file"]))
    conf.update(name="dummy-gcn4", model="gcn", layers=[8, 8, 8, 3])
    (tmp_path / "configs" / "dummy-gcn4.json").write_text(json.dumps(conf))
    (tmp_path / "traffic" / "dummy-ring.json").write_text(json.dumps(
        {"nodes": 400, "avg_degree": 3, "inter": "ring", "structure_seed": 9,
         "splits": {"train": 200, "val": 50, "test": 50},
         "job": {"eval_every": 2}}))
    (tmp_path / "layer_metrics" / "all_ops_ms.json").write_text(json.dumps(
        {"name": "all_ops_ms", "unit": "ms", "better": "lower",
         "layer": "device", "moves": "epoch_s", "kind": "device_trace",
         "source": "device_scope", "match": ".", "reduce": "ms_per_epoch"}))
    m["configs"].append({"name": "dummy-gcn4", "source": "a test", "file": str(
        tmp_path / "configs" / "dummy-gcn4.json"), "reduced": [],
        "why": "a test"})
    m["workloads"].append({"name": "dummy-gcn4.ring", "config": "dummy-gcn4",
                           "traffic": "dummy-ring", "chips": 1,
                           "why": "a test"})
    m["per_layer"].append({"name": "all_ops_ms", "unit": "ms",
                           "better": "lower", "source": "device_trace",
                           "layer": "device", "moves": "epoch_s",
                           "workloads": ["dummy-gcn4.ring"]})
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(m))
    out, checks, traced = _run(["--workload", "dummy-gcn4.ring", "--seed",
                                "5", "--trace", "1"], tmp_path,
                               manifest=str(path))
    assert out["metrics"]["all_ops_ms"]["value"] > 0
    assert checks["logits_match_reference_initial"]
    out, _, timed = _run(["--workload", "dummy-gcn4.ring", "--seed", "6",
                          "--trace", "0"], tmp_path, manifest=str(path))
    assert out["attempted"] % 2 == 0      # the recipe's own eval_every
    # the recipe's structure_seed: another --seed, the same graph

    def graph(lines):
        return next(ln for ln in lines if ln.startswith("# bench: graph: "))
    assert graph(traced) == graph(timed)


def test_without_a_tpu_nothing_is_printed(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--workload", "gcn-reddit.regular", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--out", str(tmp_path)],
        cwd=mf.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "needs 1 TPU chip" in p.stderr


def test_unknown_workload_is_an_error(tmp_path):
    p = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--workload", "nope", "--seed", "1", "--seconds", "1", "--trace",
         "0", "--rehearse-cpu"], cwd=mf.ROOT, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0 and "no workload 'nope'" in p.stderr


class _FakeDevice:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_peak_is_in_use_plus_reserved_on_the_fullest_chip():
    """What the v5e reported for gcn-reddit.regular (PR 22): live arrays
    1.29 GB, program scratch 4.39 GB reserved beside them."""
    from benchmark import run as bench_run
    chip = {"peak_bytes_in_use": 1287452160,
            "peak_bytes_reserved": 4394631168}
    emptier = {"peak_bytes_in_use": 1000, "peak_bytes_reserved": 2000}
    assert bench_run.peak_bytes(
        [_FakeDevice(emptier), _FakeDevice(chip)], False) == 5682083328
    # no estimate stands in for a number the device does not report
    with pytest.raises(RuntimeError, match="no peak_bytes_reserved"):
        bench_run.peak_bytes([_FakeDevice({"peak_bytes_in_use": 7})], False)
    with pytest.raises(RuntimeError, match="peak_bytes_in_use and no peak"):
        bench_run.peak_bytes([_FakeDevice(None)], False)
    assert bench_run.peak_bytes([_FakeDevice(None)], True) == 0


def test_config_takes_the_recipes_job_and_refuses_unknown_fields():
    from benchmark import run as bench_run
    conf = mf.load(os.path.join(mf.ROOT, "benchmark", "configs",
                                "gcn-reddit.json"))
    cell = {"chips": 4}
    cfg = bench_run.make_config(conf, {"job": {"reorder": "on"}}, cell, 9)
    assert (cfg.layers, cfg.num_parts, cfg.seed) == ([602, 256, 41], 4, 9)
    assert (cfg.learning_rate, cfg.weight_decay, cfg.decay_rate) == (
        0.01, 0.0001, 0.97)
    assert cfg.reorder == "on" and cfg.eval_every == 5
    assert cfg.aggregate_precision == "fast"
    assert cfg.aggregate_backend == "auto"
    with pytest.raises(ValueError, match="no Config field"):
        bench_run.make_config(conf, {"job": {"turbo": 1}}, cell, 9)


@pytest.mark.parametrize("cell", ["tiny-gcn.regular", "tiny-gcn.skewed"])
def test_grad_check_walks_on_the_cpu(cell):
    """benchmark/grad_check.py, the one-run-per-configuration comparison of
    loss and gradients at a cell's real size, on a rehearsal cell."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "grad_check.py"),
         "--workload", cell, "--seed", "3", "--rehearse-cpu"],
        cwd=mf.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["loss_rel"] < 1e-5
    assert set(out["grad_rel_fro"]) == {"linear_0", "linear_1"}
    assert max(out["grad_rel_fro"].values()) < 1e-4
    from benchmark import checks
    assert checks.GRAD_REL_FRO_TOL == 3e-3
