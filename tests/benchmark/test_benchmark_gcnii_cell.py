"""The `gcnii-reddit.skewed` cell's files: its entries by membership, the
recipe's graph against `reddit-skewed.json`'s key for key, the four new
per-layer metrics read from device instructions named as the v5e's trace
names them and from the program's gauges, and the harness walking a tiny
GCNII cell under a memory plan on the CPU from new files alone (traced and
untraced)."""

import copy
import json
import math
import os
import subprocess
import sys

import pytest

from benchmark import checks, layer_metrics, roofline, trace_reduce
from benchmark import manifest as mf

BENCH = mf.load(os.path.join(mf.ROOT, "BENCHMARK.json"))
REHEARSAL = mf.load(os.path.join(mf.ROOT, "benchmark", "rehearsal",
                                 "manifest.json"))
CELL = "gcnii-reddit.skewed"
LAYERS = [602] + [256] * 16 + [41]
NEW_METRICS = ("gcnii_agg_ms", "gcnii_agg_roofline", "gcnii_remat_layers",
               "gcnii_saved_bytes")
GENERIC = ("graph_s", "plan_build_s", "compile_s", "host_gap_ms", "dense_ms",
           "device_idle_share")
JOB_KEYS = ("job", "name", "what")      # what the recipe may differ in


def check_entries(m):
    """What this cell needs of a manifest, by membership: entries that
    later PRs append change nothing here (`test_benchmark_manifest.py`
    calls this with a grown copy)."""
    cell = mf.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "gcnii-reddit", "reddit-skewed-fit", 1)
    entry = mf.config_entry(m, "gcnii-reddit")
    conf = mf.load(os.path.join(mf.ROOT, entry["file"]))
    assert entry["reduced"] == conf["reduced"] == []
    assert entry["source"] == conf["source"] and "2007.02133" in conf["source"]
    assert (conf["model"], conf["reference"], conf["layers"]) == (
        "gcnii", "gcnii", LAYERS)
    assert (conf["learning_rate"], conf["weight_decay"], conf["dropout"],
            conf["decay_rate"], conf["eval_every"]) == (0.01, 0.0005, 0.5,
                                                        1.0, 5)
    assert (conf["precision"], conf["aggregate_backend"]) == ("fast", "auto")
    # its own bound, between two chip readings that its why names
    assert checks.logits_tol_problems(conf) == []
    assert "chip, PR 37" in conf["logits_tol"]["why"]
    for key in ("layers", "alpha", "lambda", "transplant", "hyper_parameters",
                "weight_decay", "bias", "weights", "early_stopping",
                "mem_plan", "precision"):
        assert key in conf["assumed"], key
    # the recipe: reddit-skewed's graph letter for letter, and the plan
    fit = mf.load(mf.traffic_path(m, cell))
    skewed = mf.load(os.path.join(mf.ROOT, "benchmark", "traffic",
                                  "reddit-skewed.json"))
    for key in set(fit) | set(skewed):
        if key not in JOB_KEYS:
            assert fit[key] == skewed[key], key
    assert fit["job"] == {"mem_plan": "auto"} and skewed["job"] == {}
    names = {e["name"] for e in mf.metrics_for(m, "per_layer", CELL)}
    assert set(NEW_METRICS) | set(GENERIC) <= names
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert os.path.isfile(os.path.join(mf.LAYER_METRICS_DIR,
                                           name + ".json"))
    # gcn-reddit's split of the sweeps stays with its cells: here one scope
    # claims both phases, and `dense_ms` is what it leaves
    for name in ("agg_p1_ms", "agg_p2_ms", "agg_roofline", "mm_agg_ms",
                 "tconv_attend_ms"):
        assert name not in names
    e2e = {e["name"] for e in mf.metrics_for(m, "end_to_end", CELL)}
    assert e2e == {"epoch_s", "edges_per_s_per_chip", "peak_hbm_gib",
                   "setup_s"}
    moved = {name: by_name[name]["moves"] for name in NEW_METRICS}
    assert moved == {"gcnii_agg_ms": "epoch_s",
                     "gcnii_agg_roofline": "epoch_s",
                     "gcnii_remat_layers": "epoch_s",
                     "gcnii_saved_bytes": "peak_hbm_gib"}


def test_the_cell_and_its_entries():
    check_entries(BENCH)
    assert mf.problems_in(BENCH) == []


def test_the_reference_is_the_configurations_own_module():
    conf = mf.load(os.path.join(
        mf.ROOT, mf.config_entry(BENCH, "gcnii-reddit")["file"]))
    path = os.path.join(mf.ROOT, "benchmark", "references",
                        conf["reference"] + ".py")
    assert os.path.isfile(path)
    with open(path, encoding="utf-8") as f:
        assert "roc_tpu" not in f.read().replace("`roc_tpu/", "")


def test_the_programs_op_list_is_the_irs_own():
    """`run.model_ops` of the real builder: 16 sum aggregates at width 256,
    the weighted `add`s with two inputs as wide as their output, the
    weights as plain numbers, no op kind the harness does not know."""
    from benchmark import run as bench_run
    from roc_tpu.models import build_model
    model = build_model("gcnii", LAYERS, 0.5)
    assert bench_run.aggregate_widths(model) == [256] * 16
    ops = bench_run.model_ops(model)
    assert {op["kind"] for op in ops} == {
        "dropout", "linear", "activation", "norm", "aggregate", "add"}
    adds = [op for op in ops if op["kind"] == "add"]
    assert len(adds) == 32
    assert all(op["in_widths"] == [256, 256] and op["out_width"] == 256
               and op["wa"] + op["wb"] == pytest.approx(1.0) for op in adds)
    assert (adds[0]["wa"], adds[0]["wb"]) == (pytest.approx(0.9), 0.1)
    assert adds[1]["wb"] == math.log(0.4 / 1 + 1.0)
    assert [op["layer"] for op in ops][-1] == 17


# -- the readers, on instructions named as the chip's trace names them ------

E, N = 23516643, 232965
SHAPES = {"chips": 1, "nodes": N, "in_edges": E, "precision": "fast",
          "aggregate_widths": [256] * 16, "layers": LAYERS, "ops": [],
          "backend": "binned"}


def _op(text, start, dur):
    return trace_reduce.make_op(text, float(start), float(dur))


def _trace(sweeps=34, sweep_ns=118e6, with_kernels=True):
    """One traced epoch: ``sweeps`` pairs of the binned kernels' custom
    calls (32 first, the rest recomputed: the trace tells them apart by
    nothing but their number), a linear and an elementwise fusion."""
    ops, t = [], 0.0
    if with_kernels:
        for i in range(sweeps):
            ops.append(_op(f"%_p1_run.{i} = bf16[2768896,256]{{1,0:T(8,128)"
                           f"(2,1)}} custom-call(%x, %s, %o)", t,
                           0.55 * sweep_ns))
            ops.append(_op(f"%_p2_run.{i} = f32[20480,256]{{1,0:T(8,128)}} "
                           f"custom-call(%st, %d)", t + 0.55 * sweep_ns,
                           0.45 * sweep_ns))
            t += sweep_ns
    ops.append(_op("%fusion.7 = f32[232965,256]{1,0:T(8,128)} fusion(%p0, "
                   "%p1), kind=kOutput", t, 3e6))
    ops.append(_op("%multiply_add_fusion.3 = f32[232965,256]{1,0:T(8,128)} "
                   "fusion(%a, %b), kind=kLoop", t + 3e6, 1e6))
    trace_reduce._self_times(ops)
    end = t + 4e6
    return trace_reduce.Trace(
        {0: ops}, [("bench.window", 0.0, end), ("bench.epoch", 0.0, end)])


def _run(trace, backend="binned", counters=None):
    cell = mf.cell(BENCH, CELL)
    specs = [mf.layer_metric_spec(BENCH, cell, e["name"])
             for e in mf.metrics_for(BENCH, "per_layer", CELL)]
    run = layer_metrics.TracedRun(
        trace, specs, {"plan_build": [2.0]},
        {"graph_s": 6.9, "compile_s": 30.0, **(counters or {})},
        {**SHAPES, "backend": backend}, "TPU v5 lite")
    return run, {s["name"]: s for s in specs}


def test_the_sweeps_and_the_rest_partition_the_epoch():
    run, specs = _run(_trace())
    agg = layer_metrics.read(run, specs["gcnii_agg_ms"])
    dense = layer_metrics.read(run, specs["dense_ms"])
    assert agg == pytest.approx(34 * 118.0)     # first and recomputed alike
    assert dense == pytest.approx(4.0)
    busy = trace_reduce.busy_ns(run.epoch_ops[0]) / 1e6
    assert agg + dense == pytest.approx(busy)
    # one scope claims both kernels: nothing is claimed twice
    assert [s["name"] for s in run.partition_scopes()] == ["gcnii_agg_ms"]


def test_the_roofline_share_falls_as_a_plan_recomputes_more():
    """The least work is 16 aggregates x 2 sweeps whatever the plan does:
    more recomputed sweeps, a lower share; sweeps of no length fail."""
    least, binds = roofline.least_seconds("aggregation_sweeps", SHAPES,
                                          "TPU v5 lite")
    assert binds == "bytes" and 0.4 < least < 0.6
    shares = []
    for sweeps in (32, 34, 48):
        run, specs = _run(_trace(sweeps))
        shares.append(layer_metrics.read(run, specs["gcnii_agg_roofline"]))
        assert shares[-1] == pytest.approx(
            100.0 * least / (sweeps * 0.118))
    assert 0 < shares[2] < shares[1] < shares[0] < 100
    run, specs = _run(_trace(sweep_ns=1e3))
    with pytest.raises(ValueError, match="gcnii_agg_roofline.*roofline"):
        layer_metrics.read(run, specs["gcnii_agg_roofline"])


def test_a_binned_run_without_the_kernels_fails_and_the_counters_read():
    gauges = {"mem_plan_remat_layers": 1.0, "mem_plan_kept_layers": 17.0,
              "mem_plan_saved_bytes": 11689251840.0,
              "mem_plan_predicted_peak_bytes": 13577600000.0}
    run, specs = _run(_trace(with_kernels=False), counters=gauges)
    assert specs["gcnii_agg_ms"]["required_for_backend"] == "binned"
    with pytest.raises(ValueError, match="gcnii_agg_ms.*binned backend"):
        layer_metrics.read(run, specs["gcnii_agg_ms"])
    assert layer_metrics.read(run, specs["gcnii_remat_layers"]) == 1.0
    assert layer_metrics.read(run, specs["gcnii_saved_bytes"]) \
        == 11689251840.0
    # a program without the gauges (the parent): nothing to read, no error
    bare, _ = _run(_trace())
    for name in ("gcnii_remat_layers", "gcnii_saved_bytes"):
        assert layer_metrics.read(bare, specs[name]) is None
    # on another backend (the CPU's stand-in) no kernel is no error
    cpu, _ = _run(_trace(with_kernels=False), backend=None)
    assert layer_metrics.read(cpu, specs["gcnii_agg_ms"]) == 0.0
    assert layer_metrics.read(cpu, specs["gcnii_agg_roofline"]) is None


# -- the harness on a tiny cell, from new files alone ------------------------

TINY_LAYERS = [24] + [16] * 6 + [5]


def _manifest(tmp_path, budget):
    m = copy.deepcopy(REHEARSAL)
    for sub in ("configs", "traffic"):
        (tmp_path / sub).mkdir()
    conf = mf.load(os.path.join(mf.ROOT, "benchmark", "configs",
                                "gcnii-reddit.json"))
    # `binned` runs the cell's own kernels (interpreted); `auto` answers
    # the xla backend on a CPU.  `exact`: the configuration's bound was
    # measured for `fast` at the cell's in-degree of ~100, and the bf16
    # staging's error falls with the degree: at this graph's 13 `fast`
    # reads over it (test_benchmark_gcnii_reference.py holds the bound to
    # both precisions at the cell's degree)
    conf.update(name="tiny-gcnii", layers=TINY_LAYERS, source="a test",
                aggregate_backend="binned", precision="exact")
    (tmp_path / "configs" / "tiny-gcnii.json").write_text(json.dumps(conf))
    recipe = mf.load(os.path.join(mf.ROOT, "benchmark", "rehearsal",
                                  "traffic", "tiny-skewed.json"))
    # a CPU reports no bytes_limit: the plan gets its budget from the job
    recipe.update(name="tiny-skewed-fit",
                  job={"mem_plan": "auto", "mem_budget": budget})
    (tmp_path / "traffic" / "tiny-skewed-fit.json").write_text(
        json.dumps(recipe))
    m["configs"].append({"name": "tiny-gcnii", "source": "a test",
                         "file": str(tmp_path / "configs" /
                                     "tiny-gcnii.json"),
                         "reduced": [], "why": "a test"})
    m["workloads"].append({"name": "tiny-gcnii.skewed",
                           "config": "tiny-gcnii",
                           "traffic": "tiny-skewed-fit",
                           "chips": 1, "why": "a test"})
    for e in BENCH["per_layer"]:
        if e["name"] in NEW_METRICS:
            m["per_layer"].append(dict(e, workloads=["tiny-gcnii.skewed"]))
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(m))
    return str(path)


def _bench(args, tmp_path, manifest):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--seconds", "1", "--rehearse-cpu", "--out", str(tmp_path / "out"),
         "--manifest", manifest, "--workload", "tiny-gcnii.skewed"] + args,
        cwd=mf.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    flags = next(json.loads(ln.split("checks: ", 1)[1]) for ln in lines
                 if ln.startswith("# bench: checks: "))
    return json.loads(lines[-1]), flags, lines


@pytest.mark.parametrize("trace", [0, 1])
def test_the_harness_runs_a_tiny_gcnii_cell_under_a_plan(trace, tmp_path):
    manifest = _manifest(tmp_path, "2700k")
    # a seed past 2**31, as the driver's are
    out, flags, lines = _bench(
        ["--seed", str(2**31 + 377 + trace), "--trace", str(trace)],
        tmp_path, manifest)
    assert {k for k, v in flags.items() if not v} == {
        "tpu_with_the_cells_chips"}
    assert out["failed"] == 0 and out["correct"] is False
    # the logits of initial and final parameters against gcnii.py's, under
    # the configuration's own bound
    assert set(out["compared"]) == {"logits_rel_fro_initial",
                                    "logits_rel_fro_final"}
    conf = mf.load(os.path.join(mf.ROOT, "benchmark", "configs",
                                "gcnii-reddit.json"))
    for which, c in out["compared"].items():
        assert c["limit"] == conf["logits_tol"][which.rsplit("_", 1)[1]]
        assert c["value"] < c["limit"]
    program = next(json.loads(ln.split("program: ", 1)[1]) for ln in lines
                   if ln.startswith("# bench: program: "))
    assert program["backend"] == "binned" and program["trainer"] == "Trainer"
    if not trace:
        assert set(out["metrics"]) == {"epoch_s", "edges_per_s_per_chip",
                                       "peak_hbm_gib", "setup_s"}
        assert out["attempted"] % 5 == 0 and out["attempted"] > 0
        return
    got = out["metrics"]
    with open(tmp_path / "out" / "run.json", encoding="utf-8") as f:
        counters = json.load(f)["counters"]
    # the plan decided something at this budget, and the line says what
    assert 0 < counters["mem_plan_remat_layers"] < 8
    assert counters["mem_plan_remat_layers"] \
        + counters["mem_plan_kept_layers"] == 8
    assert got["gcnii_remat_layers"]["value"] \
        == counters["mem_plan_remat_layers"]
    assert got["gcnii_saved_bytes"]["value"] \
        == counters["mem_plan_saved_bytes"] > 0
    assert counters["mem_plan_predicted_peak_bytes"] <= 2700 * 1024
    assert counters["step_unscoped_share"] == 0.0
    # a CPU's stand-in events name no custom call: the sweeps read 0 and
    # the share is left out
    assert got["gcnii_agg_ms"]["value"] >= 0.0
    assert ("gcnii_agg_roofline" in got) == (
        got["gcnii_agg_ms"]["value"] > 0)
    assert got["dense_ms"]["value"] > 0
    assert got["plan_build_s"]["value"] > 0
    assert out["attempted"] == 3
