"""The `gat-reddit.skewed` cell's files: the harness walks a tiny GAT cell on
the CPU from new files alone (traced and untraced), and the four new
per-layer metrics read what they say from device instructions named as the
v5e's trace names them."""

import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import layer_metrics, trace_reduce
from benchmark import manifest as mf

BENCH = mf.load(os.path.join(mf.ROOT, "BENCHMARK.json"))
REHEARSAL = mf.load(os.path.join(mf.ROOT, "benchmark", "rehearsal",
                                 "manifest.json"))
CELL = "gat-reddit.skewed"
NEW_METRICS = ("gat_attend_ms", "gat_edge_ms", "gat_roofline",
               "gat_plan_build_s")
ACCEPTED_CELLS = ["gcn-reddit.regular", "gcn-reddit.skewed"]


def test_the_cell_and_its_entries():
    cell = mf.cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "gat-reddit", "reddit-skewed", 1)
    conf = mf.load(os.path.join(mf.ROOT,
                                mf.config_entry(BENCH, "gat-reddit")["file"]))
    assert (conf["model"], conf["reference"], conf["layers"],
            conf["heads"]) == ("gat", "gat", [602, 8, 41], 8)
    assert (conf["learning_rate"], conf["weight_decay"], conf["dropout"],
            conf["decay_rate"], conf["eval_every"]) == (0.005, 0.0005, 0.6,
                                                        1.0, 5)
    assert (conf["precision"], conf["aggregate_backend"],
            conf["reduced"]) == ("fast", "auto", [])
    names = [e["name"] for e in mf.metrics_for(BENCH, "per_layer", CELL)]
    assert set(NEW_METRICS) <= set(names)
    # the binned kernels' metrics stay with the cells that run the kernels
    for name in ("agg_p1_ms", "agg_p2_ms", "agg_copy_ms", "agg_roofline"):
        entry = next(e for e in BENCH["per_layer"] if e["name"] == name)
        assert entry["workloads"] == ACCEPTED_CELLS
        assert name not in names
    for name in NEW_METRICS:
        entry = next(e for e in BENCH["per_layer"] if e["name"] == name)
        assert entry["workloads"] == [CELL]
    e2e = {e["name"] for e in mf.metrics_for(BENCH, "end_to_end", CELL)}
    assert e2e == {"epoch_s", "edges_per_s_per_chip", "peak_hbm_gib",
                   "setup_s"}


# -- the readers, on instructions named as the chip's trace names them ------

E = 23516643


def _op(text, start, dur):
    return trace_reduce.make_op(text, float(start), float(dur))


def _trace(with_scans=True):
    """One traced epoch: a linear, a [K, E] elementwise fusion, the mask's
    random bits, a scan (`while`) with a gather fusion and a
    dynamic-update-slice into a [K, E] buffer in its body, Adam."""
    ops = [
        _op("%fusion.7 = f32[232965,64]{1,0:T(8,128)} fusion(%p0, %p1), "
            "kind=kOutput", 0, 100),
        _op("%select_multiply_fusion.1 = f32[8,23516643]{1,0:T(8,128)} "
            "fusion(%a, %b), kind=kLoop", 100, 400),
        _op("%xor_fusion.2 = u32[8,23516643]{1,0:T(8,128)} fusion(%k), "
            "kind=kLoop", 500, 200),
        _op("%fusion.9 = f32[1,23516643]{1,0:T(1,128)} fusion(%a), "
            "kind=kLoop", 700, 50),
    ]
    if with_scans:
        ops += [
            _op("%while.3 = (s32[], f32[8,24117248]{1,0:T(8,128)}) "
                "while(%tuple.5), condition=%c, body=%b", 1000, 5000),
            _op("%fusion.21 = f32[1048576,8]{1,0:T(8,128)} fusion(%x, %i), "
                "kind=kCustom", 1100, 3000),
            _op("%dynamic-update-slice.4 = f32[8,24117248]{1,0:T(8,128)} "
                "dynamic-update-slice(%buf, %g, %z, %o)", 4100, 1500),
        ]
    ops.append(_op("%fusion.30 = f32[602,64]{1,0:T(8,128)} fusion(%w, %g), "
                   "kind=kLoop", 6100, 80))
    trace_reduce._self_times(ops)
    return trace_reduce.Trace(
        {0: ops},
        [("bench.window", 0.0, 7000.0), ("bench.epoch", 0.0, 7000.0)])


def _run(trace, backend="xla", spans=None):
    cell = mf.cell(BENCH, CELL)
    specs = [mf.layer_metric_spec(BENCH, cell, e["name"])
             for e in mf.metrics_for(BENCH, "per_layer", CELL)]
    shapes = {"chips": 1, "nodes": 232965, "in_edges": E,
              "precision": "fast", "aggregate_widths": [64, 41],
              "layers": [602, 8, 41], "backend": backend}
    run = layer_metrics.TracedRun(
        trace, specs, spans or {"plan_build": [20.0],
                                "gat_plan_build": [12.5]},
        {"graph_s": 6.9, "compile_s": 30.0}, shapes, "TPU v5 lite")
    return run, {s["name"]: s for s in specs}


def test_the_scans_the_edge_arrays_and_the_rest_partition_the_epoch():
    run, specs = _run(_trace())
    read = {n: layer_metrics.read(run, specs[n])
            for n in ("gat_attend_ms", "gat_edge_ms", "dense_ms")}
    # everything inside the while (its own 500 ns of self time apart)
    assert read["gat_attend_ms"] == pytest.approx((3000 + 1500) / 1e6)
    # [K, E]-typed results outside the scans: products, mask bits, K = 1
    assert read["gat_edge_ms"] == pytest.approx((400 + 200 + 50) / 1e6)
    # the linear, Adam and the while's own time are the rest's
    assert read["dense_ms"] == pytest.approx((100 + 80 + 500) / 1e6)
    busy = trace_reduce.busy_ns(run.epoch_ops[0]) / 1e6
    assert sum(read.values()) == pytest.approx(busy)


def test_the_roofline_share_is_of_the_scans_and_under_100():
    run, specs = _run(_trace())
    share = layer_metrics.read(run, specs["gat_roofline"])
    least, binds = layer_metrics.roofline.least_seconds(
        "aggregation_sweeps", run.shapes, "TPU v5 lite")
    assert binds == "bytes"
    assert share == pytest.approx(100.0 * least / (4500 / 1e9))
    # at the chip's true times (seconds an epoch, not microseconds) the
    # share is a few per cent: two sweeps of 64 and 41 wide rows
    assert 0.010 < least < 0.020


def test_a_traced_run_without_the_scans_fails():
    """The cell's backend is `xla` (no sum or avg aggregate for a plan
    backend to take), so `required_for_backend: xla` makes a traced run
    fail when nothing ran inside a scan: the attention path was renamed or
    replaced and the metric would read 0 with `correct` still true."""
    run, specs = _run(_trace(with_scans=False))
    assert specs["gat_attend_ms"]["required_for_backend"] == "xla"
    with pytest.raises(ValueError, match="gat_attend_ms.*xla backend"):
        layer_metrics.read(run, specs["gat_attend_ms"])
    # the rehearsal (backend None) and a metric of a span read on
    rehearsed, _ = _run(_trace(with_scans=False), backend=None)
    assert layer_metrics.read(rehearsed, specs["gat_attend_ms"]) == 0.0
    assert layer_metrics.read(run, specs["gat_plan_build_s"]) == 12.5
    # a parent without the span: nothing to read, no error
    bare, _ = _run(_trace(), spans={"plan_build": [20.0]})
    assert layer_metrics.read(bare, specs["gat_plan_build_s"]) is None


# -- the harness on a tiny GAT cell, from new files alone -------------------

def _manifest(tmp_path):
    m = copy.deepcopy(REHEARSAL)
    for sub in ("configs", "traffic"):
        (tmp_path / sub).mkdir()
    conf = mf.load(os.path.join(mf.ROOT, "benchmark", "configs",
                                "gat-reddit.json"))
    # `matmul` sends the attention through the plan path, as `auto` does on
    # the chip at the cell's size (on the CPU `auto` answers the dense one)
    conf.update(name="tiny-gat", layers=[24, 8, 5], source="a test",
                aggregate_backend="matmul")
    (tmp_path / "configs" / "tiny-gat.json").write_text(json.dumps(conf))
    recipe = mf.load(os.path.join(mf.ROOT, "benchmark", "rehearsal",
                                  "traffic", "tiny-skewed.json"))
    (tmp_path / "traffic" / "tiny-skewed.json").write_text(
        json.dumps(recipe))
    m["configs"].append({"name": "tiny-gat", "source": "a test", "file": str(
        tmp_path / "configs" / "tiny-gat.json"), "reduced": [],
        "why": "a test"})
    m["workloads"].append({"name": "tiny-gat.skewed", "config": "tiny-gat",
                           "traffic": "tiny-skewed", "chips": 1,
                           "why": "a test"})
    for e in BENCH["per_layer"]:
        if e["name"] in NEW_METRICS:
            m["per_layer"].append(dict(e, workloads=["tiny-gat.skewed"]))
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(m))
    return str(path)


def _bench(args, tmp_path, manifest):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--seconds", "1", "--rehearse-cpu", "--out", str(tmp_path / "out"),
         "--manifest", manifest, "--workload", "tiny-gat.skewed"] + args,
        cwd=mf.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    checks = next(json.loads(ln.split("checks: ", 1)[1]) for ln in lines
                  if ln.startswith("# bench: checks: "))
    return json.loads(lines[-1]), checks, lines, p.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_the_harness_runs_a_tiny_gat_cell_from_new_files(trace, tmp_path):
    manifest = _manifest(tmp_path)
    # a seed past 2**31, as the driver's are
    out, checks, lines, err = _bench(
        ["--seed", str(2**31 + 77 + trace), "--trace", str(trace)],
        tmp_path, manifest)
    assert {k for k, v in checks.items() if not v} == {
        "tpu_with_the_cells_chips"}
    assert out["failed"] == 0 and out["correct"] is False
    program = next(json.loads(ln.split("program: ", 1)[1]) for ln in lines
                   if ln.startswith("# bench: program: "))
    assert program["backend"] == "xla" and program["trainer"] == "Trainer"
    # the trainer's own start-up line: which attention path, fused or not
    assert "# attention: backend=plan gat_fused=False (no -megafuse)" in err
    if not trace:
        assert set(out["metrics"]) == {"epoch_s", "edges_per_s_per_chip",
                                       "peak_hbm_gib", "setup_s"}
        assert out["attempted"] % 5 == 0 and out["attempted"] > 0
        return
    got = out["metrics"]
    # read from the trace and the spans; a CPU's stand-in events carry no
    # result type and no nesting, so the two device scopes read 0 here and
    # the share of a roofline, whose numerator they are, nothing
    assert got["gat_plan_build_s"]["value"] > 0
    assert got["gat_plan_build_s"]["value"] <= got["plan_build_s"]["value"]
    assert got["gat_attend_ms"] == {"value": 0.0, "unit": "ms"}
    assert got["gat_edge_ms"] == {"value": 0.0, "unit": "ms"}
    assert "gat_roofline" not in got and "agg_roofline" not in got
    assert got["dense_ms"]["value"] > 0
    assert out["attempted"] == 3
