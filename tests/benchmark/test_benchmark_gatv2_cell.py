"""The `gatv2-reddit.skewed` cell's files: the harness walks a tiny GATv2
cell on the CPU from new files alone (traced and untraced), the four new
per-layer metrics read what they say from device instructions named as the
v5e's trace names them, and the cell's shape function counts what its file
says."""

import copy
import importlib.util
import json
import math
import os
import re
import subprocess
import sys

import pytest

from benchmark import checks, layer_metrics, roofline, trace_reduce
from benchmark import manifest as mf

BENCH = mf.load(os.path.join(mf.ROOT, "BENCHMARK.json"))
REHEARSAL = mf.load(os.path.join(mf.ROOT, "benchmark", "rehearsal",
                                 "manifest.json"))
CELL = "gatv2-reddit.skewed"
NEW_METRICS = ("gatv2_attend_ms", "gatv2_edge_ms", "gatv2_roofline",
               "gatv2_residual_bytes")
GENERIC = ("graph_s", "plan_build_s", "compile_s", "host_gap_ms", "dense_ms",
           "device_idle_share")


def check_entries(m):
    """What this cell needs of a manifest, by membership: entries that
    later PRs append change nothing here (`test_benchmark_manifest.py`
    calls this with a grown copy)."""
    cell = mf.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "gatv2-reddit", "reddit-skewed", 1)
    entry = mf.config_entry(m, "gatv2-reddit")
    conf = mf.load(os.path.join(mf.ROOT, entry["file"]))
    assert entry["reduced"] == conf["reduced"] == []
    assert entry["source"] == conf["source"] and len(conf["source"]) <= 200
    for name in ("2105.14491", "eq 7", "share_weights=False", "3.3"):
        assert name in conf["source"], name
    assert (conf["model"], conf["reference"], conf["layers"],
            conf["heads"]) == ("gatv2", "gatv2", [602, 8, 41], 8)
    assert (conf["learning_rate"], conf["weight_decay"], conf["dropout"],
            conf["decay_rate"], conf["eval_every"]) == (0.005, 0.0005, 0.6,
                                                        1.0, 5)
    assert (conf["precision"], conf["aggregate_backend"]) == ("fast", "auto")
    # its own bound, between two chip readings that its why names
    assert checks.logits_tol_problems(conf) == []
    assert re.match(r"chip, PR \d+: ", conf["logits_tol"]["why"])
    for key in ("recipe", "ogb_rows", "layers", "bias", "share_weights",
                "attention_dropout", "eval_every", "weights", "precision"):
        assert key in conf["assumed"], key
    names = {e["name"] for e in mf.metrics_for(m, "per_layer", CELL)}
    assert set(NEW_METRICS) | set(GENERIC) <= names
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
    # the other attention cells' metrics stay with them, the binned
    # kernels' with theirs
    for name in ("gat_attend_ms", "gat_roofline", "tconv_attend_ms",
                 "tconv_roofline", "agg_p1_ms", "mm_agg_ms"):
        assert name not in names
    e2e = {e["name"] for e in mf.metrics_for(m, "end_to_end", CELL)}
    assert e2e == {"epoch_s", "edges_per_s_per_chip", "peak_hbm_gib",
                   "setup_s"}
    moved = {e["name"]: e["moves"] for e in m["per_layer"]}
    assert moved["gatv2_residual_bytes"] == "peak_hbm_gib"
    assert {moved[n] for n in NEW_METRICS[:3]} == {"epoch_s"}


def test_the_cell_and_its_entries():
    check_entries(BENCH)


def test_the_reference_is_the_configurations_own_module():
    conf = mf.load(os.path.join(
        mf.ROOT, mf.config_entry(BENCH, "gatv2-reddit")["file"]))
    path = os.path.join(mf.ROOT, "benchmark", "references",
                        conf["reference"] + ".py")
    assert os.path.isfile(path)
    with open(path, encoding="utf-8") as f:
        assert "roc_tpu" not in f.read().replace("`roc_tpu/", "")


# -- the shape function ------------------------------------------------------

E, N = 23516643, 232965
OPS = [{"kind": "dropout", "layer": 0, "in_widths": [602], "out_width": 602},
       {"kind": "gat", "score": "dynamic", "layer": 0, "heads": 8,
        "head_dim": 8, "in_widths": [602], "out_width": 64},
       {"kind": "activation", "layer": 0, "in_widths": [64],
        "out_width": 64},
       {"kind": "gat", "score": "dynamic", "layer": 1, "heads": 1,
        "head_dim": 41, "in_widths": [64], "out_width": 41},
       # an additive or a dot op beside them asks nothing of these scans
       {"kind": "gat", "layer": 2, "heads": 8, "head_dim": 8,
        "in_widths": [41], "out_width": 64},
       {"kind": "gat", "score": "dot", "layer": 3, "heads": 4,
        "mean_heads": 1, "head_dim": 32, "in_widths": [64],
        "out_width": 128}]
SHAPES = {"chips": 1, "nodes": N, "in_edges": E, "precision": "fast",
          "aggregate_widths": [], "layers": [602, 8, 41], "ops": OPS,
          "backend": "xla"}


def test_the_shape_function_counts_what_its_file_says():
    fn = roofline.shape_function("gatv2_sweeps")
    flops, nbytes = fn(SHAPES)
    layers = [(8, 64), (1, 41)]       # heads, heads x head width
    assert flops == sum(9 * 2.0 * E * w for _, w in layers)
    assert nbytes == sum(4 * 2 * N * w * 2 + 4 * E * 4 + 2 * k * E * 4
                         for k, w in layers)
    # exact stages float32 rows; four chips hold a quarter each
    exact = fn({**SHAPES, "precision": "exact"})
    assert exact[1] - nbytes == sum(4 * 2 * N * w * 2 for _, w in layers)
    assert fn({**SHAPES, "chips": 4})[1] == pytest.approx(nbytes / 4)
    # a model without the op asks nothing of the scans
    assert fn({**SHAPES, "ops": OPS[:1] + OPS[4:]}) == (0.0, 0.0)
    least, binds = roofline.least_seconds("gatv2_sweeps", SHAPES,
                                          "TPU v5 lite")
    assert binds == "bytes" and 0.002 < least < 0.004
    with open(os.path.join(roofline.SHAPES_DIR, "gatv2_sweeps.py"),
              encoding="utf-8") as f:
        source = f.read()
    assert "import roc_tpu" not in source and "from roc_tpu" not in source
    assert "Left out" in source


def test_the_programs_op_list_feeds_the_shape_function():
    """`run.model_ops` of the real builder gives the two dynamic-score gat
    ops with `heads` and `head_dim`.  The ops are of kind `gat` because the
    harness's own test of `ops` (`test_shapes_ops_lists_every_op_once_with
    _its_widths`) lets no other kind but `linear` change a tensor's
    width."""
    from benchmark import run as bench_run
    from roc_tpu.models import build_model
    model = build_model("gatv2", [602, 8, 41], 0.6, heads=8)
    ops = bench_run.model_ops(model)
    gatv2 = [op for op in ops if op["kind"] == "gat"]
    assert [(op["heads"], op["head_dim"], op["out_width"], op["score"])
            for op in gatv2] == [(8, 8, 64, "dynamic"), (1, 41, 41,
                                                         "dynamic")]
    fn = roofline.shape_function("gatv2_sweeps")
    assert fn({**SHAPES, "ops": ops}) == fn(SHAPES)
    assert bench_run.aggregate_widths(model) == [64, 41]


# -- the readers, on instructions named as the chip's trace names them ------

def _op(text, start, dur):
    return trace_reduce.make_op(text, float(start), float(dur))


def _trace(with_scans=True):
    """One traced epoch: a projection, a [K, E] elementwise fusion, the
    mask's random bits, a scan (`while`) with a row-gather fusion and a
    dynamic-update-slice into a [K, E] buffer in its body, the ELU."""
    ops = [
        _op("%fusion.7 = f32[232965,64]{1,0:T(8,128)} fusion(%p0, %p1), "
            "kind=kOutput", 0, 100),
        _op("%multiply_exponential_fusion.1 = f32[8,23516643]"
            "{1,0:T(8,128)} fusion(%a, %b), kind=kLoop", 100, 400),
        _op("%xor_fusion.2 = u32[8,23516643]{1,0:T(8,128)} fusion(%k), "
            "kind=kLoop", 500, 200),
    ]
    if with_scans:
        ops += [
            _op("%while.3 = (s32[], f32[16,24117248]{1,0:T(8,128)}) "
                "while(%tuple.5), condition=%c, body=%b", 1000, 5000),
            _op("%fusion.21 = f32[32768,128]{1,0:T(8,128)} fusion(%x, %i), "
                "kind=kCustom", 1100, 3000),
            _op("%dynamic-update-slice.4 = f32[16,24117248]{1,0:T(8,128)} "
                "dynamic-update-slice(%buf, %g, %z, %o)", 4100, 1500),
        ]
    ops.append(_op("%fusion.30 = f32[232965,64]{1,0:T(8,128)} "
                   "fusion(%w, %g), kind=kLoop", 6100, 80))
    trace_reduce._self_times(ops)
    return trace_reduce.Trace(
        {0: ops},
        [("bench.window", 0.0, 7000.0), ("bench.epoch", 0.0, 7000.0)])


def _run(trace, backend="xla", counters=None):
    cell = mf.cell(BENCH, CELL)
    specs = [mf.layer_metric_spec(BENCH, cell, e["name"])
             for e in mf.metrics_for(BENCH, "per_layer", CELL)]
    run = layer_metrics.TracedRun(
        trace, specs, {"plan_build": [20.0], "gat_plan_build": [12.5]},
        {"graph_s": 6.9, "compile_s": 30.0, **(counters or {})},
        {**SHAPES, "backend": backend}, "TPU v5 lite")
    return run, {s["name"]: s for s in specs}


def test_the_scans_the_edge_arrays_and_the_rest_partition_the_epoch():
    run, specs = _run(_trace())
    read = {n: layer_metrics.read(run, specs[n])
            for n in ("gatv2_attend_ms", "gatv2_edge_ms", "dense_ms")}
    # everything inside the while (its own 500 ns of self time apart)
    assert read["gatv2_attend_ms"] == pytest.approx((3000 + 1500) / 1e6)
    # [K, E]-typed results outside the scans: products, mask bits
    assert read["gatv2_edge_ms"] == pytest.approx((400 + 200) / 1e6)
    # the projections, the ELU and the while's own time are the rest's
    assert read["dense_ms"] == pytest.approx((100 + 80 + 500) / 1e6)
    busy = trace_reduce.busy_ns(run.epoch_ops[0]) / 1e6
    assert sum(read.values()) == pytest.approx(busy)


def test_the_roofline_share_is_of_the_scans_and_a_share_over_100_fails():
    run, specs = _run(_trace(), backend=None)
    share = layer_metrics.read(run, specs["gatv2_roofline"])
    least, _ = roofline.least_seconds("gatv2_sweeps", SHAPES, "TPU v5 lite")
    assert share == pytest.approx(100.0 * least / (4500 / 1e9))
    # scans of microseconds read far over 100: a chip run fails the read
    with pytest.raises(ValueError, match="gatv2_roofline.*of the roofline"):
        layer_metrics.read(_run(_trace())[0], specs["gatv2_roofline"])


def test_a_traced_run_without_the_scans_fails_and_the_counter_reads():
    run, specs = _run(_trace(with_scans=False),
                      counters={"gatv2_residual_bytes": 846599148.0})
    assert specs["gatv2_attend_ms"]["required_for_backend"] == "xla"
    with pytest.raises(ValueError, match="gatv2_attend_ms.*xla backend"):
        layer_metrics.read(run, specs["gatv2_attend_ms"])
    assert layer_metrics.read(run, specs["gatv2_residual_bytes"]) \
        == 846599148.0
    # a program without the gauge (the parent): nothing to read, no error
    bare, _ = _run(_trace())
    assert layer_metrics.read(bare, specs["gatv2_residual_bytes"]) is None


# -- the harness on a tiny cell, from new files alone ------------------------

def _manifest(tmp_path):
    m = copy.deepcopy(REHEARSAL)
    for sub in ("configs", "traffic"):
        (tmp_path / sub).mkdir()
    conf = mf.load(os.path.join(mf.ROOT, "benchmark", "configs",
                                "gatv2-reddit.json"))
    # `matmul` sends the attention through the plan road, as `auto` does on
    # the chip at the cell's size (on the CPU `auto` answers the dense one)
    conf.update(name="tiny-gatv2", layers=[24, 4, 5], source="a test",
                aggregate_backend="matmul")
    (tmp_path / "configs" / "tiny-gatv2.json").write_text(json.dumps(conf))
    recipe = mf.load(os.path.join(mf.ROOT, "benchmark", "rehearsal",
                                  "traffic", "tiny-skewed.json"))
    (tmp_path / "traffic" / "tiny-skewed.json").write_text(
        json.dumps(recipe))
    m["configs"].append({"name": "tiny-gatv2", "source": "a test",
                         "file": str(tmp_path / "configs" /
                                     "tiny-gatv2.json"),
                         "reduced": [], "why": "a test"})
    m["workloads"].append({"name": "tiny-gatv2.skewed",
                           "config": "tiny-gatv2", "traffic": "tiny-skewed",
                           "chips": 1, "why": "a test"})
    for e in BENCH["per_layer"]:
        if e["name"] in NEW_METRICS:
            m["per_layer"].append(dict(e, workloads=["tiny-gatv2.skewed"]))
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(m))
    return str(path)


def _bench(args, tmp_path, manifest):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--seconds", "1", "--rehearse-cpu", "--out", str(tmp_path / "out"),
         "--manifest", manifest, "--workload", "tiny-gatv2.skewed"] + args,
        cwd=mf.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    flags = next(json.loads(ln.split("checks: ", 1)[1]) for ln in lines
                 if ln.startswith("# bench: checks: "))
    return json.loads(lines[-1]), flags, lines, p.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_the_harness_runs_a_tiny_gatv2_cell_from_new_files(trace, tmp_path):
    manifest = _manifest(tmp_path)
    # a seed past 2**31, as the benchmark's runs may take
    out, flags, lines, err = _bench(
        ["--seed", str(2**31 + 177 + trace), "--trace", str(trace)],
        tmp_path, manifest)
    assert {k for k, v in flags.items() if not v} == {
        "tpu_with_the_cells_chips"}
    assert out["failed"] == 0 and out["correct"] is False
    # the logits of initial and final parameters against gatv2.py's
    assert set(out["compared"]) == {"logits_rel_fro_initial",
                                    "logits_rel_fro_final"}
    for c in out["compared"].values():
        assert c["value"] < 1e-5 < c["limit"]
    program = next(json.loads(ln.split("program: ", 1)[1]) for ln in lines
                   if ln.startswith("# bench: program: "))
    assert program["backend"] == "xla" and program["trainer"] == "Trainer"
    # the trainer's own start-up line: which road, which score
    line = next(ln for ln in err.splitlines()
                if ln.startswith("# attention: "))
    assert line.startswith("# attention: backend=plan ")
    assert " gatv2_score=dynamic " in line and " gatv2_row_scans=8" in line
    if not trace:
        assert set(out["metrics"]) == {"epoch_s", "edges_per_s_per_chip",
                                       "peak_hbm_gib", "setup_s"}
        assert out["attempted"] % 5 == 0 and out["attempted"] > 0
        return
    got = out["metrics"]
    with open(tmp_path / "out" / "run.json", encoding="utf-8") as f:
        info = json.load(f)
    edges = info["graph"]["in_edges"] if "in_edges" in info["graph"] \
        else None
    counters = info["counters"]
    # e float32 of both ops, [8, E] then [1, E]; one [8, E] array
    assert got["gatv2_residual_bytes"]["value"] \
        == counters["gatv2_residual_bytes"] > 0
    assert 8 * counters["gatv2_residual_bytes"] == 9 * counters[
        "gatv2_score_bytes"]
    if edges is not None:
        assert counters["gatv2_score_bytes"] == 8 * edges * 4
    assert (counters["gatv2_row_scans"], counters["gatv2_src_scans"]) \
        == (8, 2)
    assert counters["gatv2_plan_pad_ratio"] >= 1.0
    assert "gatv2_backend" not in counters      # a labelled gauge
    # a CPU's stand-in events carry no result type and no nesting, so the
    # two device scopes read 0 here (a few microseconds when XLA's CPU
    # runtime starts an instruction while a `while` runs on another thread)
    for name in ("gatv2_attend_ms", "gatv2_edge_ms"):
        assert got[name]["unit"] == "ms"
        assert got[name]["value"] >= 0.0
        assert math.isfinite(got[name]["value"])
    assert ("gatv2_roofline" in got) == (got["gatv2_attend_ms"]["value"] > 0)
    assert not {"gat_roofline", "tconv_roofline", "agg_roofline"} & set(got)
    assert got["dense_ms"]["value"] > 0
    assert got["plan_build_s"]["value"] > 0
    assert out["attempted"] == 3


def test_the_shapes_module_loads_as_a_file():
    spec = importlib.util.spec_from_file_location(
        "gatv2_sweeps_probe", os.path.join(roofline.SHAPES_DIR,
                                           "gatv2_sweeps.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert (module.SWEEPS, module.PRODUCTS, module.TABLES) == (4, 9, 2)
