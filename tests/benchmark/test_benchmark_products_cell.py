"""The `gcn-products.p4` cell's files: the entries agree with the data files,
the five new per-layer metrics read what they say from device instructions
named as the v5e's trace names them, and the harness walks a tiny copy of
the cell on four virtual CPU devices from new files alone (traced and
untraced)."""

import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import graphgen, layer_metrics, trace_reduce
from benchmark import manifest as mf

BENCH = mf.load(os.path.join(mf.ROOT, "BENCHMARK.json"))
REHEARSAL = mf.load(os.path.join(mf.ROOT, "benchmark", "rehearsal",
                                 "manifest.json"))
CELL = "gcn-products.p4"
NEW_METRICS = ("exchange_ms", "exchange_exposed_ms", "mm_agg_ms",
               "mm_agg_roofline", "partition_s", "place_p4_s")
GENERIC = ("graph_s", "plan_build_s", "compile_s", "host_gap_ms",
           "dense_ms", "device_idle_share")
ONE_CHIP_CELLS = ["gcn-reddit.regular", "gcn-reddit.skewed",
                  "gat-reddit.skewed"]


def _conf():
    return mf.load(os.path.join(
        mf.ROOT, mf.config_entry(BENCH, "gcn-products")["file"]))


def test_the_cell_and_its_entries():
    cell = mf.cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "gcn-products", "products-local-p4", 4)
    # the benchmark's one four-chip cell, and the last entry of its list
    assert [w["name"] for w in BENCH["workloads"] if w["chips"] == 4] == [
        CELL]
    assert BENCH["workloads"][-1]["name"] == CELL
    assert [w["name"] for w in BENCH["workloads"][:3]] == ONE_CHIP_CELLS
    entry = mf.config_entry(BENCH, "gcn-products")
    assert entry == BENCH["configs"][-1] and entry["reduced"] == []
    conf = _conf()
    assert conf["source"] == entry["source"] and len(conf["source"]) <= 200
    assert "products/gnn.py" in conf["source"] and "gnn.cc:75-92" in \
        conf["source"]
    assert (conf["model"], conf["reference"], conf["layers"]) == (
        "gcn", "gcn", [100, 256, 256, 47])
    assert (conf["learning_rate"], conf["weight_decay"], conf["decay_rate"],
            conf["dropout"], conf["eval_every"]) == (0.01, 0.0, 1.0, 0.5, 5)
    assert (conf["precision"], conf["aggregate_backend"],
            conf["reduced"]) == ("fast", "auto", [])
    for key in ("projected_residual", "layer_recipe", "bias", "batch_norm",
                "epochs", "weights", "ogb_arguments", "deployment"):
        assert key in conf["assumed"], key
    names = [e["name"] for e in mf.metrics_for(BENCH, "per_layer", CELL)]
    assert set(names) == set(NEW_METRICS) | set(GENERIC)
    for name in NEW_METRICS:
        e = next(e for e in BENCH["per_layer"] if e["name"] == name)
        assert e["workloads"] == [CELL]
        assert e["moves"] == ("setup_s" if name.endswith("_s")
                              else "epoch_s")
    # the new entries stand at the end of the list, in this order
    assert [e["name"] for e in BENCH["per_layer"][-6:]] == list(NEW_METRICS)
    # each names its own layer: the partitioner alone, and the train
    # step's set-up under the name the benchmark already gives that layer
    layer = {e["name"]: e["layer"] for e in BENCH["per_layer"]}
    assert layer["partition_s"] == \
        "partition (graph/partition.py partition_graph)"
    assert layer["place_p4_s"] == layer["place_s"] == layer["compile_s"]
    e2e = {e["name"] for e in mf.metrics_for(BENCH, "end_to_end", CELL)}
    assert e2e == {"epoch_s", "edges_per_s_per_chip", "peak_hbm_gib",
                   "setup_s"}
    assert mf.problems_in(BENCH) == []


def test_the_recipe_is_the_issues():
    recipe = graphgen.load_recipe(mf.traffic_path(BENCH, mf.cell(BENCH, CELL)))
    assert (recipe["avg_degree"], recipe["degree_law"], recipe["skew"],
            recipe["communities"], recipe["p_intra"], recipe["inter"],
            recipe["layout"], recipe["structure_seed"], recipe["job"]) == (
        25, "power", 2.5, 47, 0.8, "ring", "contiguous", 1, {})
    whole = {"train": 196615, "val": 39323, "test": 2213091}
    assert sum(whole.values()) == 2449029
    n = recipe["nodes"]
    assert 1028592 <= n <= 2449029
    if n == 2449029:
        assert recipe["splits"] == whole and not recipe["reduced"]
    else:       # the one cut allowed: nodes, with the measurement behind it
        assert set(recipe["reduced"]) == {"nodes"}
        assert any(c.isdigit() for c in recipe["reduced"]["nodes"])
        for k, v in whole.items():
            assert recipe["splits"][k] == pytest.approx(v * n / 2449029,
                                                        abs=1)


def test_the_exchange_readers_are_the_rehearsals():
    for name in ("exchange_ms", "exchange_exposed_ms"):
        a = mf.load(os.path.join(mf.ROOT, "benchmark", "layer_metrics",
                                 name + ".json"))
        b = mf.load(os.path.join(mf.ROOT, "benchmark", "rehearsal",
                                 "layer_metrics", name + ".json"))
        assert a == b and a["across"] == "max"


# -- the readers, on instructions named as the chip's trace names them ------

NODES, EDGES = 2449029, 124603333


def _op(text, start, dur):
    return trace_reduce.make_op(text, float(start), float(dur))


def _trace(with_scans=True):
    """One traced epoch of one device: a linear, the halo rows' gather, an
    all-to-all, a matmul-backend scan (`while`) with the row gather, the
    one-hot product, the carry's update and, inside it, a collective (an
    exchange the compiler sank into the loop); the gradient all-reduce;
    Adam."""
    ops = [
        _op("%fusion.7 = f32[612864,256]{1,0:T(8,128)} fusion(%p0, %p1), "
            "kind=kOutput", 0, 100),
        _op("%gather_fusion.2 = f32[4,74047,256]{2,1,0:T(8,128)} "
            "fusion(%x, %i), kind=kLoop", 100, 60),
        _op("%all-to-all.3 = f32[4,74047,256]{2,1,0:T(8,128)} "
            "all-to-all(%gather_fusion.2), replica_groups={{0,1,2,3}}",
            160, 240),
    ]
    if with_scans:
        ops += [
            _op("%while.5 = (s32[], f32[616952,256]{1,0:T(8,128)}) "
                "while(%tuple.9), condition=%c, body=%b", 400, 4000),
            _op("%fusion.21 = f32[131072,256]{1,0:T(8,128)} fusion(%x, %i), "
                "kind=kCustom", 450, 2000),
            _op("%convolution_fusion.3 = f32[512,8,256]{2,1,0:T(8,128)} "
                "fusion(%s1, %g), kind=kOutput", 2450, 1000),
            _op("%all-to-all.4 = f32[4,74047,47]{2,1,0:T(8,128)} "
                "all-to-all(%h), replica_groups={{0,1,2,3}}", 3450, 300),
            _op("%dynamic-update-slice.6 = f32[616952,256]{1,0:T(8,128)} "
                "dynamic-update-slice(%acc, %o, %b, %z)", 3750, 500),
        ]
    ops += [
        _op("%all-reduce.1 = f32[256,256]{1,0:T(8,128)} all-reduce(%g), "
            "replica_groups={{0,1,2,3}}, to_apply=%add", 4500, 50),
        _op("%fusion.30 = f32[256,256]{1,0:T(8,128)} fusion(%w, %g), "
            "kind=kLoop", 4550, 80),
    ]
    trace_reduce._self_times(ops)
    return trace_reduce.Trace(
        {0: ops},
        [("bench.window", 0.0, 5000.0), ("bench.epoch", 0.0, 5000.0)])


def _run(trace, backend="matmul", spans=None):
    cell = mf.cell(BENCH, CELL)
    specs = [mf.layer_metric_spec(BENCH, cell, e["name"])
             for e in mf.metrics_for(BENCH, "per_layer", CELL)]
    shapes = {"chips": 4, "nodes": NODES, "in_edges": EDGES,
              "precision": "fast", "aggregate_widths": [256, 256, 47],
              "layers": [100, 256, 256, 47], "backend": backend}
    run = layer_metrics.TracedRun(
        trace, specs, spans if spans is not None else {
            "partition": [5.5], "plan_build": [100.0], "halo_build": [0.9],
            "place_data": [3.5], "init_params": [1.75], "mem_plan": [0.25],
            "step_build": [0.5]},
        {"graph_s": 50.0, "compile_s": 170.0}, shapes, "TPU v5 lite")
    return run, {s["name"]: s for s in specs}


def test_scans_collectives_and_the_rest_split_the_busy_time():
    run, specs = _run(_trace())
    read = {n: layer_metrics.read(run, specs[n])
            for n in ("mm_agg_ms", "exchange_exposed_ms", "dense_ms")}
    # everything inside the while but the collective the compiler put there
    assert read["mm_agg_ms"] == pytest.approx((2000 + 1000 + 500) / 1e6)
    # all three collectives, wherever they run: nothing else computes then
    assert read["exchange_exposed_ms"] == pytest.approx(
        (240 + 300 + 50) / 1e6)
    # the linear, the halo rows' gather, Adam and the while's own 200 ns
    assert read["dense_ms"] == pytest.approx((100 + 60 + 80 + 200) / 1e6)
    busy = trace_reduce.busy_ns(run.epoch_ops[0]) / 1e6
    assert sum(read.values()) == pytest.approx(busy)
    # synchronous collectives: in flight for as long as they are exposed
    assert layer_metrics.read(run, specs["exchange_ms"]) == pytest.approx(
        read["exchange_exposed_ms"])
    assert layer_metrics.read(run, specs["partition_s"]) == 5.5
    assert layer_metrics.read(run, specs["plan_build_s"]) == 100.9
    # what `place_s` reads on one chip, under this cell's own name: with
    # the two above, every set-up span of the sharded trainer has a reader
    assert layer_metrics.read(run, specs["place_p4_s"]) == 6.0
    assert specs["place_p4_s"]["spans"] == mf.load(os.path.join(
        mf.ROOT, "benchmark", "layer_metrics", "place_s.json"))["spans"]


def test_the_roofline_share_is_of_the_scans_alone_and_under_100():
    run, specs = _run(_trace())
    share = layer_metrics.read(run, specs["mm_agg_roofline"])
    least, binds = layer_metrics.roofline.least_seconds(
        "aggregation_sweeps", run.shapes, "TPU v5 lite")
    assert binds == "bytes"
    assert share == pytest.approx(100.0 * least / (3500 / 1e9))
    # a chip's share of the epoch's six sweeps at 256, 256 and 47: 89
    # milliseconds at the published bandwidth, so scans of seconds read
    # a few per cent
    assert 0.080 < least < 0.100
    assert specs["mm_agg_roofline"]["exclude"] == specs["mm_agg_ms"][
        "exclude"]
    assert specs["mm_agg_roofline"]["inside"] == specs["mm_agg_ms"]["inside"]


def test_a_traced_run_without_the_scans_fails():
    """`auto` resolves `matmul` on the cell's shards; a traced run in which
    nothing ran inside a scan means the backend's loop was renamed or
    replaced, and its time would have moved into `dense_ms` with `correct`
    still true."""
    run, specs = _run(_trace(with_scans=False))
    assert specs["mm_agg_ms"]["required_for_backend"] == "matmul"
    assert specs["mm_agg_ms"]["partition"] is True
    with pytest.raises(ValueError, match="mm_agg_ms.*matmul backend"):
        layer_metrics.read(run, specs["mm_agg_ms"])
    # another backend, and the rehearsal (backend None), read on
    for backend in ("binned", None):
        other, _ = _run(_trace(with_scans=False), backend=backend)
        assert layer_metrics.read(other, specs["mm_agg_ms"]) == 0.0
        assert layer_metrics.read(other, specs["mm_agg_roofline"]) is None
    # a parent without the span: nothing to read, no error
    bare, _ = _run(_trace(), spans={"plan_build": [100.0]})
    assert layer_metrics.read(bare, specs["partition_s"]) is None
    assert layer_metrics.read(bare, specs["place_p4_s"]) is None


# -- the harness on a tiny copy of the cell, from new files alone -----------

TINY_NODES = 3000


def _manifest(tmp_path):
    m = copy.deepcopy(REHEARSAL)
    for sub in ("configs", "traffic", "layer_metrics"):
        (tmp_path / sub).mkdir()
    conf = _conf()
    # `matmul` sends the aggregation through the chunk plans, as `auto`
    # does on the chip at the cell's size (on the CPU `auto` answers xla)
    conf.update(name="tiny-products", layers=[12, 16, 16, 7],
                source="a test", aggregate_backend="matmul")
    (tmp_path / "configs" / "tiny-products.json").write_text(
        json.dumps(conf))
    recipe = mf.load(mf.traffic_path(BENCH, mf.cell(BENCH, CELL)))
    scale = TINY_NODES / recipe["nodes"]
    recipe.update(nodes=TINY_NODES, avg_degree=5, communities=7, splits={
        k: int(v * scale) for k, v in recipe["splits"].items()})
    (tmp_path / "traffic" / "tiny-products-p4.json").write_text(
        json.dumps(recipe))
    for name in NEW_METRICS:    # found beside the configuration, as new
        spec = mf.load(os.path.join(mf.ROOT, "benchmark", "layer_metrics",
                                    name + ".json"))
        (tmp_path / "layer_metrics" / (name + ".json")).write_text(
            json.dumps(spec))
    m["configs"].append({"name": "tiny-products", "source": "a test",
                         "file": str(tmp_path / "configs"
                                     / "tiny-products.json"),
                         "reduced": [], "why": "a test"})
    m["workloads"].append({"name": "tiny-products.p4",
                           "config": "tiny-products",
                           "traffic": "tiny-products-p4", "chips": 4,
                           "why": "a test"})
    have = {e["name"] for e in m["per_layer"]}
    for e in BENCH["per_layer"]:
        if e["name"] in NEW_METRICS:
            new = dict(e, workloads=["tiny-products.p4"])
            if e["name"] in have:   # the rehearsal's own exchange readers
                m["per_layer"] = [new if x["name"] == e["name"] else x
                                  for x in m["per_layer"]]
            else:
                m["per_layer"].append(new)
    # the kernels' metrics stay with the cells that run the kernels
    for e in m["per_layer"]:
        if e["name"].startswith("agg_"):
            e["workloads"] = ["tiny-gcn.regular", "tiny-gcn.skewed"]
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(m))
    return str(path)


def _bench(args, tmp_path, manifest):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--seconds", "1", "--rehearse-cpu", "--out", str(tmp_path / "out"),
         "--manifest", manifest, "--workload", "tiny-products.p4"] + args,
        cwd=mf.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    checks = next(json.loads(ln.split("checks: ", 1)[1]) for ln in lines
                  if ln.startswith("# bench: checks: "))
    return json.loads(lines[-1]), checks, lines, p.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_the_harness_runs_a_tiny_copy_of_the_cell_from_new_files(trace,
                                                                 tmp_path):
    manifest = _manifest(tmp_path)
    # a seed past 2**31, as the driver's are
    out, checks, lines, err = _bench(
        ["--seed", str(2**31 + 290 + trace), "--trace", str(trace)],
        tmp_path, manifest)
    assert {k for k, v in checks.items() if not v} == {
        "tpu_with_the_cells_chips"}
    assert out["failed"] == 0 and out["correct"] is False
    assert out["device"]["count"] == 4
    program = next(json.loads(ln.split("program: ", 1)[1]) for ln in lines
                   if ln.startswith("# bench: program: "))
    assert (program["backend"], program["exchange"], program["trainer"]) == (
        "matmul", "halo", "SpmdTrainer")
    shards = program["shards"]
    assert shards["parts"] == 4 and shards["halo_rows_per_peer"] > 0
    # the trainer's own start-up line says the same, and why the backend
    line = next(ln for ln in err.splitlines()
                if ln.startswith("# exchange: "))
    assert line.startswith("# exchange: mode=halo parts=4 ")
    assert f" halo_rows_per_peer={shards['halo_rows_per_peer']} " in line
    assert line.endswith(" agg_backend=matmul (-aggr-backend=matmul)")
    if not trace:
        assert set(out["metrics"]) == {"epoch_s", "edges_per_s_per_chip",
                                       "peak_hbm_gib", "setup_s"}
        assert out["attempted"] % 5 == 0 and out["attempted"] > 0
        return
    got = out["metrics"]
    assert set(GENERIC) <= set(got)
    assert set(got) <= set(GENERIC) | set(NEW_METRICS)
    # collectives run on the CPU runtime too, and the spans are the host's
    assert got["exchange_ms"]["value"] > 0
    assert 0 < got["exchange_exposed_ms"]["value"] <= \
        got["exchange_ms"]["value"] * 1.000001
    assert got["partition_s"] == {
        "value": got["partition_s"]["value"], "unit": "s"}
    assert 0 < got["partition_s"]["value"] < got["plan_build_s"]["value"] + \
        got["partition_s"]["value"]
    # the five set-up readers are of sibling spans: together under the
    # benchmark's trainer phase
    assert got["place_p4_s"]["unit"] == "s" and got["place_p4_s"]["value"] > 0
    assert got["dense_ms"]["value"] > 0
    # a CPU's stand-in events carry no nesting, so nothing is "inside a
    # while" there: the scans' time reads 0, or a little where the CPU
    # runtime's threads overlap an op with a `while` (PERF.md section 7);
    # either way the metric is reported and the share follows it
    assert got["mm_agg_ms"]["unit"] == "ms"
    assert got["mm_agg_ms"]["value"] >= 0
    assert ("mm_agg_roofline" in got) == (got["mm_agg_ms"]["value"] > 0)
    assert out["attempted"] == 3
