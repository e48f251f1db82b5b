"""The reduction from a profiler trace to device times, against a recorded
trace committed beside this file: `gcn-reddit.regular` on one TPU v5e
(PR 22's first traced chip run: three epochs and one evaluation, 2,534
device instructions), and on hand-made instruction lists for what a
one-chip trace cannot hold (collectives)."""

import gzip
import os
import shutil

import pytest

from benchmark import layer_metrics, trace_reduce
from benchmark import manifest as mf
from benchmark.trace_reduce import Trace, make_op

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = "gcn-reddit.regular.v5e.xplane.pb.gz"
# what the traced graph was (its run.json): seed 3 of reddit-regular
SHAPES = {"chips": 1, "nodes": 232965, "in_edges": 23391517,
          "precision": "fast", "aggregate_widths": [256, 41]}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    with gzip.open(os.path.join(DATA, RECORDED), "rb") as src, \
            open(d / "chip.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    path = trace_reduce.find_xplane(str(d.parent.parent.parent))
    assert path.endswith("chip.xplane.pb")
    return trace_reduce.load(path)


@pytest.fixture(scope="module")
def traced_run(recorded):
    m = mf.load(os.path.join(mf.ROOT, "BENCHMARK.json"))
    cell = m["workloads"][0]
    specs = [mf.layer_metric_spec(m, cell, e["name"])
             for e in mf.metrics_for(m, "per_layer", cell["name"])]
    return layer_metrics.TracedRun(
        recorded, specs, {"plan_build": [18.0], "halo_build": [0.5]},
        {"graph_s": 6.9, "compile_s": 1.5}, SHAPES, "TPU v5 lite"), specs


def test_recorded_trace_is_read_whole(recorded):
    assert sorted(recorded.devices) == [0]
    assert len(recorded.devices[0]) == 2534
    assert len(recorded.async_ops[0]) == 97
    names = [n for n, _, _ in recorded.annotations]
    assert names.count("bench.epoch") == 3
    assert names.count("bench.eval") == 1 and names.count("bench.window") == 1
    # the host's annotations and the device's instructions share a clock:
    # every instruction starts inside the window annotation
    (a, b), = recorded.windows("bench.window")
    assert all(a <= o.start < b for o in recorded.devices[0])


def test_instruction_names_are_cut_from_the_hlo_text(recorded):
    kernels = {o.name.split(".")[0] for o in recorded.devices[0]
               if o.opcode == "custom-call"}
    assert kernels == {"_p1_flat_run", "_p2_run", "custom-call"}
    op = next(o for o in recorded.devices[0]
              if o.name == "_p1_flat_run.12")
    assert op.scope == "_p1_flat_run.12 custom-call f32[2170880,256]"
    assert any(o.opcode == "while" for o in recorded.devices[0])


RECORDED_VALUES = {
    "agg_p1_ms": 1562.642085, "agg_p2_ms": 176.5165023,
    "agg_copy_ms": 160.9424427, "dense_ms": 21.8325437, "host_gap_ms": 3.56793,
    "device_idle_share": 0.2615558, "plan_build_s": 18.5, "graph_s": 6.9,
    "compile_s": 1.5,
}


@pytest.mark.parametrize("name", sorted(RECORDED_VALUES))
def test_recorded_metric(traced_run, name):
    run, specs = traced_run
    spec = next(s for s in specs if s["name"] == name)
    assert layer_metrics.read(run, spec) == pytest.approx(
        RECORDED_VALUES[name], rel=1e-6)


def test_roofline_share_names_the_headroom(traced_run):
    run, specs = traced_run
    spec = next(s for s in specs if s["name"] == "agg_roofline")
    # 35.1 ms of HBM traffic at 819 GB/s against 1739 ms measured
    assert layer_metrics.read(run, spec) == pytest.approx(2.016, abs=0.005)


def test_device_times_add_up_to_the_busy_time(traced_run):
    """Self times partition the busy time: the scopes that claim to split
    it, the collectives and the rest add up to the traced epochs' busy
    time (the acceptance bound is 2 %; by construction it is exact)."""
    run, specs = traced_run
    parts = [s for s in specs if s["reduce"] == "ms_per_epoch"
             and (s.get("partition") or s["source"] == "device_rest")]
    assert {s["name"] for s in parts} == {"agg_p1_ms", "agg_p2_ms",
                                          "agg_copy_ms", "dense_ms"}
    total = sum(layer_metrics.read(run, s) for s in parts)
    busy = trace_reduce.busy_ns(run.epoch_ops[0]) / 1e6 / 3
    assert total == pytest.approx(busy, rel=1e-9)
    assert busy == pytest.approx(1921.9335737, rel=1e-6)


def test_every_per_layer_metric_of_the_cell_reads_the_recorded_trace(
        traced_run):
    run, specs = traced_run
    assert len(specs) == 10
    for spec in specs:
        assert layer_metrics.read(run, spec) is not None, spec["name"]


def test_the_index_copies_all_run_inside_the_scans(recorded):
    """`agg_copy_ms` claims `copy` instructions of s32 arrays only inside a
    `while` (the scan over bin groups); in the recorded epoch every one of
    them is, 96 an epoch, and the f32 copies outside are the rest's."""
    (first, *_), = [recorded.windows("bench.epoch")]
    ops = trace_reduce.clip(recorded.devices[0], [first])
    s32 = trace_reduce.select(ops, r"^copy(\.\d+)? copy s32\[")
    assert len(s32) == 96
    assert all(o.inside.startswith("while.") for o in s32)
    assert {o.inside.split(" ")[1] for o in s32} == {"while"}
    loose = [o for o in ops if o.opcode == "copy" and not o.inside]
    assert loose and all("f32[" in o.scope for o in loose)


@pytest.mark.parametrize("backend, raises", [
    ("binned", True), ("matmul", False), (None, False)])
def test_a_kernel_the_backend_needs_must_be_in_the_trace(recorded, backend,
                                                         raises):
    """Kernels are found by the jitted function's name; after a rename in
    the program nothing would match and the time would fall to `dense_ms`
    with `correct` still true.  A scope marked `required_for_backend`
    fails the traced run instead, when that backend was resolved."""
    spec = {"name": "agg_p1_ms", "source": "device_scope", "partition": True,
            "match": r"^_p1_renamed_run(\.\d+)? custom-call",
            "required_for_backend": "binned", "reduce": "ms_per_epoch"}
    run = layer_metrics.TracedRun(recorded, [spec], {}, {},
                                  {**SHAPES, "backend": backend},
                                  "TPU v5 lite")
    if raises:
        with pytest.raises(ValueError, match="renamed or did not run"):
            layer_metrics.read(run, spec)
    else:
        assert layer_metrics.read(run, spec) == 0.0
    found = dict(spec, match=r"^_p1(_flat)?_run(\.\d+)? custom-call")
    assert layer_metrics.read(run, found) > 0


def test_breakdown_names_operations_and_gaps(recorded):
    (w,) = recorded.windows("bench.window")
    ops = trace_reduce.clip(recorded.devices[0], [w])
    top = trace_reduce.top_ops(ops, 10)
    assert len(top) == 10 and top[0][0].startswith("_p1_flat_run")
    assert top[0][1] == pytest.approx(1.218954296)
    assert all(len(n) < 100 for n, _ in top)
    gaps = trace_reduce.idle_gaps(ops, w, recorded.annotations, 5)
    assert len(gaps) == 5 and gaps[0][1] >= gaps[-1][1] > 0
    assert {n for n, _ in gaps} <= {"bench.epoch", "bench.eval",
                                    "bench.window"}


def test_nothing_to_read_returns_nothing():
    run = layer_metrics.TracedRun(None, [], {}, {}, SHAPES, "TPU v5 lite")
    for spec in (
            {"name": "a", "source": "device_scope", "match": ".",
             "reduce": "ms_per_epoch"},
            {"name": "b", "source": "device_rest", "reduce": "ms_per_epoch"},
            {"name": "c", "source": "device_collective", "part": "exposed",
             "reduce": "ms_per_epoch"},
            {"name": "d", "source": "device_idle",
             "reduce": "share_of_window"},
            {"name": "e", "source": "annotation_gap",
             "annotation": "bench.epoch", "reduce": "ms"},
            {"name": "f", "source": "host_span", "spans": ["plan_build"],
             "reduce": "seconds"},
            {"name": "g", "source": "counter", "counter": "graph_s",
             "reduce": "value"}):
        assert layer_metrics.read(run, spec) is None, spec["name"]


# ---- hand-made instruction lists -----------------------------------------

def _ops(*rows):
    ops = [make_op(text, float(start), float(dur))
           for text, start, dur in rows]
    ops.sort(key=lambda o: (o.start, -o.dur))
    trace_reduce._self_times(ops)
    return ops


def test_self_time_of_nested_events():
    ops = _ops(("%while.1 = (s32[]) while((s32[]) %t), body=%b", 0, 100),
               ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 10, 30),
               ("%_p2_run.3 = f32[8,128]{1,0:T(8,128)} custom-call(f32[8] "
                "%x), custom_call_target=\"tpu_custom_call\"", 50, 40),
               ("%copy.2 = f32[8]{0} copy(f32[8]{0} %q)", 120, 5))
    by = {o.name: o for o in ops}
    assert by["while.1"].self_dur == 30 and by["fusion.1"].self_dur == 30
    assert by["_p2_run.3"].opcode == "custom-call"
    assert sum(o.self_dur for o in ops) == trace_reduce.busy_ns(ops) == 105
    assert trace_reduce.scope_ns(ops, r"^_p2_run") == 40
    assert trace_reduce.scope_ns(ops, r".", exclude=r"custom-call") == 65
    # what runs inside the while knows it; the copy after it does not
    assert by["fusion.1"].inside == "while.1 while (s32[])"
    assert by["copy.2"].inside == "" and by["while.1"].inside == ""
    assert trace_reduce.scope_ns(ops, r".", inside=r"^while") == 70
    assert trace_reduce.scope_ns(ops, r" copy ", inside=r"^while") == 0


def test_collectives_in_flight_and_exposed():
    """A synchronous all-reduce is exposed whole.  An asynchronous
    all-to-all is in flight from its -start to the end of its -done, and
    exposed only while those two hold the instruction stream."""
    ops = _ops(("%all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %g)", 0, 10),
               ("%all-to-all-start.2 = (f32[4]) all-to-all-start(f32[4] %x)",
                20, 2),
               ("%fusion.7 = f32[4]{0} fusion(f32[4]{0} %y)", 22, 50),
               ("%all-to-all-done.2 = f32[4]{0} all-to-all-done((f32[4]) "
                "%all-to-all-start.2)", 72, 8))
    in_flight, exposed = trace_reduce.collective_ns(ops)
    assert exposed == 10 + 2 + 8
    assert in_flight == 10 + (80 - 20)
    # with the trace's async line the span is read from there
    span = [make_op("%all-to-all-start.2 = (f32[4]) all-to-all-start(f32[4] "
                    "%x)", 20.0, 60.0),
            make_op("%copy-start.1 = (f32[4]) copy-start(f32[4] %x)", 0.0,
                    90.0)]
    assert trace_reduce.collective_ns(ops, span) == (70.0, 20.0)
    assert [o.name for o in ops if trace_reduce.is_collective(o)] == [
        "all-reduce.1", "all-to-all-start.2", "all-to-all-done.2"]
    # bare names, as a CPU trace prints them
    assert trace_reduce.is_collective(make_op("all-to-all.3", 0.0, 1.0))
    assert not trace_reduce.is_collective(make_op("fusion.3", 0.0, 1.0))


def test_rest_leaves_out_collectives_and_claimed_scopes():
    ops = _ops(("%_p1_run.1 = f32[8] custom-call(f32[8] %x)", 0, 40),
               ("%all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %g)", 40, 10),
               ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 50, 25))
    trace = Trace({0: ops}, [("bench.epoch", 0.0, 100.0),
                             ("bench.window", 0.0, 100.0)], {0: []})
    p1 = {"name": "p1", "source": "device_scope", "partition": True,
          "match": r"^_p1(_flat)?_run", "reduce": "ms_per_epoch"}
    rest = {"name": "rest", "source": "device_rest",
            "reduce": "ms_per_epoch"}
    exposed = {"name": "x", "source": "device_collective", "part": "exposed",
               "across": "max", "reduce": "ms_per_epoch"}
    gap = {"name": "gap", "source": "annotation_gap",
           "annotation": "bench.epoch", "reduce": "ms"}
    idle = {"name": "idle", "source": "device_idle",
            "reduce": "share_of_window"}
    run = layer_metrics.TracedRun(trace, [p1, rest, exposed], {}, {},
                                  SHAPES, "TPU v5 lite")
    assert layer_metrics.read(run, p1) == pytest.approx(40e-6)
    assert layer_metrics.read(run, rest) == pytest.approx(25e-6)
    assert layer_metrics.read(run, exposed) == pytest.approx(10e-6)
    assert layer_metrics.read(run, gap) == pytest.approx(25e-6)
    assert layer_metrics.read(run, idle) == pytest.approx(25.0)
    clash = dict(p1, name="again")
    run = layer_metrics.TracedRun(trace, [p1, clash, rest], {}, {}, SHAPES,
                                  "TPU v5 lite")
    with pytest.raises(ValueError, match="may not overlap"):
        layer_metrics.read(run, rest)


def test_idle_gaps_take_the_innermost_annotation():
    ops = _ops(("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 10, 20),
               ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)", 60, 20))
    notes = [("bench.window", 0.0, 100.0), ("bench.epoch", 5.0, 30.0),
             ("bench.eval", 85.0, 10.0)]
    gaps = trace_reduce.idle_gaps(ops, (0.0, 100.0), notes, 5)
    assert gaps == [["bench.window", 30e-9], ["bench.eval", 20e-9],
                    ["bench.epoch", 10e-9]]
    assert trace_reduce.idle_gaps(ops, (0.0, 200.0), notes, 1) == [
        ["outside bench.*", 120e-9]]
