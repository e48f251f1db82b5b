"""What a gat model adds to the program's own tracing: the `gat_plan_build`
span inside `plan_build`, the `attention` record and its gauges, the
trainer's start-up line, the planner's bytes for a gat op, and that the
fused kernel's binned plans are built only for a kernel that may run."""

import numpy as np
import pytest

from roc_tpu import obs
from roc_tpu.graph import datasets
from roc_tpu.memory import estimator
from roc_tpu.models import build_gat
from roc_tpu.obs import report as obs_report
from roc_tpu.train.config import Config
from roc_tpu.train.driver import Trainer


def _dataset(n=200):
    return datasets.synthetic("t", n, 4.0, 8, 4, n_train=30, n_val=30,
                              n_test=30, seed=3)


def _config(ds, **kw):
    base = dict(layers=[ds.in_dim, 8, ds.num_classes], num_epochs=1,
                eval_every=10**9, dropout_rate=0.6, model="gat", heads=2,
                aggregate_backend="matmul", weight_decay=0.0)
    base.update(kw)
    return Config(**base)


@pytest.fixture
def recording():
    was = obs.enabled()
    obs.enable(True)
    obs.get_tracer().clear()
    yield obs.get_tracer()
    obs.get_tracer().clear()
    obs.enable(was)


def test_gat_plan_build_is_a_span_inside_plan_build(recording):
    ds = _dataset()
    cfg = _config(ds)
    tr = Trainer(cfg, ds, build_gat(cfg.layers, 0.6, heads=2))
    spans = {s.name: s for s in recording.spans()}
    inner, outer = spans["gat_plan_build"], spans["plan_build"]
    assert inner.depth == outer.depth + 1 and inner.tid == outer.tid
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.dur_ns <= outer.start_ns + outer.dur_ns
    plans = tr.gdata.gat_plans
    e = ds.graph.num_edges
    assert inner.args["edges"] == e
    assert inner.args["chunks_dst"] == plans.dst_obi.shape[0]
    assert inner.args["chunks_src"] == plans.src_obi.shape[0]
    assert inner.args["slots"] == plans.dst_pos.size + plans.src_pos.size
    assert inner.args["pad_ratio"] == pytest.approx(
        inner.args["slots"] / (2 * e))
    assert inner.args["pad_ratio"] >= 1.0


def test_attention_record_gauges_and_start_up_line(tmp_path, capsys):
    ds = _dataset()
    cfg = _config(ds, obs=True, obs_dir=str(tmp_path / "obs"))
    tr = Trainer(cfg, ds, build_gat(cfg.layers, 0.6, heads=2))
    info = tr.attention_info()
    e = ds.graph.num_edges
    assert info["backend"] == "plan"
    assert list(info) == ["backend", "plan_pad_ratio", "score_bytes",
                          "dst_reads", "fwd_scans", "src_scans",
                          "short_scans"]
    # the forward walks the plans twice an op: su and the max's broadcast
    assert info["fwd_scans"] == 4
    # the backward walks the src-keyed plan once an op: dast rides
    # dtable's scan (two ops here)
    assert info["src_scans"] == 2
    # the plan path reads node tables by edge_dst through the dst plan
    assert info["dst_reads"] == "plan"
    # e float32 + the score's sign, [K, E] each: 2 heads, then 1
    assert info["score_bytes"] == (2 + 1) * e * 5
    line = next(ln for ln in capsys.readouterr().err.splitlines()
                if ln.startswith("# attention:"))
    assert (line.startswith("# attention: backend=plan ")
            and f"gat_score_bytes={info['score_bytes']}" in line)
    # the record's fields in its order (obs.report.attention_line); the
    # fixed text `gat_fused=False (no -megafuse)` went with PR 33
    assert line == ("# attention: backend=plan"
                    f" gat_plan_pad_ratio={info['plan_pad_ratio']:.4f}"
                    f" gat_score_bytes={info['score_bytes']}"
                    " gat_dst_reads=plan gat_fwd_scans=4 gat_src_scans=2"
                    " gat_short_scans=0")
    tr.train(print_fn=lambda *a, **k: None)
    recs = obs.load_jsonl(str(tmp_path / "obs" / "metrics.jsonl"))
    att, = [r for r in recs if r["type"] == "attention"]
    assert att["backend"] == "plan" and "fused" not in att
    assert att["gat_plan_pad_ratio"] == pytest.approx(info["plan_pad_ratio"])
    assert att["gat_score_bytes"] == info["score_bytes"]
    assert att["gat_dst_reads"] == "plan"
    assert att["gat_src_scans"] == 2 and list(att)[-2:] == [
        "gat_src_scans", "gat_short_scans"]
    prom = (tmp_path / "obs" / "metrics.prom").read_text()
    assert "roc_gat_src_scans 2" in prom            # unlabelled: a counter
    assert "roc_gat_fwd_scans 4" in prom
    assert "roc_gat_plan_pad_ratio " in prom and "roc_gat_score_bytes " in prom
    assert 'roc_gat_backend{backend="plan"} 1' in prom
    assert 'roc_gat_dst_reads{dst_reads="plan"} 1' in prom
    text = obs_report.report(str(tmp_path / "obs" / "trace.json"),
                             str(tmp_path / "obs" / "metrics.jsonl"))
    assert "# attention: backend=plan gat_plan_pad_ratio=" in text
    assert (f"gat_score_bytes={info['score_bytes']} gat_dst_reads=plan"
            " gat_fwd_scans=4 gat_src_scans=2 gat_short_scans=0") in text
    assert "gat_plan_build" in text


def test_the_xla_scans_still_gather_by_edge_dst(capsys):
    """`-aggr-backend xla` (the dense / chunked scans) indexes its node
    tables by edge_dst: the trainer says so."""
    ds = _dataset()
    cfg = _config(ds, aggregate_backend="xla")
    tr = Trainer(cfg, ds, build_gat(cfg.layers, 0.6, heads=2))
    info = tr.attention_info()
    assert (info["backend"], info["dst_reads"]) == ("xla", "gather")
    line = next(ln for ln in capsys.readouterr().err.splitlines()
                if ln.startswith("# attention:"))
    assert line.startswith("# attention: backend=xla ")
    assert line.endswith(
        " gat_dst_reads=gather gat_fwd_scans=0 gat_src_scans=0"
        " gat_short_scans=0")
    assert info["fwd_scans"] == info["src_scans"] == info["short_scans"] == 0


def test_models_without_attention_say_nothing(capsys):
    from roc_tpu.models import build_gcn
    ds = _dataset()
    cfg = Config(layers=[ds.in_dim, 8, ds.num_classes], eval_every=10**9)
    tr = Trainer(cfg, ds, build_gcn(cfg.layers, 0.5))
    assert tr.attention_info() is None
    assert "# attention" not in capsys.readouterr().err


def test_the_planner_sees_a_gat_ops_edge_residuals():
    """`e` and the score's sign are [K, E], edges on the lane axis: what the
    device holds is heads x edges x (4 + 1) bytes a gat op, not 16 x that.
    An all-KEEP step keeps them; a planned layer recomputes them with the
    layer, so only `bytes_full` carries them."""
    rows, edges, heads = 1000, 50000, 8
    model = build_gat([32, 8, 5], 0.6, heads=heads)
    est = estimator.estimate_model(model, rows, edges)
    # without the per-edge term: every op output, rows x width x 4
    dims = estimator._op_out_dims(model)
    plain = [sum(rows * dims[op.out] * 4 for op in model.ops
                 if op.attrs.get("layer", 0) == i) for i in (0, 1)]
    assert est.layers[0].bytes_full - plain[0] == heads * edges * 5
    assert est.layers[1].bytes_full - plain[1] == 1 * edges * 5
    assert est.layers[0].bytes_saved < est.layers[0].bytes_full - edges * 5
    gat0 = next(op for op in model.ops if op.kind == "gat")
    assert estimator.gat_edge_residual_bytes(gat0, edges) == heads * edges * 5
    assert estimator.gat_edge_residual_bytes(model.ops[0], edges) == 0


def test_the_trainers_estimate_counts_the_attention_plans():
    ds = _dataset()
    cfg = _config(ds)
    tr = Trainer(cfg, ds, build_gat(cfg.layers, 0.6, heads=2))
    plans = tr.gdata.gat_plans
    plan_bytes = sum(int(np.prod(a.shape)) * 4 for a in plans[:8])
    without = estimator.fixed_bytes_for(
        tr.model, ds.graph.num_nodes, ds.in_dim, ds.num_classes,
        ds.graph.num_edges)
    assert tr.mem_estimate.fixed_bytes == without + plan_bytes
