"""What the sharded trainer says about its partition and its exchange: the
`# exchange:` start-up line, the `exchange` record and its gauges under
-obs, the reason the aggregation backend is what it is, and the set-up
spans a four-part run records (siblings, each with `parts`)."""

import numpy as np
import pytest

from roc_tpu import obs
from roc_tpu.graph import datasets
from roc_tpu.models import build_gcn
from roc_tpu.obs import channel
from roc_tpu.obs import report as obs_report
from roc_tpu.ops.pallas import binned
from roc_tpu.parallel.spmd import SpmdTrainer
from roc_tpu.train import driver
from roc_tpu.train.config import Config

PARTS = 4
SETUP_SPANS = ["partition", "halo_build", "plan_build", "place_data",
               "step_build"]


def _dataset(n=400):
    return datasets.synthetic("t", n, 4.0, 16, 4, n_train=100, n_val=50,
                              n_test=50, seed=3)


def _config(**kw):
    base = dict(layers=[16, 16, 16, 4], num_epochs=1, num_parts=PARTS,
                eval_every=10**9, dropout_rate=0.0,
                aggregate_backend="matmul")
    base.update(kw)
    return Config(**base)


def _trainer(ds, **kw):
    cfg = _config(**kw)
    return SpmdTrainer(cfg, ds, build_gcn(cfg.layers, cfg.dropout_rate))


def _line(capsys):
    lines = [ln for ln in capsys.readouterr().err.splitlines()
             if ln.startswith("# exchange:")]
    assert len(lines) == 1, lines
    return lines[0]


def _edge_cut(ds, part):
    """Live edges whose source another part owns, counted from the graph
    and the partition's bounds alone."""
    g = ds.graph
    dst = np.repeat(np.arange(g.num_nodes), np.diff(g.row_ptr))
    owner = np.zeros(g.num_nodes, np.int64)
    for p, (lo, hi) in enumerate(np.asarray(part.bounds)):
        owner[lo:hi + 1] = p
    return int(np.count_nonzero(owner[g.col_idx] != owner[dst]))


def test_halo_run_says_what_it_exchanges(capsys):
    ds = _dataset()
    tr = _trainer(ds)
    info = tr.exchange_info()
    part, K = tr.part, tr.halo.K
    S = part.shard_nodes
    assert list(info) == [
        "mode", "parts", "rows_per_epoch", "bytes_per_epoch",
        "halo_rows_per_peer", "halo_fraction", "edge_cut_share",
        "padded_max_tax", "shard_edges_live_min", "shard_edges_live_max",
        "agg_chunks_fwd", "agg_slot_fill_fwd", "agg_chunks_bwd",
        "agg_slot_fill_bwd", "agg_backend", "agg_backend_reason"]
    assert (info["mode"], info["parts"], info["halo_rows_per_peer"]) == (
        "halo", PARTS, K)
    # three aggregations at widths 16, 16, 4; each exchanges P x K rows a
    # device forward and the same backward, float32 on the wire
    assert tr._aggregate_widths() == [16, 16, 4]
    assert info["rows_per_epoch"] == 2 * 3 * PARTS * K
    assert info["bytes_per_epoch"] == 2 * PARTS * K * (16 + 16 + 4) * 4
    assert info["bytes_per_epoch"] == 2 * channel.wire_bytes_per_step(
        "halo", PARTS, S, [16, 16, 4], send_cols=K)
    assert info["halo_fraction"] == pytest.approx(
        PARTS * K / (S + PARTS * K))
    live = np.asarray(part.num_edges_valid)
    assert info["shard_edges_live_min"] == live.min()
    assert info["shard_edges_live_max"] == live.max()
    assert info["padded_max_tax"] == pytest.approx(
        part.shard_edges * PARTS / live.sum() - 1.0)
    assert info["edge_cut_share"] == pytest.approx(
        _edge_cut(ds, part) / live.sum())
    assert 0.0 < info["edge_cut_share"] < 1.0
    # ONE plan set over the combined table: what its placed arrays hold
    for d in ("fwd", "bwd"):
        parts, chunks, slots = getattr(tr.gdata.plans, d + "_esrc").shape
        assert parts == PARTS and info["agg_chunks_" + d] == chunks
        assert info["agg_slot_fill_" + d] == pytest.approx(
            live.max() / (chunks * slots))
        assert 0.0 < info["agg_slot_fill_" + d] <= 1.0
    assert (info["agg_backend"], info["agg_backend_reason"]) == (
        "matmul", "-aggr-backend=matmul")
    line = _line(capsys)
    assert line == obs_report.exchange_line(info)
    assert line.startswith(f"# exchange: mode=halo parts={PARTS} "
                           f"rows_per_epoch={info['rows_per_epoch']} ")
    assert f" halo_rows_per_peer={K} " in line
    assert (f" agg_chunks_fwd={info['agg_chunks_fwd']} "
            f"agg_slot_fill_fwd={info['agg_slot_fill_fwd']:.4f} "
            f"agg_chunks_bwd={info['agg_chunks_bwd']} "
            f"agg_slot_fill_bwd={info['agg_slot_fill_bwd']:.4f} "
            "agg_backend=matmul (-aggr-backend=matmul)") in line
    assert line.endswith(" agg_backend=matmul (-aggr-backend=matmul)")


def test_a_backend_without_plans_reports_no_chunks(capsys):
    info = _trainer(_dataset(), aggregate_backend="xla").exchange_info()
    assert info["agg_backend"] == "xla"
    assert not [k for k in info if k.startswith(("agg_chunks", "agg_slot"))]
    line = _line(capsys)
    assert "agg_chunks" not in line and "agg_slot_fill" not in line
    assert line.endswith(" agg_backend=xla (-aggr-backend=xla)")


def test_the_binned_backend_counts_its_phase_one_chunks():
    tr = _trainer(_dataset(), aggregate_backend="binned")
    info = tr.exchange_info()
    live = int(np.asarray(tr.part.num_edges_valid).max())
    for d in ("fwd", "bwd"):
        parts, groups, chunks, slots = getattr(tr.gdata.plans,
                                               d).p1_srcl.shape
        assert parts == PARTS
        assert info["agg_chunks_" + d] == groups * chunks
        assert info["agg_slot_fill_" + d] == pytest.approx(
            live / (groups * chunks * slots))


def test_bf16_wire_halves_the_bytes_not_the_rows():
    ds = _dataset()
    plain = _trainer(ds).exchange_info()
    half = _trainer(ds, bf16_storage=True).exchange_info()
    assert half["rows_per_epoch"] == plain["rows_per_epoch"]
    assert half["bytes_per_epoch"] * 2 == plain["bytes_per_epoch"]


@pytest.mark.parametrize("exchange", ["allgather", "ring"])
def test_the_other_vertex_exchanges_count_their_own_rows(exchange, capsys):
    ds = _dataset()
    tr = _trainer(ds, halo=exchange != "allgather", exchange=exchange)
    info = tr.exchange_info()
    S = tr.part.shard_nodes
    assert info["mode"] == exchange
    assert info["halo_rows_per_peer"] == 0 and info["halo_fraction"] == 0.0
    per_round = S if exchange == "allgather" else (PARTS - 1) * S
    assert info["rows_per_epoch"] == 2 * 3 * per_round
    # the cut is the partition's, whatever carries the rows
    assert info["edge_cut_share"] == pytest.approx(
        _edge_cut(ds, tr.part) / np.asarray(tr.part.num_edges_valid).sum())
    assert f"mode={exchange} " in _line(capsys)


def test_edge_sharding_has_no_vertex_cut_to_report(capsys):
    ds = _dataset()
    info = _trainer(ds, edge_shard="on").exchange_info()
    assert info["mode"] == "edge" and info["edge_cut_share"] is None
    line = _line(capsys)
    assert "mode=edge " in line and "edge_cut_share" not in line


def test_single_device_runs_print_no_exchange_line(capsys):
    ds = _dataset()
    cfg = Config(layers=[16, 16, 4], eval_every=10**9)
    driver.Trainer(cfg, ds, build_gcn(cfg.layers, 0.5))
    assert "# exchange" not in capsys.readouterr().err


def test_record_gauges_and_report(tmp_path):
    ds = _dataset()
    tr = _trainer(ds, obs=True, obs_dir=str(tmp_path / "obs"))
    info = tr.exchange_info()
    tr.train(print_fn=lambda *a, **k: None)
    recs = obs.load_jsonl(str(tmp_path / "obs" / "metrics.jsonl"))
    rec, = [r for r in recs if r["type"] == "exchange"]
    assert {k: rec[k] for k in info} == info
    prom = (tmp_path / "obs" / "metrics.prom").read_text()
    for name in ("exchange_rows_per_epoch", "exchange_bytes_per_epoch",
                 "halo_rows_per_peer", "halo_fraction", "edge_cut_share",
                 "padded_max_tax", "shard_edges_live_min",
                 "shard_edges_live_max", "agg_chunks_fwd", "agg_chunks_bwd",
                 "agg_slot_fill_fwd", "agg_slot_fill_bwd"):
        assert f"roc_{name} " in prom, name
    assert 'roc_exchange_mode{mode="halo"} 1' in prom
    assert ('roc_agg_backend{backend="matmul",'
            'reason="-aggr-backend=matmul"} 1') in prom
    # the epoch records' wire bytes are the forward half of the record's
    last = [r for r in recs if r["type"] == "metrics"][-1]
    assert 2 * last["wire_bytes"] == info["bytes_per_epoch"]
    text = obs_report.report(str(tmp_path / "obs" / "trace.json"),
                             str(tmp_path / "obs" / "metrics.jsonl"))
    assert obs_report.exchange_line(info) in text


def test_setup_spans_are_siblings_and_carry_parts():
    """`partition`, `halo_build`, `plan_build`, `place_data`, `step_build`
    on the sharded path: one thread, none inside another or inside any
    other span, each with `parts`, so their sum is a share of the phase
    the benchmark times around `make_trainer`."""
    ds = _dataset()
    was = obs.enabled()
    obs.enable(True)
    obs.get_tracer().clear()
    try:
        driver.make_trainer(_config(), ds, build_gcn([16, 16, 16, 4], 0.0))
        spans = obs.get_tracer().spans()
    finally:
        obs.get_tracer().clear()
        obs.enable(was)
    mine = sorted((s for s in spans if s.name in SETUP_SPANS),
                  key=lambda s: s.start_ns)
    assert [s.name for s in mine] == SETUP_SPANS
    assert {s.depth for s in mine} == {0} and len({s.tid for s in mine}) == 1
    for a, b in zip(mine, mine[1:]):
        assert a.start_ns + a.dur_ns <= b.start_ns, (a.name, b.name)
    assert all(s.args.get("parts") == PARTS for s in mine)


def test_a_reshard_announces_its_partition_again(capsys):
    """The line is the partition's: a reshard prints it again (here onto
    the same cut, so with the same numbers)."""
    ds = _dataset()
    tr = _trainer(ds, halo=True)
    before = _line(capsys)
    tr.reshard(np.asarray(tr.part.bounds, np.int64))
    assert _line(capsys) == before


# -- why the backend is what it is ------------------------------------------

def test_the_viability_test_says_what_it_decided_on():
    # a products-size shard: 612,258 rows, 908,000 table rows, 31.2 M edges
    ok, why = binned.binned_viable_why(612258, 908000, 31_200_000)
    assert not ok and binned.binned_viable(612258, 908000, 31_200_000) is ok
    bins, blocks = -(-612258 // binned.RB), -(-908000 // binned.SB)
    assert why == (f"occupancy:{31_200_000 / (bins * blocks):.1f}"
                   f"_edges_a_cell_<_102.4(bins={bins},blocks={blocks},"
                   f"edges=31200000)")
    # the Reddit shape on one chip
    ok, why = binned.binned_viable_why(232965, 232965, 23_394_167)
    assert ok and why.startswith("occupancy:112.5_edges_a_cell_>=_102.4(")
    assert " " not in why


def test_the_policy_answers_with_the_flag_or_the_test_that_decided(
        monkeypatch):
    """One function decides and says why: resolve_backend is its first
    half, so the reason cannot drift from the choice."""
    why_of = driver.resolve_backend_why
    assert why_of("binned", 10) == ("binned", "-aggr-backend=binned")
    assert why_of("pallas", 10) == ("binned", "-aggr-backend=pallas")
    assert why_of("auto", 10, 10, 10) == ("xla", "auto:no_tpu")
    monkeypatch.setattr(driver, "on_tpu", lambda: True)
    assert why_of("auto", 10, 10, 10) == (
        "xla", f"auto:edges<{driver.AUTO_MATMUL_EDGES}")
    assert why_of("auto", 1 << 21) == ("matmul", "auto:no_row_count")
    backend, why = why_of("auto", 31_200_000, 612258, 908000)
    assert backend == "matmul"
    assert why.startswith("auto:occupancy:") and "_<_102.4(" in why
    backend, why = why_of("auto", 23_394_167, 232965, 232965)
    assert backend == "binned" and "_>=_102.4(" in why
    monkeypatch.setattr(driver, "AUTO_BINNED", False)
    assert why_of("auto", 23_394_167, 232965, 232965) == (
        "matmul", "auto:AUTO_BINNED_off")
    for args in (("auto", 1 << 21), ("auto", 23_394_167, 232965, 232965),
                 ("xla", 5), ("pallas", 5)):
        assert driver.resolve_backend(*args) == why_of(*args)[0]


def test_a_model_with_no_sum_or_avg_aggregate_says_so(monkeypatch):
    from roc_tpu.models import build_model
    monkeypatch.setattr(driver, "on_tpu", lambda: True)
    monkeypatch.setattr(driver, "AUTO_MATMUL_EDGES", 1)
    ds = _dataset()
    cfg = _config(aggregate_backend="matmul")
    gat = build_model("gat", [ds.features.shape[1], 4, ds.num_classes], 0.0,
                      heads=2)
    assert driver.effective_backend_why(cfg, ds, gat) == (
        "xla", "model_has_no_sum_or_avg_aggregate")
    gcn = build_model("gcn", [ds.features.shape[1], 4, ds.num_classes], 0.0)
    assert driver.effective_backend_why(cfg, ds, gcn) == (
        "matmul", "-aggr-backend=matmul")
    assert driver.effective_backend(cfg, ds, gat) == "xla"


def test_auto_on_a_tpu_is_asked_again_on_the_shard(monkeypatch, capsys):
    """Where `auto` would take a plan backend (a TPU, past 2**20 edges) and
    the whole graph fails the occupancy test, the sharded trainer asks it
    again with a shard's rows, its table's rows and its fullest live edge
    count, and says what that answered.  (Bins and blocks of 8 rows make
    the toy graph as sparse a grid as the products shape is at 512.)"""
    monkeypatch.setattr(driver, "on_tpu", lambda: True)
    monkeypatch.setattr(driver, "AUTO_MATMUL_EDGES", 1)
    monkeypatch.setattr(binned, "RB", 8)
    monkeypatch.setattr(binned, "SB", 8)
    ds = _dataset()
    g = ds.graph
    assert not binned.binned_viable(g.num_nodes, g.num_nodes, g.num_edges)
    tr = _trainer(ds, aggregate_backend="auto")
    info = tr.exchange_info()
    S, K = tr.part.shard_nodes, tr.halo.K
    live_max = int(np.asarray(tr.part.num_edges_valid).max())
    ok, why = binned.binned_viable_why(S, S + PARTS * K, live_max)
    assert not ok and f"edges={live_max})" in why
    assert info["agg_backend"] == "matmul"
    assert info["agg_backend_reason"] == "auto:" + why
    assert _line(capsys).endswith(f" agg_backend=matmul (auto:{why})")


# -- what the memory planner is told of a sharded step ------------------------

def test_the_planner_prices_the_shards_plans():
    """One device's share of every plan set is in the estimate's fixed
    bytes; the exchange's blocks are the step's temporaries and are not."""
    import jax
    from roc_tpu.memory import estimator
    ds = _dataset()
    tr = _trainer(ds)                   # matmul: one plan set a shard
    gd, part = tr.gdata, tr.part
    plans = sum(int(a.size) * 4 for a in jax.tree.leaves(gd.plans))
    assert estimator.plan_bytes(gd) == plans > 0
    bare = estimator.fixed_bytes_for(tr.model, part.shard_nodes, ds.in_dim,
                                     ds.num_classes, part.shard_edges)
    assert tr.mem_estimate.fixed_bytes == bare + plans // PARTS
    # no plans, no exchange: nothing added
    one = driver.Trainer(Config(layers=[16, 16, 4], eval_every=10**9,
                                aggregate_backend="xla"), ds,
                         build_gcn([16, 16, 4], 0.5))
    assert one.mem_estimate.fixed_bytes == estimator.fixed_bytes_for(
        one.model, ds.graph.num_nodes, ds.in_dim, ds.num_classes,
        ds.graph.num_edges)


def test_the_fixed_bytes_of_a_products_shard_are_the_compilers_arguments():
    """Held to a number that was read, not modelled: for the gcn-products.p4
    train step on a described v5e 2x2 the compiler counts 1,126,184,960
    bytes of arguments a chip (the cell's own trainer lowered for the
    topology, PR 30).  jit
    drops the two edge arrays the plan backends never read, so they are
    resident and not arguments.  The chunk counts are the cell's own
    (`# exchange:` agg_chunks_fwd / agg_chunks_bwd): 163,656 forward and
    198,680 backward over the combined table, of EB slots twice over plus
    a window index and a first-chunk flag."""
    from roc_tpu.memory import estimator
    from roc_tpu.ops.pallas.segment_sum import EB
    rows, edges = 612_864, 31_150_848
    chunks = 163_656 + 198_680
    plans = chunks * (2 * EB + 2) * 4
    fixed = estimator.fixed_bytes_for(
        build_gcn([100, 256, 256, 47], 0.5), rows, 100, 47, edges) + plans
    arguments, unread_edge_arrays = 1_126_184_960, edges * 2 * 4
    assert abs(fixed / (arguments + unread_edge_arrays) - 1) < 0.02
    # without the plans the planner saw under half of it
    assert (fixed - plans) / (arguments + unread_edge_arrays) < 0.45
