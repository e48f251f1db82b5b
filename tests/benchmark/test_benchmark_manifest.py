"""The manifest's static rules, the data files it points to, and that a new
cell is files and entries only."""

import copy
import json
import os

import pytest

from benchmark import graphgen
from benchmark import manifest as mf
from benchmark import measure, roofline

MANIFESTS = ["BENCHMARK.json", "benchmark/rehearsal/manifest.json"]


def _load(rel):
    return mf.load(os.path.join(mf.ROOT, rel))


@pytest.mark.parametrize("rel", MANIFESTS)
def test_manifest_has_no_problems(rel):
    assert mf.problems_in(_load(rel)) == []


def _broken(edit):
    m = copy.deepcopy(_load("BENCHMARK.json"))
    edit(m)
    return mf.problems_in(m)


BREACHES = {
    "unit_with_space": lambda m: m["end_to_end"][0].update(
        unit="edges per s"),
    "unit_over_16_characters": lambda m: m["end_to_end"][0].update(
        unit="edges/second/chip"),
    "greek_unit": lambda m: m["per_layer"][0].update(unit="µs"),
    "name_with_slash": lambda m: m["per_layer"][0].update(name="agg/p1"),
    "name_over_64": lambda m: m["per_layer"][0].update(name="a" * 65),
    "two_metrics_one_name": lambda m: m["per_layer"].append(
        dict(m["per_layer"][0])),
    "two_four_chip_cells_of_two": lambda m: [w.update(chips=4)
                                            for w in m["workloads"]],
    "chips_2": lambda m: m["workloads"][0].update(chips=2),
    "no_setup_s": lambda m: m["end_to_end"].pop(),
    "bound_over_a_tenth": lambda m: m["end_to_end"][0].update(bound=0.2),
    "bound_under_one_percent": lambda m: m["end_to_end"][0].update(
        bound=0.001),
    "moves_a_per_layer_metric": lambda m: m["per_layer"][0].update(
        moves="agg_p1_ms"),
    "why_on_a_metric": lambda m: m["per_layer"][0].update(why="because"),
    "end_to_end_from_a_program_span": lambda m: m["end_to_end"][0].update(
        source="program_span"),
    "config_without_a_cell": lambda m: m["configs"].append(
        dict(m["configs"][0], name="unused", file="benchmark/rehearsal/"
             "configs/tiny-gcn.json")),
    "pair_twice": lambda m: m["workloads"].append(
        dict(m["workloads"][0], name="again")),
    "one_cell": lambda m: m.update(workloads=m["workloads"][:1],
                                   configs=m["configs"][:1]),
    "run_seconds_52": lambda m: m.update(run_seconds=52),
    "absolute_path": lambda m: m.update(paths=["/root/benchmark"]),
    "command_outside_paths": lambda m: m.update(
        command=["python3", "tools/kernel_bench.py"]),
    "extra_top_level_key": lambda m: m.update(notes="x"),
    "metric_for_an_unknown_cell": lambda m: m["per_layer"][0].update(
        workloads=["nope"]),
    "why_of_201_characters": lambda m: m["workloads"][0].update(
        why="x" * 201),
    "better_sideways": lambda m: m["per_layer"][0].update(better="same"),
}


@pytest.mark.parametrize("breach", sorted(BREACHES))
def test_manifest_check_catches(breach):
    assert _broken(BREACHES[breach]), breach


@pytest.mark.parametrize("rel", MANIFESTS)
def test_every_cell_finds_its_files(rel):
    m = _load(rel)
    for w in m["workloads"]:
        conf = mf.load(os.path.join(
            mf.ROOT, mf.config_entry(m, w["config"])["file"]))
        assert conf["name"] == w["config"]
        for key in ("model", "layers", "learning_rate", "weight_decay",
                    "dropout", "eval_every", "precision", "reduced",
                    "assumed", "source"):
            assert key in conf, (w["name"], key)
        assert len(conf["source"]) <= 200
        recipe = graphgen.load_recipe(mf.traffic_path(m, w))
        assert recipe["nodes"] > 0
        e2e = {e["name"] for e in mf.metrics_for(m, "end_to_end", w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        per_layer = mf.metrics_for(m, "per_layer", w["name"])
        assert per_layer
        for e in per_layer:
            assert e["moves"] in e2e


def test_four_chip_share_allows_one_cell_always():
    """The rehearsal manifest has one four-chip cell of three; a second
    would be over a quarter."""
    m = _load("benchmark/rehearsal/manifest.json")
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    m["workloads"][0]["chips"] = 4
    assert any("four-chip" in p for p in mf.problems_in(m))


def test_config_sources_match_the_manifest():
    m = _load("BENCHMARK.json")
    for c in m["configs"]:
        conf = mf.load(os.path.join(mf.ROOT, c["file"]))
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]


@pytest.mark.parametrize("rel", MANIFESTS)
def test_layer_metric_files_agree_with_the_manifest(rel):
    m = _load(rel)
    from benchmark import layer_metrics
    for e in m["per_layer"]:
        spec = mf.layer_metric_spec(m, m["workloads"][0], e["name"])
        for key, theirs in (("name", "name"), ("unit", "unit"),
                            ("better", "better"), ("layer", "layer"),
                            ("moves", "moves"), ("source", "kind")):
            assert e[key] == spec[theirs], (e["name"], key)
        assert spec["source"] in layer_metrics.READERS
        if spec["reduce"] == "roofline_share":
            assert spec["shapes_fn"] in roofline.SHAPE_FUNCTIONS


def test_the_benchmark_ships_only_what_its_cells_read():
    """Every configuration, recipe and layer-metric file under benchmark/
    (the rehearsal's own directory apart) is named by BENCHMARK.json: a
    file for a cell that is not there comes with the PR that admits it."""
    m = _load("BENCHMARK.json")
    bench = os.path.join(mf.ROOT, "benchmark")

    def stems(sub):
        return {os.path.splitext(f)[0]
                for f in os.listdir(os.path.join(bench, sub))}

    assert stems("configs") == {c["name"] for c in m["configs"]}
    assert stems("traffic") == {w["traffic"] for w in m["workloads"]}
    assert stems("layer_metrics") == {e["name"] for e in m["per_layer"]}
    for c in m["configs"]:
        assert c["file"] == f"benchmark/configs/{c['name']}.json"


def test_layers_of_one_name_are_letter_for_letter():
    m = _load("BENCHMARK.json")
    layers = {e["layer"] for e in m["per_layer"]}
    # a near-duplicate (case, spacing) would split one layer in two
    assert len({" ".join(n.lower().split()) for n in layers}) == len(layers)


def test_check_budget_fits_with_24_cells():
    m = _load("BENCHMARK.json")
    runs = 2 + 14 * 24
    total = runs * (m["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_unknown_device_kind_has_no_peaks():
    assert roofline.peaks_for("TPU v5 lite")[0] == 197e12
    with pytest.raises(ValueError):
        roofline.peaks_for("TPU v9 imaginary")


def test_aggregation_sweeps_counts_bytes_and_flops():
    shapes = {"chips": 1, "nodes": 1000, "in_edges": 50000,
              "precision": "fast", "aggregate_widths": [256, 41]}
    flops, nbytes = roofline.aggregation_sweeps(shapes)
    assert flops == 2 * 2 * 50000 * (256 + 41)
    assert nbytes == 2 * ((50000 * 256 * 2 + 1000 * 256 * 4 + 50000 * 4)
                          + (50000 * 41 * 2 + 1000 * 41 * 4 + 50000 * 4))
    four = roofline.aggregation_sweeps({**shapes, "chips": 4})
    assert four[1] == pytest.approx(nbytes / 4)
    least, binds = roofline.least_seconds("aggregation_sweeps", shapes,
                                          "TPU v5 lite")
    assert binds == "bytes" and least == pytest.approx(nbytes / 819e9)


def test_spread_is_quartile_distance_over_median():
    assert measure.spread([10.0, 10.0, 10.0, 10.0]) == 0.0
    assert measure.spread([9.0, 10.0, 10.0, 11.0]) == pytest.approx(0.05)
    assert measure.spread([1.0]) != measure.spread([1.0])    # nan


def test_new_cell_is_files_and_entries_only(tmp_path):
    """A dummy configuration, recipe, cell and layer metric, added as new
    files and manifest entries beside untouched ones, is found by the same
    lookups the harness uses."""
    m = copy.deepcopy(_load("benchmark/rehearsal/manifest.json"))
    for sub in ("configs", "traffic", "layer_metrics"):
        (tmp_path / sub).mkdir()
    conf = mf.load(os.path.join(mf.ROOT, m["configs"][0]["file"]))
    conf.update(name="dummy-gin", model="gin", layers=[8, 8, 3])
    (tmp_path / "configs" / "dummy-gin.json").write_text(json.dumps(conf))
    (tmp_path / "traffic" / "dummy-ring.json").write_text(json.dumps(
        {"nodes": 300, "avg_degree": 3, "inter": "ring",
         "splits": {"train": 100, "val": 50, "test": 50},
         "job": {"reorder": "on"}}))
    (tmp_path / "layer_metrics" / "adam_ms.json").write_text(json.dumps(
        {"name": "adam_ms", "unit": "ms", "better": "lower",
         "layer": "linear, loss, Adam", "moves": "epoch_s",
         "kind": "device_trace", "source": "device_scope",
         "match": "roc_adam_update", "reduce": "ms_per_epoch"}))
    m["configs"].append({"name": "dummy-gin", "source": "a test",
                         "file": str(tmp_path / "configs" / "dummy-gin.json"),
                         "reduced": [], "why": "a test"})
    m["workloads"].append({"name": "dummy-gin.ring", "config": "dummy-gin",
                           "traffic": "dummy-ring", "chips": 1,
                           "why": "a test"})
    m["per_layer"].append({"name": "adam_ms", "unit": "ms",
                           "better": "lower", "source": "device_trace",
                           "layer": "linear, loss, Adam", "moves": "epoch_s",
                           "workloads": ["dummy-gin.ring"]})
    cell = mf.cell(m, "dummy-gin.ring")
    assert mf.traffic_path(m, cell) == str(
        tmp_path / "traffic" / "dummy-ring.json")
    assert graphgen.load_recipe(mf.traffic_path(m, cell))["inter"] == "ring"
    names = [e["name"] for e in mf.metrics_for(m, "per_layer", cell["name"])]
    assert "adam_ms" in names and "exchange_ms" not in names
    assert mf.layer_metric_spec(m, cell, "adam_ms")["match"] == \
        "roc_adam_update"
    # the metrics that were there are still found, in their own directory
    assert mf.layer_metric_spec(m, cell, "dense_ms")["source"] == \
        "device_rest"
    # and the older cells do not see the new metric
    old = [e["name"] for e in mf.metrics_for(m, "per_layer",
                                             m["workloads"][0]["name"])]
    assert "adam_ms" not in old
