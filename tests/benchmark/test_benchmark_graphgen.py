"""The generator: determinism, the printed degree statistics of every
recipe, and the structure each recipe promises."""

import glob
import os

import numpy as np
import pytest

from benchmark import graphgen
from benchmark import manifest as mf

RECIPES = sorted(
    glob.glob(os.path.join(mf.ROOT, "benchmark", "traffic", "*.json"))
    + glob.glob(os.path.join(mf.ROOT, "benchmark", "rehearsal", "traffic",
                             "*.json")))
IDS = [os.path.relpath(p, os.path.join(mf.ROOT, "benchmark")) for p in RECIPES]
SMALL = 6000    # nodes the real recipes are cut to here; the law is kept


def _small(path):
    r = graphgen.load_recipe(path)
    if r["nodes"] > SMALL:
        scale = SMALL / r["nodes"]
        r = dict(r, nodes=SMALL, splits={
            k: max(1, int(v * scale)) for k, v in r["splits"].items()})
    return r


@pytest.mark.parametrize("path", RECIPES, ids=IDS)
def test_same_seed_same_dataset(path):
    r = _small(path)
    a = graphgen.generate(r, 16, 5, seed=3)
    b = graphgen.generate(r, 16, 5, seed=3)
    c = graphgen.generate(r, 16, 5, seed=4)
    assert np.array_equal(a.graph.row_ptr, b.graph.row_ptr)
    assert np.array_equal(a.graph.col_idx, b.graph.col_idx)
    assert np.array_equal(a.features, b.features)
    assert np.array_equal(a.label_ids, b.label_ids)
    assert np.array_equal(a.mask, b.mask)
    # a recipe with structure_seed keeps its edges whatever the seed
    assert np.array_equal(a.graph.col_idx[:1000], c.graph.col_idx[:1000]) \
        == ("structure_seed" in r)
    assert not np.array_equal(a.features, c.features)
    assert not np.array_equal(a.mask, c.mask)


@pytest.mark.parametrize("path", RECIPES, ids=IDS)
def test_graph_is_what_the_program_expects(path):
    r = _small(path)
    ds = graphgen.generate(r, 16, 5, seed=1)
    g = ds.graph
    g.validate()
    n = g.num_nodes
    assert n == r["nodes"] and ds.features.shape == (n, 16)
    assert ds.features.dtype == np.float32 and ds.labels is None
    assert ds.onehot_labels().shape == (n, 5)
    dst = np.repeat(np.arange(n), np.diff(g.row_ptr))
    src = g.col_idx.astype(np.int64)
    # one self-edge per vertex, so no zero in-degree row
    assert int((src == dst).sum()) == n
    # parallel edges merged
    assert np.unique(src * n + dst).size == g.num_edges
    # symmetrised: the reversed edge list is the same set
    assert np.array_equal(np.sort(src * n + dst), np.sort(dst * n + src))
    counts = np.bincount(ds.mask, minlength=4)
    assert counts[0] == r["splits"]["train"]
    assert counts[1] == r["splits"]["val"]
    assert counts[2] == r["splits"]["test"]
    # the split is scattered: every quarter of the ids holds train rows
    assert all((ds.mask[q] == 0).any() for q in np.array_split(
        np.arange(n), 4))


@pytest.mark.parametrize("path", RECIPES, ids=IDS)
def test_degree_statistics_are_printed_and_fit_the_law(path):
    r = _small(path)
    st = graphgen.degree_stats(graphgen.generate(r, 8, 5, seed=1).graph)
    assert set(st) == {"nodes", "in_edges", "in_degree_min",
                       "in_degree_median", "in_degree_p99", "in_degree_max"}
    assert st["in_degree_min"] >= 1
    mean = st["in_edges"] / st["nodes"]
    # both directions and the self-edge, less the parallel edges merged
    # (many at this cut size, where a community has few members)
    assert 0.6 * (2 * r["avg_degree"] + 1) < mean <= 2 * r["avg_degree"] + 1
    if r["degree_law"] == "power":
        # hubs: the largest in-degree is far above the median
        assert st["in_degree_max"] > 4 * st["in_degree_median"]
        assert st["in_degree_median"] < mean
    else:
        # near-regular: Poisson tails only
        assert st["in_degree_max"] < 3 * st["in_degree_median"]


def test_structure_seed_fixes_the_edges_and_leaves_the_rest_to_the_seed():
    """What keeps the program's shapes, and so its compile and plan
    caches, the same from run to run: the cells' recipes draw communities
    and edges from `structure_seed`, features and splits from --seed."""
    r = _small(os.path.join(mf.ROOT, "benchmark", "traffic",
                            "reddit-skewed.json"))
    assert r["structure_seed"] == 1
    a = graphgen.generate(r, 16, 5, seed=3)
    b = graphgen.generate(r, 16, 5, seed=4)
    assert np.array_equal(a.graph.row_ptr, b.graph.row_ptr)
    assert np.array_equal(a.graph.col_idx, b.graph.col_idx)
    assert np.array_equal(a.label_ids, b.label_ids)     # the communities
    assert not np.array_equal(a.features, b.features)
    assert not np.array_equal(a.mask, b.mask)
    # the structure is the one the seed of that number draws without the key
    free = {k: v for k, v in r.items() if k != "structure_seed"}
    c = graphgen.generate(free, 16, 5, seed=1)
    assert np.array_equal(a.graph.col_idx, c.graph.col_idx)
    d = graphgen.generate(dict(r, structure_seed=2), 16, 5, seed=3)
    assert not np.array_equal(a.graph.col_idx[:1000], d.graph.col_idx[:1000])


def test_contiguous_ring_layout_keeps_edges_near():
    """The promise of `layout: contiguous` with `inter: ring` (the
    rehearsal's four-chip recipe, at 47 communities): a cut into four id
    ranges leaves few edges crossing; the scattered regular recipe leaves
    most."""
    local = dict(graphgen.load_recipe(os.path.join(
        mf.ROOT, "benchmark", "rehearsal", "traffic", "tiny-local-p4.json")),
        nodes=SMALL, communities=47, avg_degree=25,
        splits={"train": 3000, "val": 600, "test": 1800})
    regular = _small(os.path.join(mf.ROOT, "benchmark", "traffic",
                                  "reddit-regular.json"))

    def crossing(r):
        g = graphgen.generate(r, 8, 47, seed=1).graph
        dst = np.repeat(np.arange(g.num_nodes), np.diff(g.row_ptr))
        quarter = g.num_nodes // 4 + 1
        return float(np.mean(g.col_idx // quarter != dst // quarter))

    assert crossing(local) < 0.10
    assert crossing(regular) > 0.60


def test_power_law_rank_is_uniform_at_skew_one():
    rng = np.random.default_rng(0)
    flat = graphgen.power_law_rank(rng, 200000, 100, 1.0)
    hubs = graphgen.power_law_rank(rng, 200000, 100, 2.5)
    assert np.bincount(flat, minlength=100).max() < 2600
    assert np.bincount(hubs, minlength=100)[0] > 20000
    assert hubs.max() <= 99 and hubs.min() == 0
    # one bound per draw
    per = graphgen.power_law_rank(rng, 1000, np.full(1000, 7), 2.0)
    assert per.max() <= 6


def test_recipe_errors_are_named(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text('{"nodes": 10, "avg_degree": 2}')
    with pytest.raises(ValueError, match="splits"):
        graphgen.load_recipe(str(p))
    p.write_text('{"nodes": 10, "avg_degree": 2, "splits": {}, '
                 '"degree_law": "zipf"}')
    with pytest.raises(ValueError, match="degree_law"):
        graphgen.load_recipe(str(p))
