"""The benchmark's own seeded graph generator: a traffic recipe -> Dataset.

For a full-graph trainer the traffic is the graph.  A recipe (one JSON file
under ``benchmark/traffic/``) gives the node count, the drawn degree, the
degree law, the community structure and the layout; ``--seed`` gives the
draws.  The same recipe and seed give the same graph, features, labels and
splits.  A recipe with ``structure_seed`` draws communities and edges from
that number instead, and only features and splits from ``--seed``: the
program's shapes (chunk counts, shard sizes) follow the drawn edges, so a
graph that moved with the seed made every run compile and plan anew, and
set-up measured which seeds had run before.  A dataset is one graph; what a
user varies from job to job is the initial weights and the dropout.  The
generator is a copy of the program's two generators joined
(`roc_tpu/graph/datasets.py:synthetic` for the communities, the symmetrised
edges, the self-edges, the class-informative features and the scattered
splits; `tools/make_giant.py:_power_law_dst` for the hub profile), kept
here so that a later PR cannot change the inputs it is measured on.

Recipe keys (all but the first two optional):

  nodes        number of vertices
  avg_degree   drawn edges per vertex, before symmetrising
  degree_law   "uniform" (default) | "power": where a drawn edge's
               destination lands among its candidates
  skew         power law only: rank = floor(n * u**skew) over the
               candidates, density ~ rank^(1/skew - 1); 1.0 is uniform
  communities  number of communities; 0 = one per class (default)
  p_intra      share of drawn edges whose destination is in the source's
               own community (default 0.8); the power law with
               ``layout: scattered`` ignores communities on the destination
  inter        "uniform" (default): the other edges land anywhere;
               "ring": in a neighbouring community on the ring
  layout       "scattered" (default): community members are spread over
               the id space; "contiguous": each community is one id range
               (what a partitioner that cuts id ranges can exploit)
  symmetrize   true (default): every drawn edge is stored both ways
  splits       {"train": n, "val": n, "test": n}, scattered over the ids
  feature_snr  class mean over unit noise (default 1.0)
  structure_seed  draw communities and edges from this, not from ``--seed``

Every vertex gets one self-edge (a zero in-degree row would put 1/sqrt(0)
into the GCN norm) and parallel edges are merged, as in a real edge list.
Cost at the Reddit shape (23.5 M in-edges): about 10 s on the chip's host,
nearly all of it random draws and one counting sort by destination
(`scipy.sparse` COO -> CSR, O(E)); there is no comparison sort.
"""

from __future__ import annotations

import json

import numpy as np

RECIPE_DEFAULTS = {
    "degree_law": "uniform", "skew": 1.0, "communities": 0, "p_intra": 0.8,
    "inter": "uniform", "layout": "scattered", "symmetrize": True,
    "feature_snr": 1.0,
}
RECIPE_REQUIRED = ("nodes", "avg_degree", "splits")


def load_recipe(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        raw = json.load(f)
    missing = [k for k in RECIPE_REQUIRED if k not in raw]
    if missing:
        raise ValueError(f"{path}: recipe lacks {missing}")
    recipe = {**RECIPE_DEFAULTS, **raw}
    if recipe["degree_law"] not in ("uniform", "power"):
        raise ValueError(f"{path}: degree_law uniform|power")
    if recipe["inter"] not in ("uniform", "ring"):
        raise ValueError(f"{path}: inter uniform|ring")
    if recipe["layout"] not in ("scattered", "contiguous"):
        raise ValueError(f"{path}: layout scattered|contiguous")
    return recipe


def power_law_rank(rng, count: int, n, skew: float) -> np.ndarray:
    """``count`` ranks in [0, n) with density ~ rank^(1/skew - 1): skew 1 is
    uniform, 2-3 the few-hot-hubs shape of social and co-purchase graphs.
    ``n`` is a scalar or one bound per draw."""
    u = rng.random(count, dtype=np.float32).astype(np.float64)
    rank = (n * u ** skew).astype(np.int64)
    return np.minimum(rank, np.asarray(n, np.int64) - 1)


def _communities(rng, recipe: dict, num_classes: int):
    """(label per node, member table, start and size of each community's
    slice of the table).  ``members[start[c] + r]`` is the vertex of rank r
    in community c: ranks are a seeded shuffle of the members, so hubs sit
    anywhere inside their community."""
    n = int(recipe["nodes"])
    c = int(recipe["communities"]) or num_classes
    if recipe["layout"] == "contiguous":
        start = (np.arange(c + 1, dtype=np.int64) * n) // c
        comm = np.repeat(np.arange(c, dtype=np.int32), np.diff(start))
        members = np.concatenate(
            [start[i] + rng.permutation(int(start[i + 1] - start[i]))
             for i in range(c)]).astype(np.int32)
        return comm, members, start[:-1], np.diff(start)
    comm = rng.integers(0, c, size=n, dtype=np.int32)
    size = np.bincount(comm, minlength=c).astype(np.int64)
    start = np.concatenate([[0], np.cumsum(size)[:-1]])
    # a random key per vertex, sorted inside each community: one argsort
    # over N (not E) elements gives every community its shuffled members
    members = np.lexsort((rng.random(n, dtype=np.float32), comm)) \
        .astype(np.int32)
    return comm, members, start, size


def draw_edges(rng, recipe: dict, comm, members, start, size):
    """The drawn (source, destination) pairs, before symmetrising."""
    n = int(recipe["nodes"])
    e = int(n * float(recipe["avg_degree"]))
    c = int(size.shape[0])
    law, skew = recipe["degree_law"], float(recipe["skew"])
    src = rng.integers(0, n, size=e, dtype=np.int32)
    if law == "power" and recipe["layout"] == "scattered":
        # hubs over the whole graph, scattered by a seeded permutation:
        # no community preference on the destination (tools/make_giant.py)
        perm = rng.permutation(n).astype(np.int32)
        return src, perm[power_law_rank(rng, e, n, skew)]
    # destination community: the source's own with p_intra, else anywhere
    # (uniform) or a ring neighbour (ring)
    tgt = comm[src].astype(np.int64)
    other = rng.random(e, dtype=np.float32) >= float(recipe["p_intra"])
    k = int(other.sum())
    if recipe["inter"] == "ring":
        step = np.where(rng.random(k, dtype=np.float32) < 0.5, 1, c - 1)
        tgt[other] = (tgt[other] + step) % c
    else:
        # uniform over vertices, so a community draws by its size
        tgt[other] = comm[rng.integers(0, n, size=k, dtype=np.int32)]
    if law == "power":
        rank = power_law_rank(rng, e, size[tgt], skew)
    else:
        rank = np.minimum(
            (rng.random(e, dtype=np.float32) * size[tgt]).astype(np.int64),
            size[tgt] - 1)
    return src, members[start[tgt] + rank]


def build_csr(num_nodes: int, src, dst, symmetrize: bool):
    """In-edge CSR (row = destination, columns = its sources) with one
    self-edge per vertex and parallel edges merged.  One counting sort."""
    import scipy.sparse as sp

    from roc_tpu.graph.csr import E_DTYPE, V_DTYPE, Csr
    loops = np.arange(num_nodes, dtype=np.int32)
    if symmetrize:
        rows = np.concatenate([dst, src, loops])
        cols = np.concatenate([src, dst, loops])
    else:
        rows = np.concatenate([dst, loops])
        cols = np.concatenate([src, loops])
    m = sp.coo_matrix((np.ones(rows.shape[0], np.int8), (rows, cols)),
                      shape=(num_nodes, num_nodes)).tocsr()
    return Csr(num_nodes, int(m.nnz), m.indptr.astype(E_DTYPE),
               m.indices.astype(V_DTYPE, copy=False))


def degree_stats(graph) -> dict:
    """In-degree min / median / p99 / max and the edge count: printed by
    every run, so a reader knows which graph a number belongs to."""
    deg = np.diff(graph.row_ptr)
    return {"nodes": int(graph.num_nodes), "in_edges": int(graph.num_edges),
            "in_degree_min": int(deg.min()),
            "in_degree_median": float(np.median(deg)),
            "in_degree_p99": float(np.percentile(deg, 99)),
            "in_degree_max": int(deg.max())}


def generate(recipe: dict, in_dim: int, num_classes: int, seed: int,
             name: str = "bench"):
    """The Dataset the program is handed.  Classes follow communities
    (community mod classes), features are a class mean plus unit noise, so
    a GCN learns on it and the loss falls."""
    from roc_tpu.graph import lux
    from roc_tpu.graph.datasets import Dataset

    rng = np.random.default_rng(int(recipe.get("structure_seed", seed)))
    n = int(recipe["nodes"])
    comm, members, start, size = _communities(rng, recipe, num_classes)
    src, dst = draw_edges(rng, recipe, comm, members, start, size)
    graph = build_csr(n, src, dst, bool(recipe["symmetrize"]))
    del src, dst, members
    if "structure_seed" in recipe:
        rng = np.random.default_rng(int(seed))

    labels = (comm % num_classes).astype(np.int64)
    means = rng.standard_normal((num_classes, in_dim), dtype=np.float32)
    feats = rng.standard_normal((n, in_dim), dtype=np.float32)
    feats += np.float32(recipe["feature_snr"]) * means[labels]

    sp_ = recipe["splits"]
    picks = rng.permutation(n)
    mask = np.full(n, lux.MASK_NONE, dtype=np.int32)
    a, b, c = int(sp_["train"]), int(sp_["val"]), int(sp_["test"])
    if a + b + c > n or a <= 0:
        raise ValueError(f"splits {sp_} do not fit {n} nodes")
    mask[picks[:a]] = lux.MASK_TRAIN
    mask[picks[a:a + b]] = lux.MASK_VAL
    mask[picks[a + b:a + b + c]] = lux.MASK_TEST
    return Dataset(name, graph, feats, None, labels, mask, in_dim,
                   num_classes)
