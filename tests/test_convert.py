"""Dataset converters (roc_tpu/graph/convert.py): edge-list and OGB-style
dumps -> ROC on-disk format, plus the vendored *real* graph (Zachary's
karate club) and its golden semi-supervised curve.

The reference ships no converter (its datasets were prepared out-of-tree,
test.sh:8); SURVEY §7.1 calls for one.  The karate test is the repo's one
real-data accuracy oracle: the GCN must reproduce the published result
(Zachary 1977's model: 33/34 members, node 8 the sole miss)."""

import numpy as np
import pytest

from roc_tpu.graph import convert, datasets, lux
from roc_tpu.models import build_model
from roc_tpu.train.config import Config
from roc_tpu.train.driver import Trainer


def _write(path, text):
    with open(path, "w") as f:
        f.write(text)


def test_edge_list_basic(tmp_path):
    _write(tmp_path / "g.txt", "# comment\n0 1\n1 2\n2,0\n\n")
    ds = convert.from_edge_list(str(tmp_path / "g.txt"))
    assert ds.graph.num_nodes == 3
    # 3 directed edges + 3 self-edges
    assert ds.graph.num_edges == 6
    assert ds.in_dim == 3                      # identity features
    np.testing.assert_array_equal(ds.features, np.eye(3, dtype=np.float32))


def test_edge_list_undirected_dedups(tmp_path):
    # both orientations listed + a duplicate: symmetrize must dedup
    _write(tmp_path / "g.txt", "0 1\n1 0\n0 1\n1 2\n")
    ds = convert.from_edge_list(str(tmp_path / "g.txt"), undirected=True,
                                self_edges=False)
    assert ds.graph.num_edges == 4             # 0<->1, 1<->2
    t = ds.graph.transpose()                   # undirected: CSR == CSR^T
    np.testing.assert_array_equal(ds.graph.row_ptr, t.row_ptr)
    np.testing.assert_array_equal(ds.graph.col_idx, t.col_idx)


def test_edge_list_sidecars_and_roundtrip(tmp_path):
    _write(tmp_path / "g.txt", "0 1\n1 2\n3 0\n")
    _write(tmp_path / "f.csv", "1,0\n0,1\n1,1\n0,0\n")
    _write(tmp_path / "l.txt", "0\n1\n1\n0\n")
    ds = convert.from_edge_list(
        str(tmp_path / "g.txt"), feats_path=str(tmp_path / "f.csv"),
        labels_path=str(tmp_path / "l.txt"), split=(2, 1, 1), seed=0)
    assert ds.num_classes == 2 and ds.in_dim == 2
    convert.write(ds, str(tmp_path / "out"))
    back = datasets.load_roc_dataset(str(tmp_path / "out"), 2, 2)
    np.testing.assert_array_equal(back.graph.row_ptr, ds.graph.row_ptr)
    np.testing.assert_array_equal(back.graph.col_idx, ds.graph.col_idx)
    np.testing.assert_allclose(back.features, ds.features, atol=1e-6)
    np.testing.assert_array_equal(back.label_ids, ds.label_ids)
    np.testing.assert_array_equal(back.mask, ds.mask)


def test_edge_list_out_of_range(tmp_path):
    _write(tmp_path / "g.txt", "0 7\n")
    with pytest.raises(ValueError, match="out of range"):
        convert.from_edge_list(str(tmp_path / "g.txt"), num_nodes=4)
    _write(tmp_path / "neg.txt", "5 -1\n0 1\n")
    with pytest.raises(ValueError, match="out of range"):
        convert.from_edge_list(str(tmp_path / "neg.txt"), num_nodes=10,
                               undirected=True)


def test_edge_list_keeps_input_self_loops(tmp_path):
    # a self-loop in the input must survive symmetrization even when
    # self_edges=False (no uniform re-add)
    _write(tmp_path / "g.txt", "2 2\n0 1\n")
    ds = convert.from_edge_list(str(tmp_path / "g.txt"), undirected=True,
                                self_edges=False)
    assert ds.graph.num_edges == 3          # 0<->1 + the (2,2) loop
    src, dst = ds.graph.col_idx, ds.graph.dst_idx
    assert ((src == 2) & (dst == 2)).sum() == 1


def test_stratified_split_covers_classes():
    ids = np.array([0] * 50 + [1] * 30 + [2] * 20)
    mask = convert.stratified_split(ids, 6, 10, 20, seed=3)
    train = ids[mask == lux.MASK_TRAIN]
    assert (mask == lux.MASK_TRAIN).sum() == 6
    assert (mask == lux.MASK_VAL).sum() == 10
    assert (mask == lux.MASK_TEST).sum() == 20
    assert set(np.unique(train)) == {0, 1, 2}   # every class in train


def test_ogb_dir(tmp_path):
    root = tmp_path / "raw"
    root.mkdir()
    (root / "split").mkdir()
    _write(root / "edge.csv", "0,1\n1,2\n2,3\n")
    _write(root / "node-feat.csv", "1,0\n0,1\n1,1\n0,0\n")
    _write(root / "node-label.csv", "0\n1\n1\n0\n")
    _write(root / "split" / "train.csv", "0\n1\n")
    _write(root / "split" / "valid.csv", "2\n")
    _write(root / "split" / "test.csv", "3\n")
    ds = convert.from_ogb_dir(str(root))
    assert ds.graph.num_nodes == 4
    # 3 undirected pairs = 6 directed + 4 self-edges
    assert ds.graph.num_edges == 10
    np.testing.assert_array_equal(
        ds.mask, [lux.MASK_TRAIN, lux.MASK_TRAIN, lux.MASK_VAL,
                  lux.MASK_TEST])


def test_mtx(tmp_path):
    _write(tmp_path / "g.mtx",
           "%%MatrixMarket matrix coordinate pattern symmetric\n"
           "% a comment\n"
           "4 4 3\n"
           "2 1\n3 2\n4 1\n")
    ds = convert.from_mtx(str(tmp_path / "g.mtx"))
    assert ds.graph.num_nodes == 4
    # 3 symmetric pairs = 6 directed + 4 self-edges
    assert ds.graph.num_edges == 10
    t = ds.graph.transpose()       # symmetrized: CSR == CSR^T as edge sets
    np.testing.assert_array_equal(ds.graph.row_ptr, t.row_ptr)
    for v in range(4):             # within-row order may differ; compare
        sl = slice(int(ds.graph.row_ptr[v]),        # sorted multisets
                   int(ds.graph.row_ptr[v + 1]))
        np.testing.assert_array_equal(np.sort(ds.graph.col_idx[sl]),
                                      np.sort(t.col_idx[sl]))
    with pytest.raises(ValueError, match="MatrixMarket"):
        _write(tmp_path / "bad.mtx", "not a header\n1 1 0\n")
        convert.from_mtx(str(tmp_path / "bad.mtx"))


def test_karate_is_the_real_graph():
    ds = convert.karate_club()
    assert ds.graph.num_nodes == 34
    assert ds.graph.num_edges == 2 * 78 + 34   # symmetrized + self-edges
    # the observed fission outcome as recorded in the networkx dataset:
    # 17 members with Mr. Hi, 17 with the officers
    assert int((ds.label_ids == 0).sum()) == 17
    assert int((ds.label_ids == 1).sum()) == 17
    # canonical semi-supervised split: leaders train, everyone else test
    assert list(np.nonzero(ds.mask == lux.MASK_TRAIN)[0]) == [0, 33]
    assert int((ds.mask == lux.MASK_TEST).sum()) == 32


def test_davis_is_the_real_graph():
    ds = convert.davis_women()
    assert ds.graph.num_nodes == 32            # 18 women + 14 events
    assert ds.graph.num_edges == 2 * 89 + 32   # symmetrized + self-edges
    # Freeman's consensus split is 9 women per group; events unlabeled
    assert int((ds.label_ids[:18] == 0).sum()) == 9
    assert int((ds.label_ids[:18] == 1).sum()) == 9
    assert list(np.nonzero(ds.mask == lux.MASK_TRAIN)[0]) == [0, 13]
    assert int((ds.mask == lux.MASK_TEST).sum()) == 16
    assert int((ds.mask[18:] == lux.MASK_NONE).sum()) == 14


def test_lesmis_is_the_real_graph():
    ds = convert.les_miserables()
    assert ds.graph.num_nodes == 77
    assert ds.graph.num_edges == 2 * 254 + 77
    assert ds.num_classes == 5                 # CNM modularity communities
    assert int((ds.mask == lux.MASK_TRAIN).sum()) == 10   # 2 per class


def test_convert_rocfile_reorder_roundtrip(tmp_path):
    """tools/convert.py rocfile --reorder: re-processing an on-disk
    dataset through the RCM pass (the preprocess-once workflow) must
    yield an ISOMORPHIC dataset — same losses, features/labels/mask
    moved with their vertices — plus the transpose sidecar."""
    import os
    import subprocess
    import sys
    tool = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "convert.py")
    a = str(tmp_path / "a")
    b = str(tmp_path / "b")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    assert subprocess.run([sys.executable, tool, "lesmis", "-o", a],
                          env=env).returncode == 0
    assert subprocess.run([sys.executable, tool, "rocfile", "--file", a,
                           "--in-dim", "77", "--classes", "5", "-o", b,
                           "--reorder", "--with-transpose"],
                          env=env).returncode == 0
    assert os.path.exists(b + lux.TLUX_SUFFIX)
    da = datasets.load_roc_dataset(a, 77, 5)
    db = datasets.load_roc_dataset(b, 77, 5)
    assert da.graph.num_edges == db.graph.num_edges
    assert int((da.mask == lux.MASK_TRAIN).sum()) == \
        int((db.mask == lux.MASK_TRAIN).sum())
    cfg = Config(layers=[77, 8, 5], num_epochs=2, dropout_rate=0.0,
                 eval_every=10**9, seed=1)
    ta = Trainer(cfg, da, build_model("gcn", cfg.layers, 0.0, "sum"))
    tb = Trainer(cfg, db, build_model("gcn", cfg.layers, 0.0, "sum"))
    for i in range(2):
        la, lb = float(ta.run_epoch()), float(tb.run_epoch())
        np.testing.assert_allclose(lb, la, rtol=2e-4, err_msg=f"epoch {i}")


@pytest.mark.slow
def test_golden_davis_curve():
    """Real-data golden curve on a BIPARTITE graph (docs/GOLDEN.md):
    2-layer GCN, identity features, train = one seed woman per group
    (Evelyn, Nora).  Must reproduce Freeman's consensus split for 15 of
    the 16 held-out women, with node 15 (Dorothy Murchison — one of the
    classically ambiguous cases; she attended only two events) the sole
    miss."""
    import jax

    ds = convert.davis_women()
    cfg = Config(layers=[32, 16, 2], num_epochs=100, learning_rate=0.01,
                 weight_decay=5e-4, dropout_rate=0.5, eval_every=10**9)
    tr = Trainer(cfg, ds, build_model("gcn", cfg.layers, cfg.dropout_rate,
                                      "sum"))
    for _ in range(100):
        tr.run_epoch()
    m = jax.device_get(tr.evaluate())
    assert int(m.test_correct) == 15 and int(m.test_all) == 16
    pred = np.argmax(np.asarray(tr.predict_logits()), axis=-1)
    women = np.arange(18)
    assert list(women[(pred[:18] != ds.label_ids[:18])]) == [15]


@pytest.mark.slow
def test_golden_lesmis_curve():
    """The repo's one real NON-SATURATING pin (docs/GOLDEN.md): 5-class
    community recovery on Knuth's Les Misérables graph lands near 90%,
    not 100% — so a kernel/plan bug costing 1-2 samples moves this
    assert.  Measured (CPU, seed 1): epoch 50 val 15/19 test 45/48;
    epoch 200 val 15/19 test 45/48, train loss 0.34.  Pins leave
    2-sample cross-platform headroom."""
    import jax

    ds = convert.les_miserables()
    cfg = Config(layers=[77, 16, 5], num_epochs=200, learning_rate=0.01,
                 weight_decay=5e-4, dropout_rate=0.5, seed=1,
                 eval_every=10**9)
    tr = Trainer(cfg, ds, build_model("gcn", cfg.layers, cfg.dropout_rate,
                                      "sum"))
    for _ in range(200):
        tr.run_epoch()
    m = jax.device_get(tr.evaluate())
    assert int(m.val_correct) >= 13 and int(m.val_all) == 19
    assert int(m.test_correct) >= 43 and int(m.test_all) == 48
    assert float(m.train_loss) <= 1.0


@pytest.mark.slow
def test_golden_karate_curve():
    """Real-data golden curve (docs/GOLDEN.md): 2-layer GCN, identity
    features, train = the two faction leaders only.  Must reproduce the
    published result — 31/32 test members (33/34 overall, matching
    Zachary's own model) with node 8 the sole structural miss."""
    ds = convert.karate_club()
    cfg = Config(layers=[34, 16, 2], num_epochs=100, learning_rate=0.01,
                 weight_decay=5e-4, dropout_rate=0.5, eval_every=10**9)
    tr = Trainer(cfg, ds, build_model("gcn", cfg.layers, cfg.dropout_rate,
                                      "sum"))
    for _ in range(100):
        tr.run_epoch()
    import jax
    m = jax.device_get(tr.evaluate())
    assert int(m.test_correct) == 31 and int(m.test_all) == 32
    pred = np.argmax(np.asarray(tr.predict_logits()), axis=-1)
    assert list(np.nonzero(pred != ds.label_ids)[0]) == [8]
