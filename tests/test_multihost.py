"""Real multi-process coverage (VERDICT round-1 items 4/5/9): two
`jax.distributed` CPU processes form one 8-device mesh and train the same
sharded GCN the single-process tests train, with

  * per-host `.lux` slice loading (-perhost): each process builds only its
    4 parts' edge arrays / halo maps,
  * `_place_nodes` running with a non-zero process_index (each process
    places only its addressable shards),
  * process-0-only checkpoint writing + barrier.

The reference's analog is the Legion/GASNet multi-machine launch
(gnn_mapper.cc:88-134); its parts>GPUs trick is covered by the virtual-mesh
tests — this file covers the genuinely-multi-process seams those can't.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from roc_tpu.graph import datasets, lux

pytestmark = pytest.mark.filterwarnings("ignore")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKER = os.path.join(_REPO, "tests", "multihost_worker.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="module")
def roc_prefix(tmp_path_factory):
    ds = datasets.synthetic("mh", 600, 6.0, 12, 5,
                            n_train=100, n_val=100, n_test=100, seed=7)
    prefix = str(tmp_path_factory.mktemp("mh") / "g")
    lux.write_dataset(prefix, ds.graph, ds.features, ds.label_ids, ds.mask)
    return prefix, ds


def _spawn_workers(prefix, tmp_path):
    """One full 2-process run: spawn both workers on a fresh port, wait
    out the (load-sensitive) distributed init + train, return outputs.
    Raises TimeoutExpired after killing the pair so a retry starts from
    a clean slate — a fresh port, no half-formed gloo mesh."""
    port = _free_port()
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    procs = [subprocess.Popen(
        [sys.executable, _WORKER, str(i), "2", str(port), prefix,
         str(tmp_path)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for i in range(2)]
    outs = []
    try:
        for p in procs:
            # generous deadline: under CI load the two interpreters can
            # take minutes just to import jax and form the mesh (the
            # PR 19 flake was a too-tight 240 s here)
            out, err = p.communicate(timeout=420)
            outs.append((out, err, p.returncode))
    except subprocess.TimeoutExpired:
        for q in procs:
            q.kill()
        for q in procs:
            q.communicate()  # reap, so the retry's port is truly free
        raise
    return outs


def test_two_process_training(roc_prefix, tmp_path):
    prefix, ds = roc_prefix
    # one bounded retry through the repo's own retry primitive: a hung
    # spawn under load is the transient being deflaked, a second timeout
    # is a real failure worth a red test
    from roc_tpu import fault
    try:
        outs = fault.retrying(
            "test.multihost_spawn", lambda: _spawn_workers(prefix, tmp_path),
            attempts=2, retry_on=(subprocess.TimeoutExpired,))
    except subprocess.TimeoutExpired:
        pytest.fail("multihost worker hung (twice, 420 s deadline each)")
    for out, err, code in outs:
        assert code == 0, f"worker failed:\n{err[-3000:]}"

    results = [json.load(open(tmp_path / f"out_{i}.json")) for i in range(2)]

    # process-0-only checkpointing: exactly one writer, file visible to both
    assert results[0]["saves"] == 1 and results[1]["saves"] == 0
    assert all(r["ckpt_exists"] for r in results)

    # both processes agree on the (psum-replicated) metrics
    m0, m1 = results[0]["metrics"], results[1]["metrics"]
    assert m0 == m1

    # and the distributed run matches a single-process 8-virtual-device run
    # of the identical config (the virtual mesh is the oracle; count metrics
    # must agree exactly, loss up to collective reassociation)
    from roc_tpu.models import build_gcn
    from roc_tpu.parallel.spmd import SpmdTrainer
    from roc_tpu.train.config import Config
    import jax

    cfg = Config(layers=[12, 16, 5], num_epochs=3, dropout_rate=0.0,
                 num_parts=8, halo=True, eval_every=10**9)
    tr = SpmdTrainer(cfg, datasets.load_roc_dataset(prefix, 12, 5),
                     build_gcn(cfg.layers, 0.0))
    for _ in range(cfg.num_epochs):
        tr.run_epoch()
    ref = jax.device_get(tr.evaluate())
    for k in ref._fields:
        a, b = float(getattr(ref, k)), m0[k]
        tol = 1e-3 * max(abs(a), 1.0) if k == "train_loss" else 0.0
        assert abs(a - b) <= tol, (k, a, b)

    # perhost plan-backend GAT (round 3): both processes agree, and the
    # losses match a single-process full-load run of the same config
    assert results[0]["gat_losses"] == results[1]["gat_losses"]
    from roc_tpu.models import build_gat
    cfg_g = Config(layers=[12, 8, 5], num_epochs=2, dropout_rate=0.0,
                   num_parts=8, halo=True, eval_every=10**9, model="gat",
                   heads=2, aggregate_backend="matmul")
    tr_g = SpmdTrainer(cfg_g, datasets.load_roc_dataset(prefix, 12, 5),
                       build_gat(cfg_g.layers, 0.0, heads=2))
    ref_g = [float(tr_g.run_epoch()) for _ in range(2)]
    # same tolerance policy as the GCN train_loss check above: the
    # 2-process gloo psum reassociates float sums differently from the
    # single-process virtual mesh
    np.testing.assert_allclose(results[0]["gat_losses"], ref_g, rtol=1e-3)
