"""Worker process for tests/test_multihost.py: one of N real
`jax.distributed` processes on the CPU platform (gloo collectives over
localhost — the test-scale analog of a multi-host TPU pod over DCN).

Usage: python multihost_worker.py <proc_id> <nprocs> <port> <prefix> <outdir>

Trains the shard_map GCN with per-host loading (each process reads only its
parts' `.lux` slices), checkpoints (process-0-only write), and dumps its
metrics + bookkeeping as JSON for the parent test to assert on.
"""

import json
import os
import sys


def main():
    proc_id, nprocs = int(sys.argv[1]), int(sys.argv[2])
    port, prefix, outdir = sys.argv[3], sys.argv[4], sys.argv[5]
    devices_per_proc = 4

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import __graft_entry__
    __graft_entry__._pin_cpu_platform(devices_per_proc)

    import jax
    jax.distributed.initialize(coordinator_address=f"localhost:{port}",
                               num_processes=nprocs, process_id=proc_id)
    assert jax.process_index() == proc_id
    assert len(jax.local_devices()) == devices_per_proc

    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gcn
    from roc_tpu.parallel.spmd import SpmdTrainer
    from roc_tpu.train import checkpoint
    from roc_tpu.train.config import Config

    # Count checkpoint.save calls to prove the process-0-only gating.
    saves = []
    real_save = checkpoint.save
    checkpoint.save = lambda *a, **k: (saves.append(1), real_save(*a, **k))

    num_parts = nprocs * devices_per_proc
    ds = datasets.load_roc_dataset(prefix, 12, 5, graph_stub=True)
    ckpt = os.path.join(outdir, "ckpt.npz")
    cfg = Config(layers=[12, 16, 5], num_epochs=3, dropout_rate=0.0,
                 num_parts=num_parts, halo=True, perhost_load=True,
                 filename=prefix, eval_every=10**9, checkpoint_path=ckpt)
    trainer = SpmdTrainer(cfg, ds, build_gcn(cfg.layers, 0.0))
    for _ in range(cfg.num_epochs):
        trainer.run_epoch()
    m = jax.device_get(trainer.evaluate())
    # `extra` payload round-trip through the TRAINER's process-0-only
    # write + barrier (VERDICT r1 item 9) — the saves==1/0 assertion in the
    # parent test proves the trainer's gating, not the test's.
    trainer.save_checkpoint(ckpt, extra={"tag": "mh", "nprocs": nprocs})

    # Restore round-trips on every process (reads the file process 0 wrote).
    p2, o2, epoch2, alpha2, extra2 = checkpoint.load(ckpt, trainer.params,
                                                     trainer.opt_state)
    assert epoch2 == trainer.epoch
    assert extra2 == {"tag": "mh", "nprocs": nprocs}

    # Plan-backend GAT under per-host loading: each process builds its
    # local parts' attention plans, floors allgathered so the compiled
    # program agrees across processes (round-3 feature).
    from roc_tpu.models import build_gat
    cfg_g = Config(layers=[12, 8, 5], num_epochs=2, dropout_rate=0.0,
                   num_parts=num_parts, halo=True, perhost_load=True,
                   filename=prefix, eval_every=10**9, model="gat", heads=2,
                   aggregate_backend="matmul")
    tr_g = SpmdTrainer(cfg_g, ds, build_gat(cfg_g.layers, 0.0, heads=2))
    assert tr_g.gdata.gat_plans is not None, "perhost GAT plans not built"
    gat_losses = [float(tr_g.run_epoch()) for _ in range(2)]

    out = {
        "proc": proc_id,
        "saves": len(saves),
        "metrics": {k: float(getattr(m, k)) for k in m._fields},
        "ckpt_exists": os.path.exists(ckpt),
        "gat_losses": gat_losses,
    }
    with open(os.path.join(outdir, f"out_{proc_id}.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
