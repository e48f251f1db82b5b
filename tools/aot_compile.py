#!/usr/bin/env python
"""Compile the CLI's train step for a TPU v5e — Mosaic kernels included —
on a machine that has none.

    python tools/aot_compile.py -dataset reddit -layers 602-256-41
    python tools/aot_compile.py -dataset reddit -layers 602-256-41 -parts 4

Takes `python -m roc_tpu`'s own flags.  libtpu can describe a v5e
topology and run its compiler without a chip, so a kernel the compiler
refuses (scoped VMEM, tile alignment, an unsupported op) shows its
message here, for free, instead of costing chip minutes.  The trainer is
built on the CPU exactly as the CLI builds it, with the backend policy
answering as it would on a TPU; the step is then lowered for the
topology's devices and compiled.  Prints the Mosaic kernels in the step,
the collectives, and the compiler's memory analysis — or its error, and
exits 1.

Nothing runs: this says a program compiles, never that it is right or
how fast it is.  Those need the chip (`python chip_smoke.py`).
"""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# libtpu refuses to describe a topology until it is told what host it
# would be on, and by default lets one process load it at a time
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
os.environ["JAX_PLATFORMS"] = "cpu"


def main(argv) -> int:
    from roc_tpu.train.config import parse_args
    cfg = parse_args(argv)
    parts = cfg.num_parts
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_"
                               f"force_host_platform_device_count={parts}")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    tpus = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    if parts > len(tpus):
        raise SystemExit(f"-parts {parts}: the v5e:2x2 topology has "
                         f"{len(tpus)} devices")
    # every policy asks roc_tpu.device.on_tpu(), which asks this
    jax.default_backend = lambda: "tpu"

    from roc_tpu.graph import datasets
    from roc_tpu.models import build_model
    from roc_tpu.train.driver import make_trainer

    ds = datasets.get(cfg.dataset, seed=cfg.seed)
    model = build_model(cfg.model, cfg.layers, cfg.dropout_rate, cfg.aggr,
                        heads=cfg.heads)
    tr = make_trainer(cfg, ds, model)
    print(f"# {tpus[0].device_kind} x{parts}; backend "
          f"{cfg.aggregate_backend} -> {tr.gdata.backend}")
    if parts > 1:
        # same step, rebuilt over the topology's devices
        mesh = Mesh(np.array(tpus[:parts]), tr.mesh.axis_names)
        tr.mesh = mesh
        tr._step_cache.clear()
        tr._build_steps(tr.gdata)

        def place(a):
            spec = a.sharding.spec if isinstance(a.sharding, NamedSharding) \
                else PartitionSpec()
            return NamedSharding(mesh, spec)
    else:
        def place(a):
            return SingleDeviceSharding(tpus[0])
    args = (tr.params, tr.opt_state, tr.x, tr.labels, tr.mask, tr.gdata,
            jax.random.PRNGKey(0), jnp.float32(cfg.learning_rate),
            jnp.float32(1.0))
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=place(a)),
        jax.tree.map(jnp.asarray, args))
    lowered = tr._train_step.lower(*args)
    kernels = re.findall(r'kernel_name = "([^"]+)"', lowered.as_text())
    print(f"# Mosaic kernels in the train step ({len(kernels)}): "
          + (", ".join(f"{k} x{kernels.count(k)}"
                       for k in sorted(set(kernels))) or "none"))
    try:
        compiled = lowered.compile()
    except Exception as e:  # the compiler's verdict is the product here
        print(f"# COMPILE FAILED: {type(e).__name__}\n{e}")
        return 1
    hlo = compiled.as_text()
    print("# compiled: " + ", ".join(
        f"{op} x{hlo.count(op)}"
        for op in ("all-to-all", "all-reduce", "all-gather",
                   "collective-permute")))
    # an int32 operand XLA re-lays out for a kernel (a [rows, 1] column
    # tiled to 128 lanes a row, as the binned plans' were until PR 26)
    relaid = re.findall(r"= (s32\[[0-9,]+\])\{[^}]*\} copy\(", hlo)
    print("# copies of s32 arrays: " + (", ".join(
        f"{t} x{relaid.count(t)}" for t in sorted(set(relaid))) or "none"))
    print(f"# {compiled.memory_analysis()}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
