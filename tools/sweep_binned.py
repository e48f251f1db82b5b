"""Sweep binned-kernel constants on the real chip (uniform Reddit-scale).

Each config runs in its own SUBPROCESS with a timeout: a compile that
hangs then costs one config, not the whole sweep.  The parent never
imports jax, so it never holds the chip its children need.  Inside the child, module globals
(SB/CH/SLOT/RB/CH2 + derived) are monkeypatched before plan build and run;
since round 4 the C++ builder takes the geometry as arguments, so plans
build native (O(E)) at every config.

SWEEP_SHAPE=products sweeps the sparse-graph presets at the ogbn-products
shape instead (the north-star A/B's kernel-level companion).

Results of record: docs/PERF.md (2026-07-31 sweep that picked SLOT=128).
Run on hardware:  python tools/sweep_binned.py
One config (child mode): python tools/sweep_binned.py SB CH SLOT RB CH2 GRT [FLAT]

Edit CONFIGS below; each row is (SB, CH, SLOT, RB, CH2, group_row_target,
flat).  flat=1 builds the flat compacted schedule (binned.py GEOM_FLAT
family) instead of the slot-padded one — paired flat=0/flat=1 rows at the
same shape are the A/B that validates the predicted step reduction on
hardware.  After changing shipped defaults, mirror them in
roc_tpu/ops/pallas/binned.py AND the BN_* constants in
roc_tpu/native/src/roc_native.cc.
"""
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

H = int(os.environ.get("SWEEP_H", 256))
E = int(os.environ.get("SWEEP_E", 23_526_267))
N = int(os.environ.get("SWEEP_N", 232_965))
CHILD_TIMEOUT_S = int(os.environ.get("SWEEP_TIMEOUT_S", 600))

# The sparse presets (binned.py GEOM_*) at the production group-row
# target.  Hardcoded so the sweep PARENT never imports jax/roc_tpu (the
# subprocess-isolation design: only children may touch anything that can
# wedge); tests/test_binned.py::test_sweep_products_configs_match_presets
# pins these against the Geometry literals, so a preset retune that
# forgets this mirror fails CI instead of measuring stale tuples.
CONFIGS_PRODUCTS = [
    (512, 2048, 32, 512, 4096, 1 << 21, 0),     # GEOM_MID
    (512, 4096, 32, 512, 8192, 1 << 23, 0),     # GEOM_MID_WIDE
    (1024, 2048, 16, 1024, 2048, 1 << 21, 0),   # GEOM_SPARSE
    (1024, 4096, 16, 1024, 4096, 1 << 23, 0),   # GEOM_SPARSE_WIDE
    (2048, 1024, 16, 2048, 1024, 1 << 21, 0),   # GEOM_XSPARSE
    (1024, 2048, 16, 1024, 2048, 1 << 21, 1),   # GEOM_FLAT_SPARSE (A/B vs
    #                                             GEOM_SPARSE: same shape)
]

# (SB, CH, SLOT, RB, CH2, group_row_target, flat)
# Round-5 CPU plan-statistics study (BASELINE.md round-5 notes): at Reddit
# shape, CH=4096 + grt=2^23 cuts phase-1 grid steps 50% (16512 -> 8208)
# and CH2=8192 cuts phase-2 steps 49% (7692 -> 3891); both phases were
# measured per-grid-step-overhead-bound (docs/PERF.md), so the chunk-count
# cut is the modeled 310 -> 257 ms lever.  RB=256 and SB=1024 LOSE on the
# model (slot-padding x2.6 / MAC-bound) and are kept as controls.  CH2=8192
# failed to compile in round 2 with no message kept (PR 21: at H=256 its
# phase 2 needs more scoped VMEM than Mosaic's 16 MiB default).
CONFIGS = [
    (512, 2048, 128, 512, 4096, 1 << 21, 0),   # shipped defaults (baseline)
    (512, 2048, 128, 512, 4096, 1 << 23, 0),   # fewer groups only
    (512, 4096, 128, 512, 4096, 1 << 23, 0),   # -50% phase-1 chunks
    (512, 4096, 128, 512, 8192, 1 << 23, 0),   # + -49% phase-2 chunks
    (512, 4096, 128, 512, 8192, 1 << 21, 0),   # big chunks, small staging
    (512, 2048, 128, 256, 4096, 1 << 22, 0),   # control: model says lose
    (1024, 4096, 128, 512, 8192, 1 << 23, 0),  # control: model says MAC-bound
    (512, 4096, 128, 512, 4096, 1 << 21, 1),   # GEOM_FLAT: flat A/B vs the
    #                                            same-shape slot-padded row
]


def run_one(sb, ch, slot, rb, ch2, grt, flat=0):
    """Child-process body: measure one config, print one line."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    import roc_tpu.ops.pallas.binned as B

    B.SB, B.CH, B.SLOT, B.RB, B.CH2 = sb, ch, slot, rb, ch2

    rng = np.random.default_rng(0)
    src = rng.integers(0, N, E).astype(np.int64)
    dst = rng.integers(0, N, E).astype(np.int64)
    x = jnp.asarray(rng.standard_normal((N, H), dtype=np.float32))

    t0 = time.time()
    if flat:
        # the forced-A/B harness constructs each grid point on purpose:
        # roclint: allow(hand-rolled-geometry) — the forced-A/B harness constructs each grid point on purpose
        geom = B.Geometry(sb=sb, ch=ch, slot=slot, rb=rb, ch2=ch2,
                          grt=grt, flat=1)
        plan = B.build_binned_plan(src, dst, N, N, geom=geom,
                                   group_row_target=grt)
    else:
        plan = B.build_binned_plan(src, dst, N, N, group_row_target=grt)
    tb = time.time() - t0
    G, C1 = plan.p1_blk.shape
    C2 = plan.p2_obi.shape[1]
    pad1 = G * C1 * ch / E
    pad2 = G * C2 * ch2 / E
    from roc_tpu.device import on_tpu
    interp = not on_tpu()                     # CPU smoke: interpret mode
    run = jax.jit(lambda x, plan: jnp.sum(B.run_binned(x, plan, interp)))
    v = float(np.asarray(run(x, plan)))     # compile + correctness value
    from roc_tpu import obs
    with obs.span("bench_sweep", sb=sb, ch=ch, reps=5) as sp:
        for _ in range(5):
            out = run(x, plan)
        _ = np.asarray(out)
    dt = sp.dur_s / 5
    print(f"SB={sb} CH={ch} SLOT={slot} RB={rb} CH2={ch2} grt={grt} "
          f"flat={flat}: {dt*1e3:.1f} ms  (G={G} C1={C1} C2={C2} "
          f"pad1={pad1:.2f} pad2={pad2:.2f} build={tb:.0f}s "
          f"checksum={v:.6g})", flush=True)


def main():
    if len(sys.argv) in (7, 8):             # child mode (6 args = flat 0)
        run_one(*(int(a) for a in sys.argv[1:]))
        return
    configs = CONFIGS_PRODUCTS \
        if os.environ.get("SWEEP_SHAPE") == "products" else CONFIGS
    for cfg in configs:
        sb, ch, slot, rb, ch2, grt, flat = cfg
        if ch2 % slot or ch % slot:
            print(f"{cfg}: skipped (SLOT must divide CH and CH2)")
            continue
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__)]
                + [str(v) for v in cfg],
                timeout=CHILD_TIMEOUT_S, capture_output=True, text=True)
            out = (r.stdout or "").strip()
            if r.returncode != 0:
                lines = (r.stderr or "").strip().splitlines()
                err = next((ln for ln in reversed(lines)
                            if "Error" in ln or "error" in ln),
                           lines[-1] if lines else "")
                print(f"{cfg}: FAILED rc={r.returncode}: {err[:200]}",
                      flush=True)
            elif out:
                print(out.splitlines()[-1], flush=True)
        except subprocess.TimeoutExpired:
            print(f"{cfg}: TIMEOUT after {CHILD_TIMEOUT_S}s "
                  f"(wedged compile?)", flush=True)


if __name__ == "__main__":
    main()
