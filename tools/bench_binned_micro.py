"""Microbenchmark the binned aggregation phases on the real chip.

Times, at Reddit scale (E=23.5M, H=256):
  - full run_binned (fwd plan)
  - phase-1 alone (per group, summed)
  - phase-2 alone (per group, summed, staging reused)
  - run_binned with the single-buffered phase-1 fallback

Outputs one line per measurement; scalar-reduces results so the
device-to-host transfer of a full [N, H] result stays out of the timings.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from roc_tpu import obs
from roc_tpu.ops.pallas.binned import (
    build_binned_plan, run_binned, _p1_run, _p2_run, _pad_to, SB, CH2)

H = int(os.environ.get("MB_H", "256"))
E = int(os.environ.get("MB_E", str(23_526_267)))
N = int(os.environ.get("MB_N", str(232_965)))
REPS = int(os.environ.get("MB_REPS", "5"))

rng = np.random.default_rng(0)
print(f"# building edges E={E} N={N} H={H}", file=sys.stderr)
src = rng.integers(0, N, E).astype(np.int64)
dst = rng.integers(0, N, E).astype(np.int64)
t0 = time.time()
plan = build_binned_plan(src, dst, N, N)
print(f"# plan built in {time.time()-t0:.1f}s  G={plan.p1_blk.shape[0]} "
      f"C1={plan.p1_blk.shape[1]} C2={plan.p2_obi.shape[1]} "
      f"bpg={plan.bins_per_group}", file=sys.stderr)
x = jnp.asarray(rng.standard_normal((N, H), dtype=np.float32))


def sync(v):
    return np.asarray(jnp.sum(v))


def timeit(name, fn):
    fn()  # warmup/compile
    sync_out = fn()
    _ = sync(sync_out)
    with obs.span("bench_micro", name=name, reps=REPS) as sp:
        for _ in range(REPS):
            out = fn()
        _ = sync(out)
    dt = sp.dur_s / REPS
    print(f"{name}: {dt*1e3:.1f} ms")
    return dt


G, C1 = plan.p1_blk.shape
C2 = plan.p2_obi.shape[1]
Hp = _pad_to(H, 128)
xp = jnp.pad(x, ((0, _pad_to(plan.table_rows, SB) - x.shape[0]),
                 (0, Hp - H)))
stg_rows = C2 * CH2

timeit("full run_binned", lambda: run_binned(x, plan))


@jax.jit
def p1_all(xp, plan):
    def body(_, gp):
        srcl, off, blk = gp
        stg = _p1_run(xp, blk, off, srcl, C1, stg_rows)
        return None, jnp.sum(stg.astype(jnp.float32))
    _, s = jax.lax.scan(body, None,
                        (plan.p1_srcl, plan.p1_off, plan.p1_blk))
    return s


timeit("phase-1 only (all groups)", lambda: p1_all(xp, plan))

# phase-2 alone: reuse one group's staging buffer
stg0 = _p1_run(xp, plan.p1_blk[0], plan.p1_off[0], plan.p1_srcl[0],
               C1, stg_rows)
_ = sync(stg0)


@jax.jit
def p2_all(stg0, plan):
    def body(_, gp):
        dstl, obi, first = gp
        out = _p2_run(stg0, obi, first, dstl, C2, plan.bins_per_group * 512)
        return None, jnp.sum(out)
    _, s = jax.lax.scan(body, None,
                        (plan.p2_dstl, plan.p2_obi, plan.p2_first))
    return s


timeit("phase-2 only (all groups, same stg)", lambda: p2_all(stg0, plan))

jrb = jax.jit(lambda x, plan: jnp.sum(run_binned(x, plan)))
timeit("jit(run_binned) scalar-out", lambda: jrb(x, plan))

import functools
jrb2 = jax.jit(functools.partial(run_binned))
timeit("jit(run_binned) full-out", lambda: jrb2(x, plan))
