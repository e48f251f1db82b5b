"""The conditions of `correct`, copied from `chip_smoke.py` where it had
them (CompileCounter, one part per device) so that the program cannot relax
them, plus the comparison with the plain reference."""

from __future__ import annotations

import numpy as np

# Evaluation-mode logits, program against the float32 reference, as the
# relative Frobenius error |P - R| / |R|, by the backend `auto` resolved and
# by whose parameters they are.  The `fast` path rounds features to bf16
# once at each aggregation input (relative step 2^-8) and sums in float32.
# The cells' features are a class mean plus noise, so most of a row's sum
# is the mean, whose rounding errors over ~100 in-edges average out.
# Measured on the chip (PERF.md section 6, PR 22), binned kernels:
#   initial parameters (Glorot, so the error is the kernels' and the
#     graph's alone)   1.9e-4 to 2.9e-4 over 26 runs of both cells;
#   final parameters (trained, conditioned anew by every seed)
#                      1.8e-4 to 8.3e-4.
# The matmul backend rounds the one-hot products' other operand as well:
# 0.9e-3 to 1.8e-3.  Each bound is about twice the measured worst.
# What a bf16 *accumulate* costs was measured through the cells' own
# kernels (Pallas interpreter, in-degree ~90, the rehearsal recipes at
# degree 50): with every contraction's result rounded to bf16, which is the
# least such a change does, the initial error is 1.0e-3 against 2.3e-4 to
# 3.4e-4 for `fast`.  So the initial bound catches it and the final bound
# alone would not: `test_bf16_accumulate_in_the_binned_kernels_fails`.
# The error falls with the in-degree (less averaging at 13 in-edges: 6e-4
# to 7e-4 for `fast` on the rehearsal recipes as they are), so a much
# sparser cell on the binned kernels brings its own measured bound.
# Only the forward is held to this per run; the backward is compared by
# `benchmark/grad_check.py`, one chip run per configuration.
LOGITS_REL_FRO_TOL = {"binned": {"initial": 6e-4, "final": 2e-3}}
LOGITS_REL_FRO_TOL_OTHER = {"initial": 4e-3, "final": 4e-3}


def logits_tol(backend: str, which: str) -> float:
    """The bound for ``which`` ("initial" | "final") parameters."""
    return LOGITS_REL_FRO_TOL.get(backend, LOGITS_REL_FRO_TOL_OTHER)[which]


# Weight gradients against the reference's, dropout off (grad_check.py).
# Measured on the chip, binned `fast`: 2.7e-4 (regular), 6.2e-4 (skewed).
GRAD_REL_FRO_TOL = 3e-3


def rel_fro(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


class CompileCounter:
    """Backend compile seconds and persistent-cache traffic, from
    jax.monitoring: every compile request that consults the cache, and the
    requests it answered."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self) -> tuple:
        return self.seconds, self.requests, self.hits

    def since(self, mark) -> dict:
        s0, r0, h0 = mark
        hits = self.hits - h0
        return {"compile_s": self.seconds - s0,
                "compiled": self.requests - r0 - hits, "cache_hits": hits,
                "requests": self.requests - r0}


def one_part_per_device(trainer, parts: int) -> bool:
    """`-parts N` on fewer than N devices does not fail, it overcommits
    (parallel/mesh.py): k = N / devices shard blocks per device.  A cell
    that is about N chips must refuse that."""
    return len(set(trainer.mesh.devices.flat)) == parts and trainer.k == 1


def geometries(gdata) -> dict:
    """The forward and transposed-backward geometry of every plan set."""
    out = {}
    for name in ("plans", "plans_local", "plans_remote"):
        p = getattr(gdata, name, None)
        if p is not None and hasattr(getattr(p, "fwd", None), "geom"):
            out[name] = {"fwd": [int(v) for v in p.fwd.geom],
                         "bwd": [int(v) for v in p.bwd.geom]}
    return out
