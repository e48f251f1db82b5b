"""Adam with the reference's exact update (optimizer_kernel.cu:44-63,
optimizer.cc:79-85).

Reference formulation, reproduced verbatim:
    gt = WGrad + weight_decay * W        (L2 folded into the gradient, NOT
                                          decoupled AdamW)
    mt = beta1*M + (1-beta1)*gt
    vt = beta2*V + (1-beta2)*gt*gt
    W -= alpha_t * mt / (sqrt(vt) + epsilon)
with bias correction applied to the step size once per epoch *before* the
updates:  alpha_t = alpha * sqrt(1-beta2^t) / (1-beta1^t)  (AdamOptimizer::next).
LR decay multiplies ``alpha`` every decay_steps epochs in the driver
(gnn.cc:100-101), not here.

Where the reference gathers per-GPU gradient replicas onto ONE GPU and sums
them serially before updating (optimizer_kernel.cu:88-94), the TPU version
takes already-psum'ed gradients and runs the update replicated on every chip
— same math, no gather bottleneck.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from roc_tpu.obs import scopes


class AdamState(NamedTuple):
    m: Any            # pytree like params
    v: Any            # pytree like params
    t: jnp.ndarray    # int32 epoch counter (number of next() calls)


class Adam:
    def __init__(self, alpha: float = 0.001, beta1: float = 0.9,
                 beta2: float = 0.999, weight_decay: float = 0.0,
                 epsilon: float = 1e-8):
        self.alpha = alpha  # mutated by driver LR decay, like optimizer->alpha
        self.beta1 = beta1
        self.beta2 = beta2
        self.weight_decay = weight_decay
        self.epsilon = epsilon

    def init(self, params) -> AdamState:
        zeros = jax.tree.map(jnp.zeros_like, params)
        return AdamState(m=zeros, v=jax.tree.map(jnp.zeros_like, params),
                         t=jnp.zeros((), jnp.int32))

    def update(self, params, grads, state: AdamState, alpha):
        """One step; pure/jittable.  ``alpha`` is the (host-decayed) base LR."""
        with scopes.scope("roc.adam"):
            t = state.t + 1
            tf = t.astype(jnp.float32)
            alpha_t = (alpha * jnp.sqrt(1.0 - self.beta2 ** tf)
                       / (1.0 - self.beta1 ** tf))

            b1, b2 = self.beta1, self.beta2
            wd, eps = self.weight_decay, self.epsilon
            gt = jax.tree.map(lambda g, w: g + wd * w, grads, params)
            new_m = jax.tree.map(lambda m, g: b1 * m + (1.0 - b1) * g,
                                 state.m, gt)
            new_v = jax.tree.map(lambda v, g: b2 * v + (1.0 - b2) * g * g,
                                 state.v, gt)
            new_params = jax.tree.map(
                lambda w, m, v: w - alpha_t * m / (jnp.sqrt(v) + eps),
                params, new_m, new_v)
            return new_params, AdamState(new_m, new_v, t)
