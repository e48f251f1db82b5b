"""Dropout with explicit PRNG keys (the reference's Dropout op).

The reference uses cuDNN stateful dropout with a per-op reserve space carved
from the framebuffer allocator (dropout_kernel.cu:19-59) and a separate
plain-copy task for inference (dropout_kernel.cu:159-180).  On TPU the
idiomatic design is stateless: a `jax.random` key threaded through the step
function — same inverted-dropout math (keep w.p. 1-rate, scale by
1/(1-rate)), no reserved state, bitwise reproducible.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def dropout_keep(key, rate: float, shape):
    """The keep mask ``dropout`` draws from ``key``: Bernoulli(1 - rate)."""
    return jax.random.bernoulli(key, 1.0 - rate, shape=shape)


def dropout(key, x, rate: float, train: bool):
    """Inverted dropout; identity when not training or rate == 0."""
    if not train or rate == 0.0:
        return x
    return jnp.where(dropout_keep(key, rate, x.shape), x / (1.0 - rate), 0.0)
