"""In-degree normalization (the reference's InDegreeNorm op).

``out[v] = x[v] / sqrt(in_degree(v))`` (norm_coop_kernel,
graphnorm_kernel.cu:19-57).  Applied before AND after aggregation this yields
the symmetric D^{-1/2} A D^{-1/2} GCN propagation (gnn.cc:82-84).  The
backward pass is the same scaling (graphnorm_kernel.cu:126-136) — which JAX
autodiff derives for free since the op is linear.

The reference recomputes degrees from row_ptr inside the kernel every call;
we precompute the degree vector once at partition time (Partition.in_degree,
pad rows get degree 1) and make this a fused broadcast-multiply.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def indegree_norm(x, in_degree):
    """x: [N, H]; in_degree: [N] float.

    No zero-guard needed: degrees are >= 1 everywhere by construction
    (self-edges on real nodes, explicit 1.0 on pad rows).
    """
    return x * jax.lax.rsqrt(in_degree)[:, None]


LAYER_NORM_EPS = 1e-5


def layer_norm(x, gain, bias, eps: float = LAYER_NORM_EPS):
    """Row LayerNorm with parameters (Ba et al. 2016): every row of ``x``
    [N, H] is centred and scaled by its own mean and (biased) variance over
    the H features, then ``* gain + bias`` ([H] each).  The reference has
    no such op; the graph transformer's hidden layers need it
    (models/tconv.py).  Statistics in float32 whatever ``x`` is."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return (xc * jax.lax.rsqrt(var + eps) * gain + bias).astype(x.dtype)
