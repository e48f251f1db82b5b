"""Dense layer (the reference's Linear op).

The reference computes ``out = Wᵀ·x`` with cuBLAS (linear_kernel.cu:76-80;
no bias anywhere — the weight region is the op's only parameter,
linear.cc:39-44) plus an optionally fused cuDNN ReLU (linear_kernel.cu:81-104)
whose backward is a custom reluBackward kernel (linear_kernel.cu:120-127).

TPU mapping: one ``jnp.dot`` on the MXU; in node-major layout ([N, H] rather
than the reference's hidden-major) this is ``x @ W`` with W: [in, out].  The
fused activation needs no hand fusion — XLA fuses the elementwise max into
the GEMM epilogue — and the three backward GEMMs (weight-grad, input-grad,
linear_kernel.cu:220-231) come from autodiff.
"""

from __future__ import annotations

import jax.numpy as jnp

from roc_tpu.ops.activation import apply_activation


def linear(x, w, activation: str = "none", bias=None):
    """x: [N, in_dim]; w: [in_dim, out_dim]; activation in {none,relu,sigmoid};
    ``bias`` [out_dim] is added before the activation (the reference has
    none; a builder asks for it, models/model.py ``Model.linear``).

    fp32 inputs use full-precision accumulation (`highest`) to match the
    reference's cuBLAS SGEMM; bf16 inputs (the opt-in fast path) take the
    MXU's native bf16×bf16→fp32 route, where `highest` would cost 6 passes.
    """
    precision = "highest" if x.dtype == jnp.float32 else None
    out = jnp.dot(x, w.astype(x.dtype), precision=precision,
                  preferred_element_type=jnp.float32).astype(x.dtype)
    if bias is not None:
        out = out + bias.astype(x.dtype)
    return apply_activation(out, activation)
