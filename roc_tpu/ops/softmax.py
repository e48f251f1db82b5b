"""Masked softmax cross-entropy + training metrics (the reference's
SoftmaxCrossEntropy op).

Reference semantics (softmax_kernel.cu):
  * gradient: ``softmax(logits) - onehot_label``, zeroed for every vertex
    whose mask != TRAIN, with NO normalization by the train count
    (softmax_backward, softmax_kernel.cu:19-33).  The scalar loss whose
    gradient is exactly that is the *unreduced sum* of cross-entropy over
    train vertices — that is what :func:`masked_softmax_cross_entropy`
    returns, so `jax.grad` reproduces the reference update bit-for-bit in
    expectation.
  * reported "train_loss" metric: ``Σ_train (1 - p_true)`` — a margin-style
    sum, NOT the CE above (calc_loss, softmax_kernel.cu:65).  Reproduced
    exactly in :func:`perf_metrics` for curve comparability.
  * accuracy: argmax over softmax probabilities vs. one-hot label, tallied
    separately for TRAIN/VAL/TEST masks (softmax_kernel.cu:50-79).  NONE
    (and our pad rows) count nowhere.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from roc_tpu.obs import scopes

# Mask encoding, gnn.h:98-103.
MASK_TRAIN, MASK_VAL, MASK_TEST, MASK_NONE = 0, 1, 2, 3


class PerfMetrics(NamedTuple):
    """Mirror of the reference's PerfMetrics struct (softmax_kernel.cu:35-40)."""
    train_loss: jnp.ndarray   # Σ_train (1 - p_true)
    train_all: jnp.ndarray
    train_correct: jnp.ndarray
    val_all: jnp.ndarray
    val_correct: jnp.ndarray
    test_all: jnp.ndarray
    test_correct: jnp.ndarray


def masked_softmax_cross_entropy(logits, labels, mask):
    """Sum of CE over MASK_TRAIN rows (the loss whose grad is the reference's).

    logits: [N, C]; labels: [N, C] one-hot float; mask: [N] int32.
    """
    with scopes.scope("roc.loss"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.sum(labels * logp, axis=-1)
        train = (mask == MASK_TRAIN).astype(logits.dtype)
        return jnp.sum(ce * train)


def perf_metrics(logits, labels, mask) -> PerfMetrics:
    """The reference's evaluation pass (calc_loss, softmax_kernel.cu:41-79)."""
    with scopes.scope("roc.metrics"):
        probs = jax.nn.softmax(logits, axis=-1)
        p_true = jnp.sum(probs * labels, axis=-1)
        # Reference picks the first strictly-greater maximum starting from
        # 0.0; probabilities are strictly positive, so this is plain argmax.
        correct = jnp.argmax(probs, axis=-1) == jnp.argmax(labels, axis=-1)

        def tally(m):
            sel = mask == m
            return jnp.sum(sel), jnp.sum(sel & correct)

        train_all, train_correct = tally(MASK_TRAIN)
        val_all, val_correct = tally(MASK_VAL)
        test_all, test_correct = tally(MASK_TEST)
        train_loss = jnp.sum(jnp.where(mask == MASK_TRAIN, 1.0 - p_true, 0.0))
        return PerfMetrics(train_loss, train_all, train_correct,
                           val_all, val_correct, test_all, test_correct)


def format_metrics(epoch: int, m: PerfMetrics, infer: bool = True) -> str:
    """Reference's printed report line (softmax_kernel.cu:141-152)."""
    mode = "\t[INFER]" if infer else "[TRAIN]"
    def pct(c, a):
        return 100.0 * float(c) / max(float(a), 1.0)
    return (f"{mode}[{epoch}] train_loss: {float(m.train_loss):.4f}  "
            f"train_accuracy: {pct(m.train_correct, m.train_all):.2f}%"
            f"({int(m.train_correct)}/{int(m.train_all)})  "
            f"val_accuracy: {pct(m.val_correct, m.val_all):.2f}%"
            f"({int(m.val_correct)}/{int(m.val_all)})  "
            f"test_accuracy: {pct(m.test_correct, m.test_all):.2f}%"
            f"({int(m.test_correct)}/{int(m.test_all)})")
