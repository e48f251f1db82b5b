"""What this process compiles for, asked in one place.

``on_tpu()`` is THE predicate for "kernels compile for a TPU": it picks
compiled Pallas over interpret mode, the plan backends over xla, timed
autotune trials over the surrogate, and decides whether an MFU/roofline
figure may be claimed.  JAX itself falls back to the CPU when libtpu
finds no chip, so every entry point prints ``banner()`` on its first
lines: a run that landed on the CPU says so.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def describe() -> dict:
    """``{"platform", "kind", "count"}`` exactly as JAX reports them."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def banner() -> str:
    """One-line device stamp for log lines that carry a time."""
    d = describe()
    return f"platform={d['platform']} device_kind={d['kind']!r} " \
           f"devices={d['count']}"
