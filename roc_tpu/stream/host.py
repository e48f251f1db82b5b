"""Sanctioned host-store allocator for the streaming tier.

Every host-resident array the stream executor stages to device — shard
tables, boundary-activation stores, cotangent stores, edge arrays —
comes from :func:`alloc` / :func:`to_store` here, and roclint's
``unpinned-host-buffer`` rule flags raw ``np.empty``/``np.zeros``
allocations elsewhere under ``roc_tpu/stream/`` to keep it that way.

On backends that expose a ``pinned_host`` memory space (TPU; some GPU
builds), :func:`alloc` materializes the store as a JAX buffer committed
to pinned host memory and hands back a *zero-copy numpy view* of it:
the ring's prefetch ``device_put`` and the overlapped gradient scatter
then run DMA straight out of page-locked memory instead of paying the
pageable staging copy (the PyTorch-Direct lever, on the TPU runtime).
The view is verified to actually alias the buffer (pointer equality)
before it is trusted; any surprise — no pinned space, a copying
``__array__``, a read-only view — falls back to plain numpy, counted in
:func:`stats` so tests can pin the fallback path on CPU.

``STREAM_BW_BYTES_S`` is the assumed host<->device streaming bandwidth
used for the ledger's predicted transfer-seconds pair
(``ROC_STREAM_BW_BYTES`` overrides).
"""

from __future__ import annotations

import os
import warnings

import numpy as np

__all__ = ["alloc", "to_store", "pinned_supported", "stats", "reset_stats",
           "STREAM_BW_BYTES_S"]


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "")
    try:
        return float(raw) if raw else default
    except ValueError:
        return default


# Assumed sustained host<->device bandwidth for the stream_xfer_s ledger
# prediction.  10 GB/s is the conservative pinned-DCN figure; override
# with ROC_STREAM_BW_BYTES when calibrating a specific host.
STREAM_BW_BYTES_S = _env_float("ROC_STREAM_BW_BYTES", 10e9)

# Pinned JAX buffers whose numpy views are live stores: the view aliases
# the buffer's memory, so the buffer must outlive it.
_KEEPALIVE: list = []

_pinned_bytes = 0
_fallback_bytes = 0
_warned = False


def pinned_supported() -> bool:
    """True when the default device exposes a pinned_host memory space."""
    try:
        import jax
        dev = jax.local_devices()[0]
        return any(m.kind == "pinned_host"
                   for m in dev.addressable_memories())
    except Exception:
        return False


def _warn_once(msg: str) -> None:
    global _warned
    if not _warned:
        _warned = True
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _pinned_view(shape, dtype):
    """Zero-copy writable numpy view of a pinned_host JAX buffer, or None
    when anything about the aliasing cannot be proven."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    dev = jax.local_devices()[0]
    sharding = SingleDeviceSharding(dev, memory_kind="pinned_host")
    buf = jax.device_put(jnp.zeros(shape, dtype=dtype), sharding)
    buf.block_until_ready()
    arr = np.asarray(buf)
    # Trust the view only if it provably aliases the pinned buffer:
    # a copying __array__ would silently reintroduce pageable staging.
    try:
        ptr = arr.__array_interface__["data"][0]
        bufptr = buf.unsafe_buffer_pointer()
    except Exception:
        return None
    if ptr != bufptr:
        return None
    try:
        arr.setflags(write=True)
    except ValueError:
        return None
    _KEEPALIVE.append(buf)
    return arr


def alloc(shape, dtype) -> np.ndarray:
    """Zero-initialized host store, pinned when the backend supports it."""
    global _pinned_bytes, _fallback_bytes
    dtype = np.dtype(dtype)
    if pinned_supported():
        try:
            arr = _pinned_view(shape, dtype)
        except Exception as e:  # unexpected runtime refusal
            _warn_once(f"pinned_host allocation failed ({e!r}); "
                       "stream stores fall back to pageable memory")
            arr = None
        if arr is not None:
            _pinned_bytes += arr.nbytes
            return arr
    arr = np.zeros(shape, dtype)
    _fallback_bytes += arr.nbytes
    return arr


def to_store(src) -> np.ndarray:
    """Copy ``src`` into a freshly allocated store (pinned when possible)."""
    src = np.asarray(src)
    arr = alloc(src.shape, src.dtype)
    arr[...] = src
    return arr


def stats() -> dict:
    """Allocation accounting for bench artifacts and the fallback test."""
    return {"pinned": pinned_supported(),
            "pinned_bytes": int(_pinned_bytes),
            "fallback_bytes": int(_fallback_bytes)}


def reset_stats() -> None:
    global _pinned_bytes, _fallback_bytes, _warned
    _pinned_bytes = 0
    _fallback_bytes = 0
    _warned = False
    _KEEPALIVE.clear()
