"""The ONE roofline: peak constants + epoch FLOPs/bytes walked from the
op IR, shared by bench.py, the memory estimator, and the binned kernels.

Before this module, the peak-FLOPs/bandwidth constants and the
model-FLOPs formula lived twice (bench.py and memory/estimator.py) and
the HBM-bandwidth figure a third time (ops/pallas/binned.py) — exactly
the measurement-methodology drift that corrupts cross-run comparisons.
Every mfu / roofline_frac / recompute-price figure in the tree now flows
through here, so a constant re-fit lands everywhere at once.

Stdlib-only on purpose: kernel modules (ops/pallas) import the constants
at module load, before jax/numpy are welcome.

Accounting convention (standard MFU): count matmul/aggregation terms
only — norms, activations, dropout, and the optimizer are O(N*F) noise
against the N*F*F' and E*F terms.  Per op, for one training epoch
(fwd + bwd + opt):

  linear Fin->Fout:  6*N*Fin*Fout FLOPs (fwd + dX + dW),
                     3*(N*Fin + N*Fout)*b bytes (3 passes/epoch)
  aggregate at F:    4*E*F FLOPs (fwd + transposed bwd),
                     2*(E*F*b + N*F*b + E*4) bytes — every edge reads its
                     source row once per pass (gathers don't cache across
                     destinations in the worst case) + result writes +
                     index bytes  [scattergather_kernel.cu:20-76 is the
                     reference's corresponding hot kernel]
  gat (K heads, head_dim D): the projection matmul folded into the op
                     (Fin -> K*D) plus the aggregation sweep at K*D; the
                     per-edge score/softmax terms are O(E*K) and dropped.

b = 2 (bf16 fast path) or 4 (fp32 exact).  Walking the IR (instead of
re-deriving widths from a layer spec) makes residual projections, GAT
head folding, and SAGE concat widths come out right by construction.
"""

from __future__ import annotations

from typing import NamedTuple

__all__ = ["PEAKS", "PEAK_FLOPS", "PEAK_BW", "peaks_for", "itemsize_for",
           "model_flops_bytes", "roofline_time", "mfu", "roofline_frac"]


class Peaks(NamedTuple):
    flops: float    # bf16 MXU FLOP/s per chip
    bw: float       # HBM bytes/s per chip
    source: str


# Published per-chip peaks keyed by ``jax.devices()[0].device_kind`` — the
# single definition site.  A kind that is not here has no roofline: add
# its row with its source, never a default.
PEAKS = {
    "TPU v5 lite": Peaks(197e12, 819e9,
                         'Google Cloud documentation, "TPU v5e": 197 '
                         'TFLOP/s bf16, 819 GB/s HBM'),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peak row for a device kind; raises where a roofline figure
    was requested for hardware the table does not describe."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind={device_kind!r}: MFU / "
            f"roofline figures are only claimed for {sorted(PEAKS)} "
            f"(roc_tpu/obs/roofline.py PEAKS)") from None


# Planning constants for the host-side cost models (memory estimator
# recompute prices, the serve p50 bound):
# those price a v5e ahead of time, on any host.  Measured figures go
# through mfu()/roofline_frac() with the device's own kind instead.
PEAK_FLOPS, PEAK_BW = PEAKS["TPU v5 lite"][:2]


def itemsize_for(precision: str = "fast") -> int:
    """Feature-stream element width under the aggregation precision."""
    return 2 if precision == "fast" else 4


def model_flops_bytes(model, num_nodes: int, num_edges: int,
                      precision: str = "fast"):
    """(FLOPs, min HBM bytes) for ONE training epoch of ``model`` on a
    graph of ``num_nodes`` rows / ``num_edges`` in-edges, walked from the
    op IR (models/model.py) under the convention in the module docstring.

    The bytes figure is the standard SpMM roofline lower bound;
    roofline_frac = that bound over the measured time, 1.0 = at the
    roofline.
    """
    N, E = float(num_nodes), float(num_edges)
    b = itemsize_for(precision)
    dims = {model.input.id: model.input.dim}
    flops = nbytes = 0.0
    for op in model.ops:
        a = dims[op.inputs[0]]
        if op.kind == "linear":
            out = int(op.attrs["out_dim"])
            flops += 6.0 * N * a * out
            nbytes += 3.0 * (N * a * b + N * out * b)
        elif op.kind == "gat" and op.attrs.get("score") == "dot":
            # four projections; six row sweeps at the attention heads'
            # width, 2 E D FLOPs each (forward: the score contraction and
            # the weighted sum; backward: the contraction for de, then dq,
            # dk and dv)
            out = int(op.attrs["heads"]) * int(op.attrs["head_dim"])
            # (attrs read here, not models.model's helpers: stdlib-only)
            proj = out * int(op.attrs.get("mean_heads", 1))
            flops += 6.0 * N * a * (3 * proj + out) + 6 * 2.0 * E * proj
            nbytes += 3.0 * (N * a * b + N * (3 * proj + out) * b)
            nbytes += 6.0 * (E * proj * b + N * proj * b + E * 4)
        elif op.kind == "gat" and op.attrs.get("score") == "dynamic":
            # two projections; four row sweeps (forward the score and the
            # weighted sum; backward one over each plan), 2 E D FLOPs each
            out = int(op.attrs["heads"]) * int(op.attrs["head_dim"])
            flops += 6.0 * N * a * 2 * out + 4 * 2.0 * E * out
            nbytes += 3.0 * (N * a * b + N * 2 * out * b)
            nbytes += 4.0 * (E * out * b + N * out * b + E * 4)
        elif op.kind == "gat":
            out = int(op.attrs["heads"]) * int(op.attrs["head_dim"])
            flops += 6.0 * N * a * out + 4.0 * E * out
            nbytes += 3.0 * (N * a * b + N * out * b)
            nbytes += 2.0 * (E * out * b + N * out * b + E * 4)
        elif op.kind == "aggregate":
            out = a
            flops += 4.0 * E * out
            nbytes += 2.0 * (E * out * b + N * out * b + E * 4)
        else:
            out = a          # elementwise: O(N*F) noise, not counted
        dims[op.out] = out
    return flops, nbytes


def forward_flops_bytes(model, num_nodes: int, num_edges: int,
                        precision: str = "fast"):
    """(FLOPs, min HBM bytes) for ONE inference forward — the serving
    window's cost.  Same IR walk and convention as ``model_flops_bytes``
    with the backward shares removed: a linear is one 2·N·Fin·Fout pass
    over one byte-sweep (training's 6/3 is fwd + two bwd), an aggregate
    is one 2·E·F pass over one edge-stream sweep (training's 4/2).  The
    serving ledger pair (serve/engine.py) predicts window p50 from this
    bound; `python -m roc_tpu.obs calibration` then reports how far the
    measured serving path sits above it."""
    N, E = float(num_nodes), float(num_edges)
    b = itemsize_for(precision)
    dims = {model.input.id: model.input.dim}
    flops = nbytes = 0.0
    for op in model.ops:
        a = dims[op.inputs[0]]
        if op.kind == "linear":
            out = int(op.attrs["out_dim"])
            flops += 2.0 * N * a * out
            nbytes += N * a * b + N * out * b
        elif op.kind == "gat" and op.attrs.get("score") == "dot":
            # forward: four projections, the score contraction and the
            # weighted sum (two row sweeps at the attention heads' width)
            out = int(op.attrs["heads"]) * int(op.attrs["head_dim"])
            # (attrs read here, not models.model's helpers: stdlib-only)
            proj = out * int(op.attrs.get("mean_heads", 1))
            flops += 2.0 * N * a * (3 * proj + out) + 2 * 2.0 * E * proj
            nbytes += N * a * b + N * (3 * proj + out) * b
            nbytes += 2.0 * (E * proj * b + N * proj * b + E * 4)
        elif op.kind == "gat" and op.attrs.get("score") == "dynamic":
            # forward: two projections, the score and the weighted sum
            out = int(op.attrs["heads"]) * int(op.attrs["head_dim"])
            flops += 2.0 * N * a * 2 * out + 2 * 2.0 * E * out
            nbytes += N * a * b + N * 2 * out * b
            nbytes += 2.0 * (E * out * b + N * out * b + E * 4)
        elif op.kind == "gat":
            out = int(op.attrs["heads"]) * int(op.attrs["head_dim"])
            flops += 2.0 * N * a * out + 2.0 * E * out
            nbytes += N * a * b + N * out * b
            nbytes += E * out * b + N * out * b + E * 4
        elif op.kind == "aggregate":
            out = a
            flops += 2.0 * E * out
            nbytes += E * out * b + N * out * b + E * 4
        else:
            out = a          # elementwise: O(N*F) noise, not counted
        dims[op.out] = out
    return flops, nbytes


def roofline_time(flops: float, nbytes: float, n_dev: int = 1,
                  peaks: Peaks = None) -> float:
    """Best-possible epoch seconds: max of the compute- and memory-bound
    lower bounds across ``n_dev`` chips (``peaks`` None = the v5e
    planning constants)."""
    pf, pb = (PEAK_FLOPS, PEAK_BW) if peaks is None else peaks[:2]
    return max(flops / (n_dev * pf), nbytes / (n_dev * pb))


def mfu(flops: float, seconds: float, n_dev: int, device_kind: str):
    """Achieved model-FLOPs/s over the peak of ``n_dev`` chips of
    ``device_kind``; None for a non-positive time."""
    pf = peaks_for(device_kind).flops
    if seconds <= 0.0:
        return None
    return flops / seconds / (n_dev * pf)


def roofline_frac(flops: float, nbytes: float, seconds: float,
                  n_dev: int, device_kind: str):
    """roofline_time over the measured seconds; 1.0 = at the roofline."""
    peaks = peaks_for(device_kind)
    if seconds <= 0.0:
        return None
    return roofline_time(flops, nbytes, n_dev, peaks) / seconds
