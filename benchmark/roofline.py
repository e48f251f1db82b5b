"""Published peaks and the least work a kernel's shapes allow.

Copied from `roc_tpu/obs/roofline.py` (PEAKS, and the aggregate term of
`model_flops_bytes`) so that a later PR to the program cannot move the
yardstick.  A device kind that is not in the table is an error, never a
default.
"""

from __future__ import annotations

PEAKS = {
    # device_kind: (bf16 FLOP/s, HBM bytes/s, source)
    "TPU v5 lite": (197e12, 819e9,
                    'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                    'bf16, 819 GB/s HBM, 16 GB'),
}


def peaks_for(device_kind: str) -> tuple:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind={device_kind!r}; the table "
            f"in benchmark/roofline.py has {sorted(PEAKS)}") from None


def aggregation_sweeps(shapes: dict) -> tuple:
    """(FLOPs, HBM bytes) one chip needs for one training epoch's
    aggregation sweeps, from shapes alone.

    Every aggregate op of the model runs twice an epoch (forward, and the
    transposed sweep of the backward pass) at the width of the linear
    before it.  One sweep over E in-edges at width F: 2*E*F FLOPs (one
    multiply-add per edge and feature); E*F*b bytes of source rows (every
    edge reads its source row once: a gather does not cache across
    destinations in the worst case, the convention of the program's own
    roofline), N*F*4 bytes of results and E*4 bytes of indices.  b is 2 on
    the `fast` path (rows rounded to bf16 once), 4 on `exact`.  A chip of a
    P-chip cell holds E/P edges and N/P rows."""
    chips = shapes["chips"]
    n, e = shapes["nodes"] / chips, shapes["in_edges"] / chips
    b = 2 if shapes["precision"] == "fast" else 4
    flops = nbytes = 0.0
    for width in shapes["aggregate_widths"]:
        flops += 2 * 2.0 * e * width
        nbytes += 2 * (e * width * b + n * width * 4 + e * 4)
    return flops, nbytes


SHAPE_FUNCTIONS = {"aggregation_sweeps": aggregation_sweeps}


def least_seconds(shapes_fn: str, shapes: dict, device_kind: str) -> tuple:
    """(least seconds, "bytes" | "flops": which peak binds)."""
    flops, nbytes = SHAPE_FUNCTIONS[shapes_fn](shapes)
    peak_flops, peak_bw, _ = peaks_for(device_kind)
    by_flops, by_bytes = flops / peak_flops, nbytes / peak_bw
    return max(by_flops, by_bytes), "bytes" if by_bytes >= by_flops else "flops"
