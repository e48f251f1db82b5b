"""The autotuner's candidate space.

Two layers, matching how the kernels are parameterized:

* The **Geometry lattice** — every field the plan builders consume
  (windows, chunk widths, slot, group target, flat/unit).  Generated by
  crossing per-axis value sets and filtering through the SAME invariants
  production enforces: ``Geometry.check()`` plus binned's VMEM admission
  gate.  This is the part `choose_geometry` can act on directly, so only
  these fields persist as a tuned entry's ``geom``.

* The **knob axes** — kernel parameters that are NOT Geometry fields
  (module constants / lowering choices): the ``_DMA_CLS`` staging-copy
  size classes, ``dimension_semantics`` arbitrary-vs-parallel on the
  grid and the double-buffer depth.  The
  Geometry tuple is append-only and hashed into the v3 plan-cache key,
  so these CANNOT ride the tuple without invalidating every cached plan;
  they travel in a side-channel dict instead, priced by the surrogate's
  knob factors (documented priors, refit on device) and recorded in the
  tuned entry's ``knobs`` for the device harness to apply by patching
  the module constants — exactly how tools/sweep_binned.py steers the
  module-default geometry today.

Everything is generated in sorted, deterministic order: candidate
identity (its ``label``) is part of the surrogate's noise key, so the
lattice's iteration order is part of the byte-identical-tuned.json
contract.
"""

from __future__ import annotations

from typing import NamedTuple

from roc_tpu.ops.pallas.binned import (Geometry, _DMA_CLS, _MODEL_H,
                                       _VMEM_NOMINAL_CAP, _vmem_bytes,
                                       staging_itemsize)

# Geometry axes.  Window pairs stay square or one step apart — the cost
# model's cell statistics key on (sb, rb), and the presets' history says
# strongly rectangular windows lose on either MACs (wide sb) or residency
# (wide rb) before the lattice's other levers can compensate.
_WINDOWS = ((512, 512), (512, 1024), (1024, 512), (1024, 1024),
            (2048, 2048))
_SLOTS = (16, 32, 64, 128)
_CHS = (1024, 2048, 4096)
_CH2S = (1024, 2048, 4096, 8192)
_GRTS = (0, 1 << 23)           # group-row target: default vs wide (fewer
#                                groups = less per-(group, block) rounding)

# Knob axes (flat schedules only, where they exist): DMA size classes and
# double-buffer depth.  dimension_semantics stays a recorded axis with a
# neutral prior — both phases carry a cross-step staging/accumulation
# dependence, so "parallel" is a hardware experiment, not a CI claim.
_DMA_CLASSES = (_DMA_CLS, (32, 8, 1))
_DEPTHS = (2, 3)
_SEMANTICS = ("arbitrary",)


class KernelConfig(NamedTuple):
    """One candidate: a full Geometry plus the non-Geometry knobs."""
    geom: Geometry
    dma_cls: tuple = _DMA_CLS
    dimension_semantics: str = "arbitrary"
    depth: int = 2

    @property
    def label(self) -> str:
        """Stable identity string: the surrogate's noise key and the
        search's tie-breaker, so it must determine the candidate.  The
        "+m0+f1+g0" tail is fixed text: labels recorded in trial keys
        and noise draws keep the strings they were written with."""
        g = "/".join(str(v) for v in tuple(self.geom))
        k = (f"dma{'.'.join(str(c) for c in self.dma_cls)}"
             f"+{self.dimension_semantics}+d{self.depth}+m0+f1+g0")
        return f"{g}|{k}"

    def knobs(self) -> dict:
        """The tuned-entry side-channel (store.py schema)."""
        return {"dma_cls": list(self.dma_cls),
                "dimension_semantics": self.dimension_semantics,
                "depth": self.depth}


def _geometries(storage_dtype: str):
    """The admissible Geometry lattice, deterministically ordered."""
    out = []
    units = (0, 16) if storage_dtype == "bf16" else (0,)
    for sb, rb in _WINDOWS:
        for ch in _CHS:
            for ch2 in _CH2S:
                for flat in (0, 1):
                    grts = (0,) if flat else _GRTS   # flat packing already
                    #                 removes the rounding grt buys down
                    for grt in grts:
                        for unit in (units if flat else (0,)):
                            # slot is dead in flat kernels but must still
                            # satisfy the divisibility invariant; one
                            # canonical value keeps flat candidates from
                            # duplicating per slot.
                            slots = (16,) if flat else _SLOTS
                            for slot in slots:
                                g = Geometry(sb, ch, slot, rb, ch2,
                                             grt=grt, flat=flat, unit=unit)
                                if _admissible(g):
                                    out.append(g)
    return out


def _admissible(g: Geometry) -> bool:
    try:
        g.check()
    except AssertionError:
        return False
    return _vmem_bytes(g) <= _VMEM_NOMINAL_CAP


def candidate_lattice(storage_dtype: str = "fp32") -> list:
    """All KernelConfigs the screen stage prices.  Knob axes only fan out
    where they act (flat schedules own the DMA run lists), keeping the
    lattice in the hundreds — big enough that the hand presets are a
    measure-zero subset, small enough that the O(cells) exact-schedule
    screen stays sub-second per shape on CPU."""
    if storage_dtype not in ("fp32", "bf16"):
        raise ValueError(f"storage_dtype={storage_dtype!r}")
    cfgs = []
    for g in _geometries(storage_dtype):
        if not g.flat:
            cfgs.append(KernelConfig(geom=g))
            continue
        for dma_cls in _DMA_CLASSES:
            for sem in _SEMANTICS:
                for depth in _DEPTHS:
                    # a third pipeline buffer is one more staging chunk
                    # resident in VMEM — charge it before admitting
                    if depth == 3 and (_vmem_bytes(g) + g.ch * _MODEL_H
                                       * staging_itemsize(g, False)
                                       > _VMEM_NOMINAL_CAP):
                        continue
                    cfgs.append(KernelConfig(
                        geom=g, dma_cls=dma_cls,
                        dimension_semantics=sem, depth=depth))
    # Sorted by label: the lattice's order is part of the determinism
    # contract (ties in the screen break by position).
    cfgs.sort(key=lambda c: c.label)
    return cfgs
