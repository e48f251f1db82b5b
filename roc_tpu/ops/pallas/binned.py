"""Binned two-phase sum-aggregation — the TPU answer to the reference's
`aggre_coop_kernel` (scattergather_kernel.cu:20-76) at full-graph scale.

Why a second kernel family exists (measured on v5e, docs/PERF.md): XLA
lowers the [E]-row gather behind every aggregation to a dynamic-slice loop
that issues one row per ~10 ns and reads a full (8,128) tile per row — at
Reddit scale (23.5M edges) the gather alone costs 235-300 ms, ~80% of the
epoch.  The reference never pays this: its CUDA kernel's random accesses
ride a GPU cache hierarchy.  TPUs have no HBM cache, so the fix is to
restructure the data movement itself, radix-style:

  PHASE 1 (bin scatter, sequential reads): edges are pre-sorted by
    (source block, destination bin).  The kernel streams x one SB-row
    block at a time (large sequential DMAs — no per-row gather), expands
    each chunk of CH edges into their source rows with ONE one-hot MXU
    matmul (T[CH, SB] @ xblk[SB, H]), and DMA-writes the result to a
    staging buffer in SLOT-row groups at plan-computed, slot-aligned
    offsets.  Staging is laid out bin-major, so phase 1 is a blocked
    transpose from source order to destination-bin order.

  PHASE 2 (windowed scatter, sequential reads): staging is consumed in
    chunk-sized sequential DMAs; each chunk belongs to ONE bin of RB
    destination rows held resident in VMEM, and one one-hot matmul
    (S[CH2, RB]^T @ chunk) scatter-adds the rows into the bin.  fp32
    accumulation; rows may sit in any order inside a bin, which is what
    lets phase 1 write cells block-major without a per-bin sort.

  Bin GROUPS stripe the staging buffer: phases 1+2 run per group of bins
  (a lax.scan over stacked per-group plans), so staging holds ~E/G rows
  instead of E; x is re-read once per group, which is noise (the table
  is ~100x smaller than the edge stream).

Cost per aggregation: read x G times (sequential) + write staging once
(SLOT-row DMAs with block-cell run locality) + read staging once
(sequential) + one-hot matmuls (~E*(SB+RB)*H MACs, bf16).  Two precisions:

  fast (default): staging rides bf16 — one-hot factors are exact, so
  features take exactly ONE bf16 rounding; accumulation stays fp32
  (golden curves within ±1 sample of fp32, docs/GOLDEN.md).

  exact: fp32 staging + 3-way bf16 splits through the MXU.  A fp32 value
  is hi+mid+lo of three bf16 roundings of successive residuals (8
  mantissa bits each covers fp32's 24); each split-dot's products against
  the EXACT one-hot factor are exact in fp32, so the only rounding is
  the fp32 accumulation itself — the same rounding the reference's fp32
  CUDA sums make (types.h:7).  Costs: 2x staging DMA bytes, 3x MXU MACs.
  The FAST path's phases measured DMA-issue-bound on hardware (29%/44%
  MXU, round 2, BASELINE.md), which predicts much of the extra compute
  hides behind the same DMAs; the exact mode's own epoch time is
  unmeasured until the next hardware window (tools/hw_revalidate.sh
  step 2a).  The one-hot `matmul` backend (roc_tpu/ops/aggregate.py)
  remains the plan-B exact path.

Static-shape discipline: every (source-block, bin) cell is padded to a
multiple of SLOT rows, every source block's chunk count and every bin's
chunk count to whole chunks, and per-group chunk counts to a common max.
Pad rows carry src-local 0 and dst-local RB; phase 2 zero-masks dst-local
RB rows *before* the dot so uninitialized staging garbage (even NaN)
cannot leak through a 0 coefficient.
"""

from __future__ import annotations

import dataclasses
import os
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# stdlib-only tracer entry point (no obs package body is pulled in here)
from roc_tpu.obs import scopes
from roc_tpu.obs.tracer import span as _obs_span
# Calibration ledger (stdlib-only, like the tracer): choose_geometry
# PREDICTS the winning schedule's step/staging-row counts, the plan
# builder MEASURES what it actually built, and the obs stream records
# both — `python -m roc_tpu.obs calibration` reads the ratio.
from roc_tpu.obs.ledger import content_key as _content_key
from roc_tpu.obs.ledger import get_ledger as _get_ledger

SB = 512      # source rows per x block (phase-1 streaming unit)
CH = 2048     # edge slots per phase-1 chunk
# Staging write granularity (rows; multiple of the bf16 sublane 16).  Swept
# on v5e at Reddit scale (docs/PERF.md): 32 -> 203.7 ms, 64 -> 189.2,
# 128 -> 184.4 per aggregation — phase 1 is partly DMA-issue-bound, and
# 4x fewer slot DMAs beats the slightly higher cell padding.
SLOT = 128
RB = 512      # destination rows per bin (phase-2 resident window)
CH2 = 4096    # staging rows per phase-2 chunk
# (nslot/slot2 derive on Geometry below — every consumer rebinds from the
# plan's geometry, so no module-level derived constants exist to go stale
# under tools/sweep_binned.py's monkeypatching of the five above)

# Flat-schedule staging granularity, rows.  One fp32 sublane tile is
# (8, 128), so 8-row cell padding is the finest the DMA engine can move
# without tearing tiles — and it is what gets pad1 under 1.05 at Reddit
# shape (avg cell ~113 edges: 8-row padding wastes ~3.3%, SLOT=128 wastes
# 43%).  Flat staging at this default unit is therefore fp32: a bf16 tile
# is (16, 128) and an 8-row slice of it is sublane-misaligned.  The
# bf16-storage pipeline (round 9) instead sets Geometry.unit=16 — cells
# pad to one whole bf16 sublane tile, every size-classed copy stays
# tile-aligned, and staging rides bf16 (halving the DMA bytes for ~2x the
# cell-padding tax: ~6.6% vs ~3.3% at Reddit's ~113-edge cells).
_UNIT = 8
# Staging-copy size classes for the flat schedule, in _UNIT-row units:
# each per-(chunk, staging) run of consecutive rows decomposes greedily
# into 128/32/8-row DMAs, so a dense cell still moves in few descriptors
# while an 8-row tail costs exactly one.
_DMA_CLS = (16, 4, 1)
# Build-time ceiling on a group's staging rows for storing a fused
# (phase-1/phase-2 interleaved) schedule on the plan: 2 x 32768 rows x
# fp32 x H must fit VMEM alongside the working buffers, so fusion only
# ever applies to small groups/widths; run_binned re-gates on the real H
# at trace time and falls back to the flat two-pass path.
_FUSE_MAX_STG_ROWS = 1 << 15


from typing import NamedTuple


class Geometry(NamedTuple):
    """One binned-schedule geometry: every constant the plan builders and
    kernels share.  Carried on the plan (static meta), so plans built with
    different geometries coexist in one process — the sparse-graph presets
    below are how products-density graphs get a binned fast path at all
    (VERDICT r3 item 3: the dense geometry's slot padding is ~5-20x there).

    Invariants (asserted at use): slot divides ch and ch2; slot is a
    multiple of 16 (bf16 sublane granularity of the staging slot DMAs);
    VMEM budget ~16 MB/core bounds ch*sb (phase-1 one-hot), ch2*rb
    (phase-2 one-hot) and the rb*H resident window."""
    sb: int       # source rows per x block (phase-1 streaming unit)
    ch: int       # edge slots per phase-1 chunk
    slot: int     # staging write granularity, rows
    rb: int       # destination rows per bin (phase-2 resident window)
    ch2: int      # staging rows per phase-2 chunk
    # Group-row target (0 = module default _GROUP_ROW_TARGET).  Part of the
    # geometry because chunk counts depend on it: fewer groups mean less
    # per-(group, block) chunk rounding in phase 1 (the products-shape
    # chunk-count lever, tools/sweep_binned.py) at the cost of a larger
    # staging buffer.
    grt: int = 0
    # Hub-split threshold (0 = pure binned): cells with fewer than
    # `hub_minc` edges route to the one-hot matmul side of a hybrid plan
    # (build_binned_plans).  Power-law graphs concentrate most edges into
    # a few dense hub cells while the degree tail sprays thin cells whose
    # slot padding dominates; the split keeps the binned kernels on the
    # dense cells only.
    hub_minc: int = 0
    # Flat compacted schedule (round 8): 1 = the plan builders pack every
    # (group, block) stream into one flat chunk list at 8-row granularity
    # (cells pad to _UNIT=8 rows instead of SLOT; a chunk may span two
    # source blocks; staging writes become per-run size-classed DMAs from
    # scalar-prefetched metadata), eliminating the per-(group, block)
    # chunk rounding that made pad1=1.43 at Reddit shape.  At the default
    # 8-row unit staging rides fp32 at both precisions — an 8-row slice of
    # a bf16 (16, 128)-tiled buffer is sublane-misaligned, so the finer
    # granularity buys its padding win with 2x staging DMA bytes
    # (hardware-window question; docs/DESIGN.md §Flat schedule, §Precision).
    flat: int = 0
    # Flat-schedule unit rows (0 = the module default _UNIT=8, fp32
    # staging).  unit=16 is the bf16-storage variant (round 9): cells pad
    # to one whole bf16 (16, 128) sublane tile, so staging and the
    # size-classed copies ride bf16 — half the DMA bytes of the fp32
    # 8-row unit for ~2x its cell-padding tax.  Only flat geometries use
    # it — FINAL (round 10): the slot-padded schedule will never grow a
    # bf16 staging unit, because its 8-row cells slice a bf16 (16, 128)
    # tile mid-sublane at every cell boundary; check() rejects non-flat
    # unit=16 so the dead end stays unreachable.  "exact" precision needs
    # fp32 staging and run_binned rejects the combination.  New fields MUST append after this one: native plan
    # builders and the sweep tooling consume tuple(geom)[:5], and the
    # plan-cache key/version hash the whole tuple.
    unit: int = 0

    @property
    def nslot(self) -> int:
        return self.ch // self.slot

    @property
    def slot2(self) -> int:
        return self.ch2 // self.slot

    @property
    def unit_rows(self) -> int:
        """Flat-schedule staging granularity, rows (module default when
        the field is 0)."""
        return self.unit or _UNIT

    @property
    def kd(self) -> int:
        """Flat-schedule DMA descriptor slots per chunk: worst case one
        copy per unit-row unit."""
        return self.ch // self.unit_rows

    @property
    def group_rows(self) -> int:
        return self.grt or _GROUP_ROW_TARGET

    def check(self) -> "Geometry":
        assert self.sb >= 1 and self.rb >= 1, self
        assert self.slot >= 16 and self.slot % 16 == 0, \
            f"slot must be a positive multiple of 16: {self}"
        assert self.ch >= self.slot and self.ch % self.slot == 0, self
        assert self.ch2 >= self.slot and self.ch2 % self.slot == 0, self
        assert self.unit in (0, 16), \
            f"unit must be 0 (fp32 8-row) or 16 (bf16 tile): {self}"
        if self.unit:
            assert self.flat, f"unit is a flat-schedule field: {self}"
        if self.flat:
            u = self.unit_rows
            assert self.ch % u == 0 and self.ch2 % u == 0, self
        return self


def _default_geom() -> Geometry:
    """The module constants above remain the source of truth for the
    default geometry (tools/sweep_binned.py monkeypatches them; the env
    knobs there must keep steering everything that doesn't pass an
    explicit geometry)."""
    return Geometry(SB, CH, SLOT, RB, CH2)


# Presets for sparser graphs than the (dense, Reddit-like) default serves.
# The padding tax of a geometry is cells_touched * slot / E; sparser graphs
# touch more cells per edge, so slot shrinks and (to keep the cell count
# down) the windows grow.  Larger windows cost more one-hot MACs per edge
# ((sb + rb) * H), which is why these are not the default: choose_geometry
# picks per graph from ACTUAL plan statistics.
# VMEM at H<=512 (fp32 worst case, ~16 MB/core budget):
#   mid    = dense windows, slot 32:  same footprint as the default.
#   sparse = 1024/2048-row windows:  p1 one-hot (2048x1024 bf16) 4 MB +
#            gbuf 2x2048xH, p2 one-hot (2048x1024 bf16) 4 MB + rb*H out.
GEOM_MID = Geometry(sb=512, ch=2048, slot=32, rb=512, ch2=4096)
GEOM_SPARSE = Geometry(sb=1024, ch=2048, slot=16, rb=1024, ch2=2048)
# Ultra-sparse: 2048-row windows quarter the cell count again; ch/ch2
# shrink to keep the one-hot intermediates inside VMEM (t = 1024x2048
# bf16 = 4 MB, phase-2 s_t likewise).  4096*H MACs per edge — only wins
# where the occupancy stats say every smaller window drowns in slot
# padding, which is exactly what the cost model weighs.
GEOM_XSPARSE = Geometry(sb=2048, ch=1024, slot=16, rb=2048, ch2=1024)

# Wide-chunk variants — the products-shape chunk-count lever (CPU sweep,
# 2026-08-04, tools/sweep_binned.py + BASELINE.md round-5 notes): at the
# 2.45M-node products shape the per-(group, block) chunk rounding and the
# per-grid-step overhead dominate both phases, so doubling the chunk sizes
# and quadrupling the group-row target (fewer groups = fewer rounded
# streams) cuts phase-1 steps ~50% (16512 -> 8208 at CH=4096 + grt=1<<23)
# and phase-2 steps ~49% (7692 -> 3891 at CH2=8192), modeled 310 -> 257 ms
# per aggregation.  VMEM doubles with the chunks, so these only fit
# H <= 256 with bf16 staging ("fast" precision) — _vmem_bytes gates them
# out of choose_geometry's candidate list beyond that.
GEOM_WIDE = Geometry(sb=512, ch=4096, slot=128, rb=512, ch2=8192,
                     grt=1 << 23)
GEOM_MID_WIDE = Geometry(sb=512, ch=4096, slot=32, rb=512, ch2=8192,
                         grt=1 << 23)
GEOM_SPARSE_WIDE = Geometry(sb=1024, ch=4096, slot=16, rb=1024, ch2=4096,
                            grt=1 << 23)

# Flat-schedule presets (round 8, docs/DESIGN.md §Flat schedule).  The flat
# packer removes per-(group, block) chunk rounding entirely, so the wide
# group-row target buys nothing — and fp32 staging at grt=1<<23 would be a
# multi-GB buffer — hence grt=0 (module default).  ch=ch2=4096 keeps both
# phases inside _VMEM_BUDGET with fp32 staging at the nominal width
# (phase 1: 4096x512 bf16 one-hot + 2 fp32 gbufs + 2 x blocks = 13 MB).
# `slot` is unused by the flat kernels but must still divide ch/ch2
# (Geometry invariant); kept at the dense default for the cache key.
GEOM_FLAT = Geometry(sb=512, ch=4096, slot=128, rb=512, ch2=4096, flat=1)
# Sparse flat variant: 1024-row windows for products-density graphs, where
# the 8-row cell padding (not chunk rounding) is what the flat schedule
# buys over GEOM_SPARSE's 16-row slots.
GEOM_FLAT_SPARSE = Geometry(sb=1024, ch=2048, slot=16, rb=1024, ch2=2048,
                            flat=1)

# bf16-storage flat variants (round 9, docs/DESIGN.md §Precision): 16-row
# units keep every staging copy aligned to the bf16 (16, 128) tile, so the
# staging buffer and its DMAs ride bf16 — half the bytes of the fp32 8-row
# unit.  choose_geometry only considers these when the caller declares
# bf16 storage (the driver's Config.bf16_storage / use_bf16 path); fp32
# runs never trade cell padding for a byte win they can't bank.
GEOM_FLAT_BF16 = GEOM_FLAT._replace(unit=16)
GEOM_FLAT_SPARSE_BF16 = GEOM_FLAT_SPARSE._replace(unit=16)

# Named presets for the ROC_BINNED_GEOM escape hatch (build_binned_plans):
# force the auto-chosen FORWARD geometry to a specific preset, for
# hardware A/B runs that must isolate one variable.
GEOM_PRESETS = {
    "wide": GEOM_WIDE,
    "mid": GEOM_MID,
    "mid_wide": GEOM_MID_WIDE,
    "sparse": GEOM_SPARSE,
    "sparse_wide": GEOM_SPARSE_WIDE,
    "xsparse": GEOM_XSPARSE,
    "flat": GEOM_FLAT,
    "flat_sparse": GEOM_FLAT_SPARSE,
    "flat_bf16": GEOM_FLAT_BF16,
    "flat_sparse_bf16": GEOM_FLAT_SPARSE_BF16,
}

# Staging ceiling per bin group, in rows (~1 GiB bf16 at H=256).  Fewer
# groups = less per-(group, block) chunk-rounding padding in phase 1 at the
# cost of a proportionally larger staging buffer; ROC_BINNED_GROUP_ROWS
# overrides for hardware sweeps (tools/sweep_binned.py).
_GROUP_ROW_TARGET = int(os.environ.get("ROC_BINNED_GROUP_ROWS", 1 << 21))
# Cap on the dense (source-block x bin) cell table per group — bounds the
# plan builders' memory on huge sparse graphs to ~256 MiB of int64 cells
# (the native builder allocates it densely; mirrored there as BN_K2_CAP).
_K2_CAP = 1 << 25


@dataclasses.dataclass(frozen=True)
class BinnedPlan:
    """One direction (out = A @ x) of a binned aggregation schedule.

    Array fields carry a leading [G] group axis; int fields are static.
      p1_srcl [G, C1, CH]    src row local to its block (pad rows: 0)
      p1_off  [G, C1, NSLOT] staging SLOT index per chunk slot
      p1_blk  [G, C1]        x block index per chunk
      p2_dstl [G, C2, CH2]   dst row local to its bin (pad rows: RB)
      p2_obi  [G, C2]        group-local bin index per chunk (nondecreasing)
      p2_first[G, C2]        1 iff first chunk of its bin
    The two index arrays are lane-dense: one chunk's indices are one row
    (the linear order is the builders' [G, C*CH]; only the shape says
    where a chunk ends), so a group's slice is 4 bytes an index in HBM
    and the kernels fetch eight chunks' rows as one (8, CH) block.  A
    [rows, 1] column would tile to 128 lanes a row: 512 bytes an index,
    re-laid out every scan step (a third of the Reddit epoch until PR 26).

    Flat-schedule plans (geom.flat, round 8) reinterpret/extend the set:
    p1_off is None (replaced by the run-list DMA metadata), p1_srcl pad
    rows carry -1 (exact-zero one-hot row), a chunk may span two source
    blocks (secondary-block rows store sb + local), and:
      p1_blk2 [G, C1]        secondary x block (== p1_blk if none)
      p1_dsrc [G, C1, KD]    staging-copy source:  cls<<16 | chunk unit
                             (cls indexes _DMA_CLS; -1 = unused slot)
      p1_ddst [G, C1, KD]    staging-copy destination unit
                             (row / geom.unit_rows)
    Fused plans additionally carry a flattened interleaved step list
    (phase 2 of group g overlapped with phase 1 of group g+1; built by
    _attach_fused when the whole group's staging fits VMEM, else None):
      f_meta  [S, 4]         (kind 0=p1/1=p2, group parity, first, stg
                             chunk index within the group's staging)
      f_rows  [S*CH, 1]      per-step srcl (kind 0) or dstl (kind 1)
      f_blk/f_blk2/f_obi [S] x blocks + GLOBAL output bin per step (p1
                             steps repeat the previous p2 step's bin)
      f_dsrc/f_ddst [S, KD]  staging-copy run lists (kind 0; else -1)
    """
    p1_srcl: jnp.ndarray
    p1_off: jnp.ndarray
    p1_blk: jnp.ndarray
    p2_dstl: jnp.ndarray
    p2_obi: jnp.ndarray
    p2_first: jnp.ndarray
    p1_blk2: jnp.ndarray = None
    p1_dsrc: jnp.ndarray = None
    p1_ddst: jnp.ndarray = None
    f_meta: jnp.ndarray = None
    f_rows: jnp.ndarray = None
    f_blk: jnp.ndarray = None
    f_blk2: jnp.ndarray = None
    f_obi: jnp.ndarray = None
    f_dsrc: jnp.ndarray = None
    f_ddst: jnp.ndarray = None
    num_rows: int = dataclasses.field(metadata={"static": True}, default=0)
    table_rows: int = dataclasses.field(metadata={"static": True}, default=0)
    bins_per_group: int = dataclasses.field(
        metadata={"static": True}, default=0)
    # The geometry the plan was built for; the kernels replay it (static).
    geom: Geometry = dataclasses.field(metadata={"static": True},
                                       default=None)


# None-valued data fields are empty pytree subtrees: tree_map skips them,
# and two-pass vs flat vs fused plans simply have different treedefs
# (separate jit cache entries — intended).
_PLAN_DATA_FIELDS = [
    "p1_srcl", "p1_off", "p1_blk", "p2_dstl", "p2_obi", "p2_first",
    "p1_blk2", "p1_dsrc", "p1_ddst",
    "f_meta", "f_rows", "f_blk", "f_blk2", "f_obi", "f_dsrc", "f_ddst"]

jax.tree_util.register_dataclass(
    BinnedPlan,
    data_fields=list(_PLAN_DATA_FIELDS),
    meta_fields=["num_rows", "table_rows", "bins_per_group", "geom"])


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


def staging_dtype(geom: Geometry, exact: bool):
    """The staging-buffer dtype a plan geometry implies at a precision —
    THE single decision point every byte consumer (kernels, VMEM gates,
    cost model, memory estimator, kernel budgets) shares.

    Slot schedule: bf16 for "fast", fp32 for "exact" (the original
    contract).  Flat schedule: a pure function of the geometry — fp32 at
    the default 8-row unit (tears bf16 tiles), bf16 at unit=16; "exact"
    needs fp32 staging, so run_binned rejects exact on unit=16 plans
    rather than silently widening."""
    if geom is not None and geom.flat:
        return jnp.bfloat16 if geom.unit == 16 else jnp.float32
    return jnp.float32 if exact else jnp.bfloat16


def staging_itemsize(geom: Geometry, exact: bool) -> int:
    return np.dtype(staging_dtype(geom, exact)).itemsize


def binned_viable(num_rows: int, table_rows: int, num_edges: int,
                  edge_src: np.ndarray = None,
                  edge_dst: np.ndarray = None) -> bool:
    """Is the binned schedule padding-tolerable for this graph?

    Cells are (source-block x bin) pairs and every non-empty cell pads to
    SLOT rows; with ~uniform edges the number of touched cells approaches
    min(E, blocks * bins), so the expected slot-padding factor is about
    blocks*bins*SLOT / E (each touched cell pays at least one SLOT).  The
    bound accepts up to ~25% slot-padding tax; beyond that (huge sparse
    graphs: ogbn-products-scale N with modest degree, measured ~5x padding)
    the one-hot matmul backend is the right fast path instead.  Threshold:
    average cell >= SLOT*4/5 = 102.4 edges — slightly tighter than the
    round-2 3*SLOT(=32) rule's >= 96; graphs averaging 96-102 edges/cell
    now take the matmul backend instead.

    With edge arrays the call defers to :func:`choose_geometry`'s
    measured-statistics policy (including the sparse presets and the hub
    hybrid) instead of the uniform-occupancy bound — a skewed or
    locality-ordered graph is credited for the cells it never touches."""
    if edge_src is not None:
        g, _ = choose_geometry(edge_src, edge_dst, num_rows, table_rows)
        return g is not None
    return binned_viable_why(num_rows, table_rows, num_edges)[0]


def binned_viable_why(num_rows: int, table_rows: int,
                      num_edges: int) -> tuple:
    """(viable, reason): :func:`binned_viable`'s uniform-occupancy test and
    the statistics it was decided on, as one phrase without spaces (the
    trainer prints it in its start-up line and labels a gauge with it)."""
    num_bins = max(-(-num_rows // RB), 1)
    num_blocks = max(-(-table_rows // SB), 1)
    ok = num_blocks * num_bins * SLOT * 4 <= num_edges * 5
    per_cell = num_edges / (num_blocks * num_bins)
    return ok, (f"occupancy:{per_cell:.1f}_edges_a_cell_"
                f"{'>=' if ok else '<'}_{SLOT * 4 / 5:.1f}"
                f"(bins={num_bins},blocks={num_blocks},edges={num_edges})")


# Cost-model calibration: re-fit in PR 24 (2026-09-30) from per-kernel device
# times on one TPU v5 lite chip (jax 0.9.0, libtpu 0.0.34), both benchmark
# graphs (232,965 nodes, 23.4 M / 23.5 M in-edges), both plan directions
# pinned to each preset in turn, widths 256 and 128.  The measured rows are
# committed beside this file as binned_chip_table.json, and
# tests/test_binned.py holds the model to every row of it (15 % on phase 1
# + phase 2 at width 256, and the measured order).  What the chip showed:
#   * the one-hot matmuls of BOTH phases run the MXU at its published peak
#     (197 TFLOP/s bf16; fitting the rate freely gives 199-207), so
#     _MXU_EFF_FLOPS is the peak itself — the round-2 figure of 35 % was
#     the old stack's;
#   * on top of the MACs the two-pass phase 1 pays 0.44 us a grid step and
#     54 ns a real staging-slot DMA (slot 128 -> 32 -> 16: 222 k, 834 k,
#     1,487 k slots a sweep; residuals under 2.2 % on seven rows);
#   * phase 2 pays 0.43 ns a staging row it reads (mask, one-hot build and
#     operand streaming follow the rows, not the step count: 4096- and
#     8192-row chunks cost the same per row);
#   * the flat phase 1 pays for every descriptor SLOT it walks, real or
#     not: 54 ns a visit, and each grid step visits all KD = ch / unit
#     slots twice (issue, then drain two steps later) — 55 us of a 65 us
#     step at KD 512 — plus 27 ns a real copy.  GEOM_FLAT (KD 512, 875 k
#     copies) and GEOM_FLAT_SPARSE (KD 256, 325 k copies, twice the steps)
#     walk the same 6.3 M slots a sweep and take the same 406 / 419 ms.
# Every term is linear in its rate, so tune/refit.py re-solves them by
# least squares over the same regressors (_cost_terms).  The matmul
# backend's constant below was NOT re-fit here.
from roc_tpu.obs.roofline import PEAK_FLOPS as _MXU_EFF_FLOPS  # noqa: E402
_CHUNK_OVERHEAD_S = 0.44e-6   # per phase-1 grid step, beyond its MACs
_SLOT_DMA_S = 54e-9           # per real staging-slot DMA (two-pass phase 1)
_P2_ROW_S = 0.43e-9           # per staging row phase 2 reads
_FLAT_SLOT_S = 54e-9          # per descriptor slot visited (flat phase 1)
_FLAT_COPY_S = 27e-9          # per real size-classed copy (flat phase 1)
# Matmul backend: per-chunk cost of the one-hot scan (gather EB rows +
# S1/S2 dots + DUS).  Re-fit 2026-08-04 from the round-2 Reddit point
# (23.5M edges -> 351 ms) against the REAL chunk count — ceil(E/EB) edge
# chunks PLUS the ceil(rows/VB) per-window >=1-chunk floor
# (segment_sum.build_chunk_plan) that the old flat 15 ns/edge model
# ignored.  That floor is exactly what inflates the matmul backend at
# products shape: 306k windows for 2.45M rows regardless of density.
_MM_CHUNK_S = 2.9e-6
_MODEL_H = 256                # nominal width: plans are H-independent
# Scoped VMEM.  Mosaic gives a kernel 16 MiB of the v5e's 128 MiB unless
# it is asked for more, and that default is below what the flat and wide
# presets need at H=256 (the round-2 CH2=8192 compile failure).  The
# two-pass kernels therefore ask per call: vmem_limit_bytes =
# _p1_vmem_bytes / _p2_vmem_bytes at the REAL padded width and precision
# (never more than _VMEM_LIMIT_MAX — beyond it the compile fails with
# Mosaic's own message).  choose_geometry, the tuned tier and the tuner's
# lattice admit a candidate when its nominal footprint (_MODEL_H, fast)
# is within _VMEM_NOMINAL_CAP: the largest preset today (GEOM_FLAT,
# 32 MiB nominal) asks for 86 MiB at H=512 exact, still inside the chip.
_VMEM_NOMINAL_CAP = 40 * (1 << 20)
_VMEM_LIMIT_MAX = 100 * (1 << 20)
# Gate for the flat schedule's fused pipeline (_fused_vmem_ok).  The
# formula is NOT checked against Mosaic and the kernel still compiles
# under the 16 MiB default (CHANGES.md PR 21 has the compile inventory).
_VMEM_BUDGET = 14 * (1 << 20)
# HBM admission for choose_geometry, the analogue of _VMEM_NOMINAL_CAP: a
# candidate's per-group temporaries (_group_hbm_bytes, from shapes: the
# staging buffer and a lane-dense index slice) may take a quarter of a
# v5e's 16 GiB.  The rest belongs to the features, the activations kept
# for the backward pass, the plans and the optimiser.  At the Reddit
# shape the model says 1.35 GB for the default two-pass group, 2.23 GB
# for GEOM_FLAT and 5.51 GB for GEOM_WIDE (grt 1 << 23).  Measured on one
# chip: the default's step peaks at 3.49 GiB (PR 26; 4.84 with the index
# operands as [rows, 1] columns, when GEOM_FLAT peaked at 5.29 GiB and
# GEOM_WIDE at 12.69, PR 24).
_HBM_GROUP_CAP = 4 * (1 << 30)


_MEASURED_CAL: dict = {}   # path -> parsed rates (None = no device table)


def measured_calibration(path: str = ""):
    """The device-measured matmul rate from the ``measured`` table
    tools/kernel_bench.py persists into tools/kernel_budgets.json:
    ``{"mm_chunk_s": <matmul per-chunk s>}`` (median over the benched
    shapes).

    Returns None — the analytic constant stays in charge — when no table
    exists, the table was recorded in interpret mode (CPU harness
    timings, not rates), it has no matmul row, or ROC_NO_MEASURED_CAL=1
    kills it.  _matmul_cost and the balance prior
    (balance/cost_model.py) warm-start from it in place of the hand-fit
    _MM_CHUNK_S.  The binned kernels' rates are NOT read from this table
    (until PR 24 one median ``per_step_s`` over every kernel and variant
    stood in for _CHUNK_OVERHEAD_S): a flat step costs 65 us and a
    two-pass step 4-7 us on the chip, no median of the two prices
    either, and the per-family rates live with their measured rows in
    binned_chip_table.json.  Cached per path; ROC_MEASURED_CAL_PATH
    overrides the default table location."""
    if os.environ.get("ROC_NO_MEASURED_CAL"):
        return None
    if not path:
        path = os.environ.get("ROC_MEASURED_CAL_PATH") or os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "..", "..", "..", "tools", "kernel_budgets.json")
    path = os.path.abspath(path)
    if path in _MEASURED_CAL:
        return _MEASURED_CAL[path]
    import json
    cal = None
    try:
        with open(path, encoding="utf-8") as f:
            m = json.load(f).get("measured") or {}
        if not m.get("interpret", True):
            mm = sorted(float(row["per_chunk_s"])
                        for shp in m.get("shapes", {}).values()
                        for row in shp.get("kernels", {}).values()
                        if row.get("variant") == "matmul")
            if mm:
                cal = {"mm_chunk_s": mm[len(mm) // 2]}
    except (OSError, ValueError, KeyError, TypeError):
        cal = None
    _MEASURED_CAL[path] = cal
    return cal


def _matmul_chunks(num_edges: int, num_rows: int) -> int:
    """Chunk count of the one-hot matmul backend for this shape: edges
    pack EB per chunk, but every VB-row output window costs at least one
    chunk (the obi>=1 invariant, segment_sum.build_chunk_plan)."""
    from roc_tpu.ops.pallas.segment_sum import EB, VB
    return -(-num_edges // EB) + -(-num_rows // VB)


def _matmul_cost(num_edges: int, num_rows: int) -> float:
    cal = measured_calibration()
    rate = (cal or {}).get("mm_chunk_s") or _MM_CHUNK_S
    return _matmul_chunks(num_edges, num_rows) * rate


# What Mosaic allocates for one grid step of the two-pass kernels: the
# pipelined operand blocks (double-buffered; the index block is eight
# chunks' lane-dense rows), the scratch, and the values the body
# materialises — the bf16 one-hot, each fp32 dot result, the masked /
# split copies of the feature operand.  An UPPER bound, checked with
# libtpu's compiler for a v5e topology (PR 21): every preset at H in
# {128, 256, 512} and both precisions compiles with the limit set to
# this model, and the smallest limit that compiles — bisected for 44
# kernel/geometry/width/precision cases, pinned in
# tests/test_chip_smoke.py — is below it in every case.  _VMEM_SLACK
# covers Mosaic's own small scratch.
_VMEM_SLACK = 2 * (1 << 20)


def _p1_vmem_bytes(geom: Geometry, H: int = _MODEL_H,
                   exact: bool = False) -> int:
    stg = staging_itemsize(geom, exact)
    two = 2 if geom.flat else 1       # flat: two x blocks, two one-hots
    nd = 3 if exact else 1            # exact: hi/mid/lo split dots
    blocks = (2 * geom.ch * H * stg               # gbuf scratch
              + 2 * 8 * geom.ch * 4               # srcl (8, ch) int32
              + 2 * two * geom.sb * H * 4)        # x block(s)
    # the one-hot [sb, ch] and the turned copy the dimension-0
    # contraction makes of it, each fp32 dot result
    body = two * (2 * geom.ch * geom.sb * 2 + nd * geom.ch * H * 4)
    if exact:
        body += 3 * geom.sb * H * 2 + 2 * geom.sb * H * 4
        if geom.flat:                 # the fp32 sum of the two blocks' rows
            body += geom.ch * H * 4
    return blocks + body + _VMEM_SLACK


def _p2_vmem_bytes(geom: Geometry, H: int = _MODEL_H,
                   exact: bool = False) -> int:
    stg = staging_itemsize(geom, exact)
    nd = 3 if exact else 1
    blocks = (2 * geom.ch2 * H * stg              # staging chunk
              + 2 * 8 * geom.ch2 * 4              # dstl (8, ch2) int32
              + 2 * geom.rb * H * 4)              # resident out window
    body = (geom.ch2 * geom.rb * 2 + geom.ch2 * H * stg
            + nd * geom.rb * H * 4)
    if exact:
        body += 3 * geom.ch2 * H * 2 + 2 * geom.ch2 * H * 4
    return blocks + body + _VMEM_SLACK


def _vmem_bytes(geom: Geometry, H: int = _MODEL_H,
                exact: bool = False) -> int:
    """Scoped VMEM the geometry's larger phase needs (see above)."""
    return max(_p1_vmem_bytes(geom, H, exact),
               _p2_vmem_bytes(geom, H, exact))


def _vmem_params(need: int):
    """Mosaic compiler params asking for ``need`` bytes of scoped VMEM."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(int(need), _VMEM_LIMIT_MAX))


def _cost_terms(padded_rows: int, geom: Geometry, H: int = _MODEL_H,
                steps1: int = None, steps2: int = None,
                copies: int = None) -> dict:
    """What ONE aggregation pass at this geometry does, counted: the
    regressors of the cost model, by the name of the rate that prices
    each (_COST_RATES).  Modeled seconds are the sum of rate x count, so
    the tuner's surrogate prices through the same counts with its own
    rates and tune/refit.py solves the rates back from them.

    ``steps1``/``steps2`` are exact grid step counts (_plan_steps):
    with them the schedule that is priced is the real one, per-(group,
    block) chunk rounding and per-group max-padding included; without
    them the ideal padded_rows / chunk.  ``copies`` is the flat
    schedule's real descriptor count (_flat_copies over the cell
    statistics); without it every padded unit is taken for one copy."""
    s1 = steps1 if steps1 is not None else padded_rows / geom.ch
    s2 = steps2 if steps2 is not None else padded_rows / geom.ch2
    terms = {"mxu": (s1 * geom.ch * geom.sb + s2 * geom.ch2 * geom.rb)
             * H * 2,
             "p1_step": s1, "p2_row": s2 * geom.ch2,
             "slot_dma": 0.0, "flat_slot": 0.0, "flat_copy": 0.0}
    if geom.flat:
        # issue walks all KD descriptor slots of the step, drain walks
        # them again two steps later, real or -1 alike
        terms["flat_slot"] = 2 * s1 * geom.kd
        terms["flat_copy"] = (copies if copies is not None
                              else padded_rows / geom.unit_rows)
    else:
        terms["slot_dma"] = padded_rows / geom.slot
    return terms


# seconds per count of each _cost_terms regressor
_COST_RATES = {"mxu": 1.0 / _MXU_EFF_FLOPS, "p1_step": _CHUNK_OVERHEAD_S,
               "p2_row": _P2_ROW_S, "slot_dma": _SLOT_DMA_S,
               "flat_slot": _FLAT_SLOT_S, "flat_copy": _FLAT_COPY_S}


def _binned_cost_model(padded_rows: int, geom: Geometry,
                       H: int = _MODEL_H, steps1: int = None,
                       steps2: int = None, copies: int = None) -> float:
    """Modeled seconds for ONE aggregation pass at this geometry, given the
    actual padded staging row count (from cell statistics): the counts of
    _cost_terms at the rates the chip measured (calibration block above).
    Phase 1 is MACs + steps + staging DMAs (per real slot in the two-pass
    schedule, per descriptor slot walked and per real copy in the flat
    one), phase 2 is MACs + staging rows read."""
    terms = _cost_terms(padded_rows, geom, H, steps1, steps2, copies)
    return sum(_COST_RATES[k] * v for k, v in terms.items())


def _flat_copies(cnt: np.ndarray, geom: Geometry):
    """Real staging copies a flat plan issues for these cell occupancies
    (None for a two-pass geometry, which has none): each cell's units
    decompose greedily into _DMA_CLS size classes, as the plan builder
    decomposes a run (a 113-edge cell pads to 15 units = 3 x 4 + 3 x 1,
    six copies).  Cells cut by a chunk boundary add a few; at Reddit
    shape this count is the built plan's, 875,088."""
    if not geom.flat:
        return None
    units = -(-np.asarray(cnt, np.int64) // geom.unit_rows)
    n = 0
    for c in _DMA_CLS:
        n += int((units // c).sum())
        units = units % c
    return n


def _group_hbm_bytes(geom: Geometry, steps1: int, steps2: int,
                     groups: int, H: int = _MODEL_H) -> int:
    """HBM the scan over bin groups holds for ONE group at a time, from
    shapes: the staging buffer [C2 * ch2, H] in its staging dtype plus the
    larger of the two int32 index operands (p1_srcl while phase 1 runs,
    p2_dstl while phase 2 does) — a lane-dense slice of the stacked plan,
    4 bytes an index, where the [rows, 1] columns of PR 24 were re-laid
    out to 128 lanes a row (1.44 GB a step at the Reddit shape).  Beside
    it, the compiler's own analysis of the Reddit train step for a v5e
    (tools/aot_compile.py, PR 26): default two-pass 1.35 GB here and
    2.66 GB of temporaries there, GEOM_FLAT 2.23 and 3.62, GEOM_WIDE 5.51
    and 6.80 — 1.3 to 1.4 GB apart in all three, as before PR 26, when
    each was 1.1 to 5.7 GB of index padding higher on both sides."""
    p1_rows = steps1 // max(groups, 1) * geom.ch
    stg_rows = steps2 // max(groups, 1) * geom.ch2
    return (stg_rows * H * staging_itemsize(geom, False)
            + max(p1_rows, stg_rows) * 4)


def _cell_stats(edge_src: np.ndarray, edge_dst: np.ndarray,
                sb: int, rb: int):
    """Nonzero (source-block x destination-bin) cells: returns
    (cell_blk, cell_bin, cnt) int64 arrays — one O(E) bincount, the single
    implementation every occupancy consumer shares."""
    blk = np.asarray(edge_src, np.int64) // sb
    bn = np.asarray(edge_dst, np.int64) // rb
    nbins = int(bn.max(initial=0)) + 1
    keys = blk * nbins + bn
    nkeys = int(blk.max(initial=0) + 1) * nbins
    if nkeys <= max(4 * len(keys), 1 << 20):
        # dense O(E + cells) bincount while the cell table is small
        cnt = np.bincount(keys, minlength=0)
        uniq = np.flatnonzero(cnt)
        cnt = cnt[uniq]
    else:
        # Sparse O(E log E) time / O(E) memory fallback: a dense bincount
        # is O(blocks*bins) memory regardless of occupancy — ~376 GB at
        # papers100M scale with sb=rb=512, which would OOM exactly the
        # offline preprocessing paths (-reorder auto, convert --reorder)
        # advertised for such graphs.
        uniq, cnt = np.unique(keys, return_counts=True)
    return uniq // nbins, uniq % nbins, cnt.astype(np.int64)


def _cell_counts(edge_src: np.ndarray, edge_dst: np.ndarray,
                 sb: int, rb: int) -> np.ndarray:
    """Nonzero cell occupancies only (see _cell_stats)."""
    return _cell_stats(edge_src, edge_dst, sb, rb)[2]


def _flat_pack(stream_g: np.ndarray, stream_units: np.ndarray,
               uc: int, G: int, segments: bool = False):
    """Flat-schedule phase-1 packer: lay each group's (source-block-major)
    unit streams into `uc`-unit chunks.  One stream = one (group, block)
    pair's ``geom.unit_rows``-row units, in cell order.  A chunk may span at most TWO
    streams — the kernel reads two x blocks per grid step — so when a
    third block would enter a partly-filled chunk the chunk is cut early;
    that cut and each group's final partial chunk are the only schedule
    waste left (vs. per-(group, block) rounding in the slot schedule).

    Returns (c1_per_g [G], segs) where segs is None unless ``segments``:
    a (stream, chunk, pos, take) int64 array, one row per contiguous span
    a stream contributes to a chunk, in global unit order.  SHARED by the
    plan builder and _plan_steps so the step predictor is exact by
    construction (pinned by test_plan_steps_match_built_plans)."""
    c1_per_g = np.zeros(G, np.int64)
    segs = [] if segments else None
    n = len(stream_g)
    i = 0
    while i < n:
        g = int(stream_g[i])
        chunk = 0
        fill = 0
        nblk = 0
        while i < n and int(stream_g[i]) == g:
            u = int(stream_units[i])
            if nblk >= 2 and 0 < fill and u > 0:
                chunk += 1          # early cut: a third distinct block
                fill = 0
                nblk = 0
            while u > 0:
                if fill == uc:
                    chunk += 1
                    fill = 0
                    nblk = 0
                take = min(u, uc - fill)
                if segments:
                    segs.append((i, chunk, fill, take))
                nblk += 1           # one span per (stream, chunk)
                fill += take
                u -= take
            i += 1
        c1_per_g[g] = chunk + (1 if fill > 0 else 0)
    if segments:
        segs = (np.asarray(segs, np.int64).reshape(-1, 4)
                if segs else np.zeros((0, 4), np.int64))
    return c1_per_g, segs


def _plan_groups(geom: Geometry, num_rows: int, table_rows: int,
                 num_edges: int):
    """(bins, source blocks, bins per group, groups) the plan builders
    lay this shape out in at this geometry."""
    num_bins = max(-(-num_rows // geom.rb), 1)
    num_blocks = max(-(-table_rows // geom.sb), 1)
    bpg = max(min(num_bins,
                  int(geom.group_rows / max(num_edges / num_bins, 1)),
                  _K2_CAP // num_blocks), 1)
    return num_bins, num_blocks, bpg, -(-num_bins // bpg)


def _flat_plan_steps(cell_blk, cell_bin, cnt, geom, num_bins, num_blocks,
                     bpg, G):
    """Flat-schedule arm of _plan_steps: cells pad to unit_rows, phase-1
    chunks pack via _flat_pack, phase-2 bins pad to whole CH2 chunks."""
    U = geom.unit_rows
    cell_units = -(-cnt // U)
    padded = int(cell_units.sum() * U)
    # phase 1: streams in (group, block) order — np.unique sorts the key
    gb = (cell_bin // bpg) * num_blocks + cell_blk
    gb_uniq, gb_inv = np.unique(gb, return_inverse=True)
    gb_units = np.bincount(gb_inv, weights=cell_units).astype(np.int64)
    c1_per_g, _ = _flat_pack(gb_uniq // num_blocks, gb_units,
                             geom.ch // U, G)
    C1 = _pad_to(max(int(c1_per_g.max(initial=0)), 1), 8)
    # phase 2: bins stay CH2-aligned in staging (empty bins cost one chunk)
    u2 = geom.ch2 // U
    bin_units = np.bincount(cell_bin, weights=cell_units,
                            minlength=num_bins).astype(np.int64)
    bin_chunks = np.maximum(-(-bin_units // u2), 1)
    c2_per_g = np.bincount(np.arange(num_bins) // bpg, weights=bin_chunks,
                           minlength=G)
    C2 = max(int(c2_per_g.max(initial=0)), 1)
    return padded, G * C1, G * C2


def _plan_steps(cell_blk: np.ndarray, cell_bin: np.ndarray,
                cnt: np.ndarray, geom: Geometry, num_rows: int,
                table_rows: int, num_edges: int):
    """Exact (padded_rows, phase-1 steps, phase-2 steps) the plan builder
    would produce for these cells — same arithmetic as
    _build_binned_plan_numpy, O(cells).  Steps are G*C1 / G*C2: every
    group runs the per-group MAXIMUM chunk count (one stacked static
    program), so group-count and rounding effects are priced, which is
    what makes the chunk-count lever visible to the cost model."""
    num_bins, num_blocks, bpg, G = _plan_groups(geom, num_rows, table_rows,
                                                num_edges)
    if geom.flat:
        return _flat_plan_steps(cell_blk, cell_bin, cnt, geom, num_bins,
                                num_blocks, bpg, G)
    cell_slots = -(-cnt // geom.slot)
    padded = int(cell_slots.sum() * geom.slot)
    # phase 1: chunks per (group, block) stream, per-group sums, max
    gb = (cell_bin // bpg) * num_blocks + cell_blk
    gb_uniq, gb_inv = np.unique(gb, return_inverse=True)
    gb_slots = np.bincount(gb_inv, weights=cell_slots).astype(np.int64)
    gb_chunks = -(-gb_slots // geom.nslot)
    c1_per_g = np.bincount((gb_uniq // num_blocks).astype(np.int64),
                           weights=gb_chunks, minlength=G)
    C1 = _pad_to(max(int(c1_per_g.max(initial=0)), 1), 8)
    # phase 2: chunks per bin (empty bins still cost one), per-group max
    bin_slots = np.bincount(cell_bin, weights=cell_slots,
                            minlength=num_bins).astype(np.int64)
    bin_chunks = np.maximum(-(-bin_slots // geom.slot2), 1)
    c2_per_g = np.bincount(np.arange(num_bins) // bpg, weights=bin_chunks,
                           minlength=G)
    C2 = max(int(c2_per_g.max(initial=0)), 1)
    return padded, G * C1, G * C2


def fused_plan_steps(cell_blk: np.ndarray, cell_bin: np.ndarray,
                     cnt: np.ndarray, geom: Geometry, num_rows: int,
                     table_rows: int, num_edges: int):
    """Exact fused grid step count for these cells, or None when no fused
    schedule would attach (non-flat geometry, ch != ch2, or group staging
    beyond _FUSE_MAX_STG_ROWS).  The fused grid runs REAL chunks only —
    _attach_fused skips pad chunks — so its step count is
    pad8(sum c1_per_g + sum bin_chunks), vs the two-pass G*C1 + G*C2
    (per-group max-padded) that _plan_steps prices.  Same arithmetic as
    _flat_plan_steps/_attach_fused, O(cells)."""
    if not (geom.flat and geom.ch == geom.ch2):
        return None
    num_bins, num_blocks, bpg, G = _plan_groups(geom, num_rows, table_rows,
                                                num_edges)
    U = geom.unit_rows
    cell_units = -(-cnt // U)
    gb = (cell_bin // bpg) * num_blocks + cell_blk
    gb_uniq, gb_inv = np.unique(gb, return_inverse=True)
    gb_units = np.bincount(gb_inv, weights=cell_units).astype(np.int64)
    c1_per_g, _ = _flat_pack(gb_uniq // num_blocks, gb_units,
                             geom.ch // U, G)
    u2 = geom.ch2 // U
    bin_units = np.bincount(cell_bin, weights=cell_units,
                            minlength=num_bins).astype(np.int64)
    bin_chunks = np.maximum(-(-bin_units // u2), 1)
    c2_per_g = np.bincount(np.arange(num_bins) // bpg, weights=bin_chunks,
                           minlength=G)
    C2 = max(int(c2_per_g.max(initial=0)), 1)
    if C2 * geom.ch2 > _FUSE_MAX_STG_ROWS:
        return None
    return _pad_to(max(int(c1_per_g.sum()) + int(bin_chunks.sum()), 1), 8)


def padded_rows_for(edge_src: np.ndarray, edge_dst: np.ndarray,
                    geom: Geometry) -> int:
    """ACTUAL slot-padded staging rows for this graph at this geometry:
    every touched (source-block x destination-bin) cell rounds up to whole
    SLOTs.  No uniform-graph assumption, so a locality-preserving vertex
    order (the greedy-cut partitioner's output) is credited for the cells
    it never touches."""
    cnt = _cell_counts(edge_src, edge_dst, geom.sb, geom.rb)
    if geom.flat:
        U = geom.unit_rows
        return int((-(-cnt // U)).sum() * U)
    return int((-(-cnt // geom.slot)).sum() * geom.slot)


def staging_bytes_for(edge_src: np.ndarray, edge_dst: np.ndarray,
                      geom: Geometry, H: int = _MODEL_H,
                      exact: bool = False) -> int:
    """Predicted staging-DMA bytes for ONE aggregation pass: every padded
    staging row is written once by phase 1 and read once by phase 2, at
    the geometry's staging dtype.  The byte axis the kernel-budget gate
    pins (tools/check_kernel_budgets.py): a bf16-unit flat geometry must
    move ~half the bytes of its fp32 twin at the same windows."""
    return (2 * padded_rows_for(edge_src, edge_dst, geom) * H
            * staging_itemsize(geom, exact))


def _plan_key(num_rows: int, table_rows: int, num_edges: int,
              geom: Geometry) -> str:
    """Content key joining choose_geometry's schedule predictions to the
    built plan's measurements: the full schedule-shaping input (shape +
    geometry tuple), so a prediction only ever pairs with the plan it was
    made for."""
    return _content_key(rows=int(num_rows), table_rows=int(table_rows),
                        edges=int(num_edges),
                        geom="/".join(str(v) for v in tuple(geom)))


def _ledger_note_plan(plan: "BinnedPlan", num_edges: int) -> None:
    """Measurement half of the plan_steps/staging_rows pairs: the BUILT
    plan's actual grid-step and staging-row counts, read off the plan
    arrays' shapes (O(1), host-side).  _plan_steps is exact by
    construction (test_plan_steps_match_built_plans), so a ratio off 1.0
    here means the predictor and builder have drifted apart."""
    led = _get_ledger()
    if not led.attached:
        return
    g = plan.geom
    G, C1 = plan.p1_blk.shape
    C2 = plan.p2_obi.shape[1]
    key = _plan_key(plan.num_rows, plan.table_rows, num_edges, g)
    led.measure("plan_steps", key, G * (C1 + C2), "steps")
    led.measure("staging_rows", key, G * C2 * g.ch2, "rows")


def _tuned_geometry(edge_src, edge_dst, num_rows, table_rows,
                    storage_dtype):
    """The tuned-tier lookup (roc_tpu/tune/store.py), failure-isolated:
    a missing/invalid store, ROC_NO_TUNED=1, or any import problem reads
    as 'no tuned entry' and the analytic model stays in charge.  Lazy
    import — tune imports this module at load time."""
    if os.environ.get("ROC_NO_TUNED"):
        return None
    try:
        from roc_tpu.tune import store as _tstore
        g, _ = _tstore.lookup(edge_src, edge_dst, num_rows, table_rows,
                              storage_dtype=storage_dtype)
        return g
    except Exception:
        return None


def _priced_tuned(edge_src, edge_dst, num_rows, table_rows, E, geom):
    """Price a tuned winner through the SAME exact-schedule model the
    analytic path uses (so the returned seconds stay comparable and the
    balancer's consumers see one currency) and emit the same calibration
    predictions a modeled win would — a tuned pick is still a prediction
    the built plan and the hardware get to grade."""
    cblk, cbin, cnt = _cell_stats(edge_src, edge_dst, geom.sb, geom.rb)
    padded, s1, s2 = _plan_steps(cblk, cbin, cnt, geom, num_rows,
                                 table_rows, E)
    t = _binned_cost_model(padded, geom, steps1=s1, steps2=s2,
                           copies=_flat_copies(cnt, geom))
    led = _get_ledger()
    if led.attached:
        key = _plan_key(num_rows, table_rows, E, geom)
        led.predict("plan_steps", key, s1 + s2, "steps")
        led.predict("staging_rows", key, s2 * geom.ch2, "rows")
        led.predict("geom_time", key, t, "s")
    return geom, t


def choose_geometry(edge_src: np.ndarray, edge_dst: np.ndarray,
                    num_rows: int, table_rows: int,
                    candidates=None, force: bool = False,
                    storage_dtype: str = "fp32"):
    """Pick the fastest-modeled binned geometry for this graph, or None if
    the matmul backend's modeled cost beats every candidate (VERDICT r3
    item 3: products-density graphs get a measured-stats policy instead of
    the uniform-occupancy rejection).

    Degree-aware: every candidate is priced at its EXACT schedule shape
    (_plan_steps over the actual cell statistics, so skew and grouping
    effects count) and additionally as a HYBRID — cells under half a slot
    (the padding-dominated tail of a power-law degree distribution) priced
    on the one-hot matmul side instead, the dense hub cells staying
    binned.  A hybrid winner is returned with ``hub_minc`` set on the
    geometry; build_binned_plans splits the edge list accordingly.

    ADMISSION, from shapes alone: a candidate is skipped when its
    nominal scoped-VMEM footprint is over _VMEM_NOMINAL_CAP, and when the
    temporaries one bin group holds in HBM (_group_hbm_bytes: staging plus
    the larger lane-padded index operand, at _MODEL_H) are over
    _HBM_GROUP_CAP — GEOM_WIDE's grt 1 << 23 halves the steps of the
    default and is 4 % faster on the chip at Reddit shape, for 12.7 GiB
    of peak HBM against 4.8 (PR 24).

    Returns (geom, modeled_seconds), with geom None when matmul wins (and
    the seconds then model matmul).  ``force=True`` always returns the best
    binned candidate — the explicit `-aggr-backend binned` path, where
    falling back to the dense default geometry on a sparse graph would
    build a multi-GB plan.

    TUNED TIER (round 12): before any modeling, the auto path
    (``candidates is None``) consults the content-keyed tuned.json the
    autotuner persists alongside the plan cache (roc_tpu/tune) — a sweep
    winner recorded for this exact graph content + (storage, fuse)
    variant is returned outright, priced through the same exact-schedule
    model so the seconds stay comparable.  ROC_NO_TUNED=1 disables the
    tier; explicit candidate lists (forced A/Bs, the tuner's own trials)
    never consult it.

    ``storage_dtype``: "fp32" (default) or "bf16" — the feature-storage
    dtype the trainer will run.  bf16 storage adds the 16-row bf16-unit
    flat presets to the candidate list (their halved staging bytes only
    exist when the input rides bf16; an fp32 run gains nothing and would
    pay the doubled cell padding)."""
    E = len(edge_src)
    if E == 0:
        return None, 0.0
    if storage_dtype not in ("fp32", "bf16"):
        raise ValueError(f"storage_dtype={storage_dtype!r}: must be "
                         f"'fp32' or 'bf16'")
    # Tuned tier (round 12, roc_tpu/tune): a persisted sweep winner for
    # this exact graph content + variant outranks the analytic model.
    # Only the AUTO path consults it — an explicit candidate list is a
    # forced A/B (kernel_bench, the tuner's own trials) and must never
    # be diverted to the thing it is measuring against.
    if candidates is None:
        tg = _tuned_geometry(edge_src, edge_dst, num_rows, table_rows,
                             storage_dtype)
        if tg is not None:
            return _priced_tuned(edge_src, edge_dst, num_rows,
                                 table_rows, E, tg)
    cands = list(candidates) if candidates is not None else \
        [_default_geom(), GEOM_WIDE, GEOM_MID, GEOM_MID_WIDE,
         GEOM_SPARSE, GEOM_SPARSE_WIDE, GEOM_XSPARSE,
         GEOM_FLAT, GEOM_FLAT_SPARSE]
    if candidates is None and storage_dtype == "bf16":
        cands += [GEOM_FLAT_BF16, GEOM_FLAT_SPARSE_BF16]
    def fits_hbm(g, s1, s2, edges):
        groups = _plan_groups(g, num_rows, table_rows, edges)[3]
        return _group_hbm_bytes(g, s1, s2, groups) <= _HBM_GROUP_CAP

    best, best_t = None, float("inf")
    best_steps = None   # winner's (s1, s2) for the calibration ledger
    stats_cache = {}
    for g in cands:
        g = g.check()
        if _vmem_bytes(g) > _VMEM_NOMINAL_CAP:
            continue
        sk = (g.sb, g.rb)
        if sk not in stats_cache:
            # occupancy statistics depend only on the window pair; slot
            # and chunk variants reuse them
            stats_cache[sk] = _cell_stats(edge_src, edge_dst, g.sb, g.rb)
        cblk, cbin, cnt = stats_cache[sk]
        padded, s1, s2 = _plan_steps(cblk, cbin, cnt, g, num_rows,
                                     table_rows, E)
        # admitted by memory as by VMEM, from shapes: a group's staging
        # and index operands must leave the step its HBM (the hybrid
        # below is admitted on its own, smaller, schedule)
        if fits_hbm(g, s1, s2, E):
            t = _binned_cost_model(padded, g, steps1=s1, steps2=s2,
                                   copies=_flat_copies(cnt, g))
            if t < best_t:
                best, best_t, best_steps = g, t, (s1, s2)
        # Hybrid variant: the sub-half-full cells' edges go to the matmul
        # side (they pay its per-chunk rate but no slot padding); the
        # matmul window floor is a fixed cost of having a matmul side at
        # all.  Only worth modeling when a meaningful split exists.
        # (Flat geometries skip it: 8-row cell padding already absorbs
        # the thin tail the hub split exists to offload.)
        minc = 0 if g.flat else g.slot // 2
        thin = cnt < minc
        E_thin = int(cnt[thin].sum())
        if 0 < E_thin < E:
            keep = ~thin
            padded_d, s1_d, s2_d = _plan_steps(
                cblk[keep], cbin[keep], cnt[keep], g, num_rows,
                table_rows, E - E_thin)
            if not fits_hbm(g, s1_d, s2_d, E - E_thin):
                continue
            t_h = (_binned_cost_model(padded_d, g, steps1=s1_d,
                                      steps2=s2_d)
                   + _matmul_cost(E_thin, num_rows))
            if t_h < best_t:
                best = g._replace(hub_minc=minc)
                best_t, best_steps = t_h, (s1_d, s2_d)
    t_matmul = _matmul_cost(E, num_rows)
    if force or (best is not None and best_t < t_matmul):
        if best is not None and best_steps is not None:
            # Prediction half of the plan_steps/staging_rows calibration
            # pairs: the built plan's counts (build_binned_plan) join by
            # content key.  geom_time stays unpaired off-device — only a
            # hardware run (tools/kernel_bench.py) measures it.
            led = _get_ledger()
            if led.attached:
                key = _plan_key(num_rows, table_rows, E, best)
                s1, s2 = best_steps
                led.predict("plan_steps", key, s1 + s2, "steps")
                led.predict("staging_rows", key, s2 * best.ch2, "rows")
                led.predict("geom_time", key, best_t, "s")
        return best, best_t
    return None, t_matmul


def split_hub_edges(edge_src: np.ndarray, edge_dst: np.ndarray,
                    geom: Geometry):
    """Partition edges for the hybrid plan: a boolean mask that is True
    for edges in (source-block x destination-bin) cells with at least
    ``geom.hub_minc`` edges (the dense hub cells that stay binned);
    False edges take the one-hot matmul side."""
    blk = np.asarray(edge_src, np.int64) // geom.sb
    bn = np.asarray(edge_dst, np.int64) // geom.rb
    nbins = int(bn.max(initial=0)) + 1
    keys = blk * nbins + bn
    _, inv, cnt = np.unique(keys, return_inverse=True, return_counts=True)
    return cnt[inv] >= geom.hub_minc


def _prefix_within_runs(values: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """Exclusive prefix sum of `values` restarted at each change of `keys`
    (keys must be grouped).  Both [n]; returns [n]."""
    if len(values) == 0:
        return np.zeros(0, np.int64)
    csum = np.cumsum(values) - values
    first = np.concatenate([[True], keys[1:] != keys[:-1]])
    run_len = np.diff(np.concatenate([np.flatnonzero(first), [len(keys)]]))
    return csum - np.repeat(csum[first], run_len)


# Process-wide count of ACTUAL plan builds (cache hits don't count).
# The serve cold-start contract ("cache load + one trace, zero plan
# rebuilds", roc_tpu/serve) snapshots this before/after engine
# construction — a counter is pinnable where a span name is not.
_PLAN_BUILD_COUNT = 0


def plan_build_count() -> int:
    """How many binned plans this process built from scratch (cache
    hits excluded).  Monotone; diff across a window to pin rebuilds."""
    return _PLAN_BUILD_COUNT


def build_binned_plan(edge_src: np.ndarray, edge_dst: np.ndarray,
                      num_rows: int, table_rows: int,
                      group_row_target: int = _GROUP_ROW_TARGET,
                      geom: Geometry = None,
                      tuned_ok: bool = True) -> BinnedPlan:
    """Host-side schedule: sort, slot-pad, and position every edge for both
    phases.  Big edge lists take the native C++ counting-sort builder
    (O(E), ~14x the NumPy lexsort path: 2.0 s vs 27.3 s at Reddit scale,
    docs/PERF.md); the vectorized
    NumPy fallback below is the correctness oracle
    (tests/test_binned.py::test_native_plan_equals_numpy).

    At 100M-edge scale even the native build is minutes of host work per
    direction, so built plans are cached on disk keyed by the edge-list
    content and the full schedule-shaping input (geometry incl. group
    target, shape) — see _plan_cache_path.

    PLAN-CACHE HYGIENE (round 12): with ``tuned_ok`` (the default), a
    requested geometry that disagrees with a NEWER tuned-tier winner for
    this same edge content warns once and yields to the tuned config —
    the cache keys on the geometry, so without this check a plan cached
    before a sweep would keep hitting at its stale geometry forever.
    ``tuned_ok=False`` is the forced-A/B escape hatch (kernel_bench, the
    tuner's own trials, ROC_BINNED_GEOM overrides): build exactly what
    was asked."""
    from roc_tpu import native
    geom = (geom or _default_geom()).check()
    if tuned_ok and not os.environ.get("ROC_NO_TUNED"):
        try:
            from roc_tpu.tune import store as _tstore
            tg = _tstore.stale_plan_geom(edge_src, edge_dst, num_rows,
                                         table_rows, geom)
        except Exception:
            tg = None
        if tg is not None:
            geom = tg.check()
    if geom.grt:
        group_row_target = geom.grt
    cache = _plan_cache_path(edge_src, edge_dst, num_rows, table_rows,
                             group_row_target, geom)
    if cache is not None and os.path.exists(cache):
        with _obs_span("plan_cache_load", rows=num_rows,
                       edges=len(edge_src)):
            loaded = _plan_cache_load(cache, num_rows, table_rows, geom)
        if loaded is not None:
            plan = _plan_from_host(*loaded, num_rows, table_rows, geom)
            _ledger_note_plan(plan, len(edge_src))
            return plan
    global _PLAN_BUILD_COUNT
    _PLAN_BUILD_COUNT += 1
    host = None
    if len(edge_src) >= (1 << 20):
        # (a checkout's first run compiles the library in `available()`)
        with _obs_span("plan_native_build", edges=len(edge_src)):
            if native.available():
                host, bpg = _native_plan_arrays(
                    edge_src, edge_dst, num_rows, table_rows,
                    group_row_target, geom)
    if host is not None:
        plan = _plan_from_host(host, bpg, num_rows, table_rows, geom)
    else:
        plan = _build_binned_plan_numpy(edge_src, edge_dst, num_rows,
                                        table_rows, group_row_target, geom)
    if cache is not None:
        with _obs_span("plan_cache_save", edges=len(edge_src)):
            _plan_cache_save(cache, plan)
    _ledger_note_plan(plan, len(edge_src))
    return plan


def _native_plan_arrays(edge_src, edge_dst, num_rows, table_rows,
                        group_row_target, geom):
    """(host arrays, bins per group) from the C++ builder; the two index
    arrays flat, as every producer hands them to _plan_from_host."""
    from roc_tpu import native
    if geom.flat:
        (p1_srcl, p1_blk, p1_blk2, p1_dsrc, p1_ddst, p2_dstl, p2_obi,
         p2_first, bpg) = native.binned_flat_plan(
             edge_src, edge_dst, num_rows, table_rows, group_row_target,
             geom)
        extra = dict(p1_blk2=p1_blk2, p1_dsrc=p1_dsrc, p1_ddst=p1_ddst)
    else:
        (p1_srcl, p1_off, p1_blk, p2_dstl, p2_obi, p2_first,
         bpg) = native.binned_plan(edge_src, edge_dst, num_rows,
                                   table_rows, group_row_target, geom)
        extra = dict(p1_off=p1_off)
    return dict(p1_srcl=p1_srcl, p1_blk=p1_blk, p2_dstl=p2_dstl,
                p2_obi=p2_obi, p2_first=p2_first, **extra), bpg


def _plan_from_host(host: dict, bins_per_group: int, num_rows: int,
                    table_rows: int, geom: Geometry) -> BinnedPlan:
    """Place one plan's host arrays (from the cache, the native or the
    NumPy builder) on the device, and attach the fused step lists to a
    flat plan.  Every producer hands the two index arrays over in its
    linear order ([G, C * ch]); here, once, they take the lane-dense
    shape BinnedPlan documents, a chunk a row.  `jnp.asarray` may return
    before the bytes have landed: the span times the calls, and a
    transfer's tail falls to whatever waits for it next."""
    G, C1 = host["p1_blk"].shape
    C2 = host["p2_obi"].shape[1]
    host = dict(host, p1_srcl=host["p1_srcl"].reshape(G, C1, geom.ch),
                p2_dstl=host["p2_dstl"].reshape(G, C2, geom.ch2))
    with _obs_span("plan_to_device",
                   bytes=sum(int(v.nbytes) for v in host.values())):
        placed = {k: jnp.asarray(v) for k, v in host.items()}
    plan = BinnedPlan(**{"p1_off": None, **placed}, num_rows=num_rows,
                      table_rows=table_rows, bins_per_group=bins_per_group,
                      geom=geom)
    return _attach_fused(plan) if geom.flat else plan


def _plan_cache_dir() -> str:
    """Plan cache location; '' disables.  ROC_PLAN_CACHE=0 opts out,
    ROC_PLAN_CACHE_DIR overrides (tests point it at tmp dirs); the
    default sits inside the checkout (roc_tpu/cache.py), so plans built
    by one checkout's builders are never served to another's kernels."""
    if os.environ.get("ROC_PLAN_CACHE", "1") == "0":
        return ""
    from roc_tpu.cache import cache_dir
    return os.environ.get("ROC_PLAN_CACHE_DIR") or cache_dir("plans")


def _plan_cache_path(edge_src, edge_dst, num_rows, table_rows,
                     group_row_target, geom):
    """Content-keyed cache file for one built plan, or None when caching
    is off or the graph is below the worth-it threshold (hashing is O(E)
    but cheap — ~1 s/GB — next to the minutes-long 100M-edge build)."""
    min_edges = int(os.environ.get("ROC_PLAN_CACHE_MIN_EDGES", 1 << 24))
    base = _plan_cache_dir()
    if not base or len(edge_src) < min_edges:
        return None
    import hashlib
    h = hashlib.sha1()
    with _obs_span("plan_key", edges=len(edge_src)):
        h.update(np.ascontiguousarray(edge_src, np.int64).tobytes())
        h.update(np.ascontiguousarray(edge_dst, np.int64).tobytes())
    # v3: the geometry tuple grew the flat-unit field (bf16 staging), so
    # v2 files no longer match any key — a bf16<->fp32 storage flip can
    # never hit a stale plan.  (v2 was the flat-schedule field itself.)
    h.update(repr(("v3", num_rows, table_rows, group_row_target,
                   tuple(geom))).encode())
    return os.path.join(base, f"binned_plan_{h.hexdigest()}.npz")


def _plan_cache_load(path, num_rows, table_rows, geom):
    """Best-effort read of a cached plan into host memory: (arrays, bins
    per group), or None on any mismatch/corruption (rebuilds).  The fused
    step lists are NOT cached — _plan_from_host rebuilds them from the
    flat arrays (cheap next to the plan build they key on)."""
    try:
        with np.load(path) as z:
            meta = z["meta"]
            if (int(meta[0]) != num_rows or int(meta[1]) != table_rows
                    or tuple(int(v) for v in z["geom"]) != tuple(geom)):
                return None
            names = ["p1_srcl", "p1_blk", "p2_dstl", "p2_obi", "p2_first"]
            names += ["p1_blk2", "p1_dsrc", "p1_ddst"] if geom.flat \
                else ["p1_off"]
            host = {k: z[k] for k in names}
        G, C1 = host["p1_blk"].shape
        if geom.flat:
            host["p1_dsrc"] = host["p1_dsrc"].reshape(G, C1, geom.kd)
            host["p1_ddst"] = host["p1_ddst"].reshape(G, C1, geom.kd)
        return host, int(meta[2])
    except Exception:
        return None


# Process-wide count of failed plan-cache saves.  A save failure is
# deliberately non-fatal (the plan is already in memory; only the NEXT
# process pays a rebuild) but it must not be silent either: a full disk
# or bad permissions turns every future cold start into a minutes-long
# rebuild.  Warn once per process, count every failure, and emit an obs
# JSONL record when a metrics sink is attached (roc_tpu/fault).
_PLAN_CACHE_SAVE_ERRORS = 0
_PLAN_CACHE_SAVE_WARNED = False


def plan_cache_save_errors() -> int:
    """How many plan-cache saves failed in this process (monotone)."""
    return _PLAN_CACHE_SAVE_ERRORS


def _plan_cache_save(path, plan: BinnedPlan) -> None:
    """Best-effort durable save (tmp + fsync + rename); failures don't
    propagate — they warn once, count, and land in the obs JSONL."""
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + f".{os.getpid()}.tmp.npz"   # savez keeps .npz as-is
        G = plan.p1_blk.shape[0]
        arrays = dict(
            p1_srcl=np.asarray(plan.p1_srcl).reshape(G, -1),
            p1_blk=np.asarray(plan.p1_blk),
            p2_dstl=np.asarray(plan.p2_dstl).reshape(G, -1),
            p2_obi=np.asarray(plan.p2_obi),
            p2_first=np.asarray(plan.p2_first),
            meta=np.asarray([plan.num_rows, plan.table_rows,
                             plan.bins_per_group], np.int64),
            geom=np.asarray(tuple(plan.geom), np.int64))
        if plan.geom.flat:
            arrays.update(
                p1_blk2=np.asarray(plan.p1_blk2),
                p1_dsrc=np.asarray(plan.p1_dsrc).reshape(G, -1),
                p1_ddst=np.asarray(plan.p1_ddst).reshape(G, -1))
        else:
            arrays["p1_off"] = np.asarray(plan.p1_off)
        np.savez(tmp, **arrays)
        from roc_tpu.fault import fsync_replace
        fsync_replace(tmp, path)
    except Exception as e:
        global _PLAN_CACHE_SAVE_ERRORS, _PLAN_CACHE_SAVE_WARNED
        _PLAN_CACHE_SAVE_ERRORS += 1
        from roc_tpu import fault as _fault
        _fault.emit_event("plan_cache_save_error", path=str(path),
                          error=f"{type(e).__name__}: {e}")
        if not _PLAN_CACHE_SAVE_WARNED:
            _PLAN_CACHE_SAVE_WARNED = True
            warnings.warn(
                f"binned plan-cache save to {path!r} failed "
                f"({type(e).__name__}: {e}); this run is unaffected but "
                f"the next cold start will rebuild the plan from scratch "
                f"(warning once; subsequent failures are counted in "
                f"plan_cache_save_errors() and the obs JSONL)")


def _build_binned_plan_numpy(edge_src: np.ndarray, edge_dst: np.ndarray,
                             num_rows: int, table_rows: int,
                             group_row_target: int = _GROUP_ROW_TARGET,
                             geom: Geometry = None) -> BinnedPlan:
    """The oracle plan builder (vectorized NumPy lexsort + prefix sums)."""
    geom = (geom or _default_geom()).check()
    if geom.grt:
        group_row_target = geom.grt
    if geom.flat:
        return _build_flat_plan_numpy(edge_src, edge_dst, num_rows,
                                      table_rows, group_row_target, geom)
    with _obs_span("plan_numpy_build", edges=len(edge_src)):
        host, bpg = _slot_plan_arrays_numpy(
            edge_src, edge_dst, num_rows, table_rows, group_row_target,
            geom)
    return _plan_from_host(host, bpg, num_rows, table_rows, geom)


def _slot_plan_arrays_numpy(edge_src, edge_dst, num_rows: int,
                            table_rows: int, group_row_target: int,
                            geom: Geometry):
    """Host arrays of the slot schedule, and the bins per group."""
    SB, CH, SLOT, RB, CH2 = geom[:5]      # noqa: N806 — shadow the module
    NSLOT, SLOT2 = geom.nslot, geom.slot2   # constants with plan geometry
    edge_src = np.asarray(edge_src, np.int64)
    edge_dst = np.asarray(edge_dst, np.int64)
    E = edge_src.shape[0]
    num_bins = max(-(-num_rows // RB), 1)
    num_blocks = max(-(-table_rows // SB), 1)

    bins_per_group = max(min(
        num_bins,
        # bins such that expected group rows ~ group_row_target:
        int(group_row_target / max(E / num_bins, 1)),
        _K2_CAP // num_blocks), 1)
    G = -(-num_bins // bins_per_group)

    bin_of = edge_dst // RB
    blk_of = edge_src // SB
    grp_of = bin_of // bins_per_group

    # Sort edges by (group, block, bin); order within a cell is free.
    order = np.lexsort((bin_of, blk_of, grp_of))
    s_src, s_dst = edge_src[order], edge_dst[order]
    s_bin, s_blk, s_grp = bin_of[order], blk_of[order], grp_of[order]

    # --- cells = (g, blk, bin), in sorted-edge order ----------------------
    cell_key = (s_grp * num_blocks + s_blk) * num_bins + s_bin
    uniq, cell_start, cell_cnt = np.unique(
        cell_key, return_index=True, return_counts=True)
    ncell = len(uniq)
    cell_slots = -(-cell_cnt // SLOT)
    cell_g = uniq // (num_bins * num_blocks)
    cell_lbin = (uniq % num_bins) - cell_g * bins_per_group

    # --- phase-1 layout: per (g, blk) stream, cells in order --------------
    gb_key = uniq // num_bins                      # g * num_blocks + blk
    gb_uniq, gb_inv = np.unique(gb_key, return_inverse=True)
    gb_slots = np.zeros(len(gb_uniq), np.int64)
    np.add.at(gb_slots, gb_inv, cell_slots)
    gb_chunks = -(-gb_slots // NSLOT)
    gb_g = gb_uniq // num_blocks
    c1_per_g = np.zeros(G, np.int64)
    np.add.at(c1_per_g, gb_g, gb_chunks)
    C1 = int(_pad_to(max(int(c1_per_g.max(initial=0)), 1), 8))
    # chunk base of each (g, blk) stream within its group:
    gb_chunk_base = _prefix_within_runs(gb_chunks, gb_g)
    # slot base of each cell within its (g, blk) stream:
    cell_p1_slot = _prefix_within_runs(cell_slots, gb_key)

    # --- phase-2 layout: per group, bins in order, block-major cells ------
    dense_bin_slots = np.zeros(G * bins_per_group, np.int64)
    bin_idx = cell_g * bins_per_group + cell_lbin
    np.add.at(dense_bin_slots, bin_idx, cell_slots)
    dense_bin_chunks = np.maximum(-(-dense_bin_slots // SLOT2), 1)
    c2_per_g = dense_bin_chunks.reshape(G, bins_per_group).sum(1)
    C2 = int(max(int(c2_per_g.max(initial=0)), 1))
    # bin chunk base within its group:
    bin_g = np.repeat(np.arange(G), bins_per_group)
    bin_chunk_base = _prefix_within_runs(dense_bin_chunks, bin_g)
    # cell slot base within its bin (cells grouped by bin, keeping the
    # block-major cell order):
    bo = np.argsort(bin_idx, kind="stable")
    cell_off_in_bin = np.zeros(ncell, np.int64)
    cell_off_in_bin[bo] = _prefix_within_runs(cell_slots[bo], bin_idx[bo])
    # absolute staging slot of each cell (group-local):
    cell_stg_slot = bin_chunk_base[bin_idx] * SLOT2 + cell_off_in_bin

    # --- per-edge positions ------------------------------------------------
    edge_cell = np.repeat(np.arange(ncell), cell_cnt)
    in_cell = np.arange(E) - np.repeat(cell_start, cell_cnt)
    p1_row = (gb_chunk_base[gb_inv[edge_cell]] * CH
              + cell_p1_slot[edge_cell] * SLOT + in_cell)
    stg_row = cell_stg_slot[edge_cell] * SLOT + in_cell

    # --- per-slot staging offsets ------------------------------------------
    total_slots = int(cell_slots.sum())
    slot_cell = np.repeat(np.arange(ncell), cell_slots)
    slot_in_cell = (np.arange(total_slots)
                    - np.repeat(np.cumsum(cell_slots) - cell_slots,
                                cell_slots))
    p1_slot_pos = (gb_chunk_base[gb_inv[slot_cell]] * NSLOT
                   + cell_p1_slot[slot_cell] + slot_in_cell)
    stg_slot = cell_stg_slot[slot_cell] + slot_in_cell

    # --- materialize -------------------------------------------------------
    p1_srcl = np.zeros((G, C1 * CH), np.int32)
    p1_blk = np.zeros((G, C1), np.int32)
    p1_off = np.full((G, C1, NSLOT), -1, np.int32)   # -1: skip (pad slot)
    g_of_edge = cell_g[edge_cell]
    p1_srcl[g_of_edge, p1_row] = (s_src - s_blk * SB).astype(np.int32)
    if len(gb_uniq):
        blk_rep = np.repeat(gb_uniq % num_blocks, gb_chunks)
        pos_rep = (np.repeat(gb_chunk_base, gb_chunks)
                   + _prefix_within_runs(np.ones_like(blk_rep),
                                         np.repeat(np.arange(len(gb_uniq)),
                                                   gb_chunks)))
        p1_blk[np.repeat(gb_g, gb_chunks), pos_rep] = blk_rep.astype(np.int32)
    g_of_slot = cell_g[slot_cell]
    p1_off[g_of_slot, p1_slot_pos // NSLOT,
           p1_slot_pos % NSLOT] = stg_slot.astype(np.int32)

    p2_dstl = np.full((G, C2 * CH2), RB, np.int32)
    p2_dstl[g_of_edge, stg_row] = (s_dst - s_bin * RB).astype(np.int32)
    p2_obi = np.zeros((G, C2), np.int32)
    p2_first = np.zeros((G, C2), np.int32)
    dbc = dense_bin_chunks.reshape(G, bins_per_group)
    for g in range(G):
        reps = dbc[g]
        obi = np.repeat(np.arange(bins_per_group), reps).astype(np.int32)
        first = np.zeros(len(obi), np.int32)
        first[np.cumsum(reps) - reps] = 1
        p2_obi[g, :len(obi)] = obi
        p2_first[g, :len(obi)] = first
        if len(obi) < C2:   # pad chunks: revisit last bin, add only zeros
            p2_obi[g, len(obi):] = obi[-1]
    return dict(p1_srcl=p1_srcl, p1_off=p1_off, p1_blk=p1_blk,
                p2_dstl=p2_dstl, p2_obi=p2_obi,
                p2_first=p2_first), bins_per_group


def _build_flat_plan_numpy(edge_src: np.ndarray, edge_dst: np.ndarray,
                           num_rows: int, table_rows: int,
                           group_row_target: int,
                           geom: Geometry) -> BinnedPlan:
    """The flat-schedule oracle builder, placed on the device."""
    with _obs_span("plan_numpy_build", edges=len(edge_src)):
        host, bpg = _flat_plan_arrays_numpy(
            edge_src, edge_dst, num_rows, table_rows, group_row_target,
            geom)
    return _plan_from_host(host, bpg, num_rows, table_rows, geom)


def _flat_plan_arrays_numpy(edge_src, edge_dst, num_rows: int,
                            table_rows: int, group_row_target: int,
                            geom: Geometry):
    """Flat-schedule oracle builder (geom.flat): same sort and cell
    machinery as the slot builder, but cells pad to unit_rows-row units
    (8 for fp32 staging, 16 for the bf16 tile-aligned variant),
    phase-1 chunks pack back-to-back across a group's (block) streams via
    _flat_pack (a chunk may span two source blocks), and the slot-offset
    table is replaced by per-chunk run lists of size-classed staging
    copies (p1_dsrc/p1_ddst, consumed via scalar prefetch).  Phase 2 keeps
    the existing kernel: bins stay CH2-aligned in staging, one bin per
    chunk."""
    U = geom.unit_rows
    SB, CH, RB, CH2 = geom.sb, geom.ch, geom.rb, geom.ch2  # noqa: N806
    UC, U2, KD = CH // U, CH2 // U, geom.kd                # noqa: N806
    edge_src = np.asarray(edge_src, np.int64)
    edge_dst = np.asarray(edge_dst, np.int64)
    E = edge_src.shape[0]
    num_bins = max(-(-num_rows // RB), 1)
    num_blocks = max(-(-table_rows // SB), 1)
    bins_per_group = max(min(
        num_bins,
        int(group_row_target / max(E / num_bins, 1)),
        _K2_CAP // num_blocks), 1)
    G = -(-num_bins // bins_per_group)

    bin_of = edge_dst // RB
    blk_of = edge_src // SB
    grp_of = bin_of // bins_per_group
    order = np.lexsort((bin_of, blk_of, grp_of))
    s_src, s_dst = edge_src[order], edge_dst[order]
    s_bin, s_blk = bin_of[order], blk_of[order]

    cell_key = ((grp_of[order] * num_blocks + s_blk) * num_bins + s_bin)
    uniq, cell_start, cell_cnt = np.unique(
        cell_key, return_index=True, return_counts=True)
    ncell = len(uniq)
    cell_units = -(-cell_cnt // U)
    cell_g = uniq // (num_bins * num_blocks)
    cell_lbin = (uniq % num_bins) - cell_g * bins_per_group

    # --- phase-2 layout (units; bins CH2-aligned, block-major cells) ------
    dense_bin_units = np.zeros(G * bins_per_group, np.int64)
    bin_idx = cell_g * bins_per_group + cell_lbin
    np.add.at(dense_bin_units, bin_idx, cell_units)
    dense_bin_chunks = np.maximum(-(-dense_bin_units // U2), 1)
    c2_per_g = dense_bin_chunks.reshape(G, bins_per_group).sum(1)
    C2 = int(max(int(c2_per_g.max(initial=0)), 1))          # noqa: N806
    bin_g = np.repeat(np.arange(G), bins_per_group)
    bin_chunk_base = _prefix_within_runs(dense_bin_chunks, bin_g)
    bo = np.argsort(bin_idx, kind="stable")
    cell_off_in_bin = np.zeros(ncell, np.int64)
    cell_off_in_bin[bo] = _prefix_within_runs(cell_units[bo], bin_idx[bo])
    cell_stg_unit = bin_chunk_base[bin_idx] * U2 + cell_off_in_bin

    # --- phase-1 flat packing (shared state machine) ----------------------
    gb_key = uniq // num_bins                      # g * num_blocks + blk
    gb_uniq, gb_inv = np.unique(gb_key, return_inverse=True)
    gb_units = np.zeros(len(gb_uniq), np.int64)
    np.add.at(gb_units, gb_inv, cell_units)
    gb_g = gb_uniq // num_blocks
    c1_per_g, segs = _flat_pack(gb_g, gb_units, UC, G, segments=True)
    C1 = int(_pad_to(max(int(c1_per_g.max(initial=0)), 1), 8))  # noqa
    seg_stream, seg_chunk, seg_pos, seg_take = segs.T
    seg_g = gb_g[seg_stream]
    seg_blk = gb_uniq[seg_stream] % num_blocks

    # Per-chunk block pair: the pos==0 segment opens the chunk (primary);
    # any pos>0 segment is a different stream of the same group
    # (secondary).  blk2 == blk means single-block.
    p1_blk = np.zeros((G, C1), np.int32)
    opens = seg_pos == 0
    p1_blk[seg_g[opens], seg_chunk[opens]] = seg_blk[opens].astype(np.int32)
    p1_blk2 = p1_blk.copy()
    tails = ~opens
    p1_blk2[seg_g[tails], seg_chunk[tails]] = seg_blk[tails].astype(np.int32)

    # --- per-unit chunk positions (global unit order == segment order) ----
    total_units = int(cell_units.sum())
    unit_cell = np.repeat(np.arange(ncell), cell_units)
    cell_unit_base = np.cumsum(cell_units) - cell_units
    unit_in_cell = np.arange(total_units) - np.repeat(cell_unit_base,
                                                      cell_units)
    seg_start = np.cumsum(seg_take) - seg_take
    in_seg = np.arange(total_units) - np.repeat(seg_start, seg_take)
    unit_chunk = np.repeat(seg_chunk, seg_take)
    unit_pos = np.repeat(seg_pos, seg_take) + in_seg
    unit_stg = cell_stg_unit[unit_cell] + unit_in_cell
    unit_g = cell_g[unit_cell]

    # --- per-edge positions -----------------------------------------------
    edge_cell = np.repeat(np.arange(ncell), cell_cnt)
    in_cell = np.arange(E) - np.repeat(cell_start, cell_cnt)
    uid = cell_unit_base[edge_cell] + in_cell // U
    p1_row = unit_chunk[uid] * CH + unit_pos[uid] * U + in_cell % U
    stg_row = cell_stg_unit[edge_cell] * U + in_cell
    g_of_edge = cell_g[edge_cell]

    # Pad rows carry -1: no lane matches, so the one-hot emits an exact
    # zero row — staging pad rows are deterministic zeros (unlike the slot
    # schedule, whose pad slots are simply never written).
    p1_srcl = np.full((G, C1 * CH), -1, np.int32)
    local = s_src - s_blk * SB
    sec = (p1_blk[g_of_edge, unit_chunk[uid]] != s_blk).astype(np.int64)
    p1_srcl[g_of_edge, p1_row] = (local + SB * sec).astype(np.int32)

    p2_dstl = np.full((G, C2 * CH2), RB, np.int32)
    p2_dstl[g_of_edge, stg_row] = (s_dst - s_bin * RB).astype(np.int32)

    # --- staging-copy run lists -------------------------------------------
    # A run: consecutive chunk units writing consecutive staging units
    # (cell fragments; accidental cross-cell merges are valid copies).
    # Greedy 128/32/8-row decomposition, entries ordered by source unit
    # within each chunk (== per-run order, the native builder's layout).
    K = int(c1_per_g.max(initial=0)) + 1
    ckey = unit_g * K + unit_chunk
    if total_units:
        brk = np.concatenate([[True],
                              (ckey[1:] != ckey[:-1])
                              | (unit_stg[1:] != unit_stg[:-1] + 1)])
    else:
        brk = np.zeros(0, bool)
    run_start = np.flatnonzero(brk)
    run_len = np.diff(np.concatenate([run_start, [total_units]]))
    run_pos0 = unit_pos[run_start] if total_units else run_start
    run_stg0 = unit_stg[run_start] if total_units else run_start
    run_key = ckey[run_start] if total_units else run_start
    ent_src, ent_dst, ent_cls, ent_key = [], [], [], []
    off = np.zeros(len(run_start), np.int64)
    for ci, csz in enumerate(_DMA_CLS):
        k = (run_len - off) // csz
        rep = np.repeat(np.arange(len(run_start)), k)
        within = np.arange(len(rep)) - np.repeat(np.cumsum(k) - k, k)
        start = off[rep] + within * csz
        ent_src.append(run_pos0[rep] + start)
        ent_dst.append(run_stg0[rep] + start)
        ent_cls.append(np.full(len(rep), ci, np.int64))
        ent_key.append(run_key[rep])
        off += k * csz
    ent_src = np.concatenate(ent_src)
    ent_dst = np.concatenate(ent_dst)
    ent_cls = np.concatenate(ent_cls)
    ent_key = np.concatenate(ent_key)
    eo = np.lexsort((ent_src, ent_key))
    ent_src, ent_dst = ent_src[eo], ent_dst[eo]
    ent_cls, ent_key = ent_cls[eo], ent_key[eo]
    epos = _prefix_within_runs(np.ones(len(ent_key), np.int64), ent_key)
    assert len(epos) == 0 or int(epos.max()) < KD
    p1_dsrc = np.full((G, C1, KD), -1, np.int32)
    p1_ddst = np.full((G, C1, KD), -1, np.int32)
    p1_dsrc[ent_key // K, ent_key % K, epos] = \
        (ent_cls * 65536 + ent_src).astype(np.int32)
    p1_ddst[ent_key // K, ent_key % K, epos] = ent_dst.astype(np.int32)

    # --- phase-2 chunk metadata (same as the slot schedule) ---------------
    p2_obi = np.zeros((G, C2), np.int32)
    p2_first = np.zeros((G, C2), np.int32)
    dbc = dense_bin_chunks.reshape(G, bins_per_group)
    for g in range(G):
        reps = dbc[g]
        obi = np.repeat(np.arange(bins_per_group), reps).astype(np.int32)
        first = np.zeros(len(obi), np.int32)
        first[np.cumsum(reps) - reps] = 1
        p2_obi[g, :len(obi)] = obi
        p2_first[g, :len(obi)] = first
        if len(obi) < C2:
            p2_obi[g, len(obi):] = obi[-1]
    return dict(p1_srcl=p1_srcl, p1_blk=p1_blk, p2_dstl=p2_dstl,
                p2_obi=p2_obi,
                p2_first=p2_first, p1_blk2=p1_blk2, p1_dsrc=p1_dsrc,
                p1_ddst=p1_ddst), bins_per_group


def _attach_fused(plan: BinnedPlan) -> BinnedPlan:
    """Build the interleaved phase-fusion step list onto a flat plan when
    an entire group's staging fits the VMEM gate (ch == ch2 and
    C2 * ch2 <= _FUSE_MAX_STG_ROWS) — phase 2 of group g then consumes
    VMEM-resident staging while phase 1 of group g+1 streams, removing the
    HBM staging round-trip.  Otherwise returns the plan unchanged (flat
    two-pass).  Built host-side at plan/cache/pad time: inside jit the
    plan arrays are tracers, so the schedule cannot be derived at trace
    time.  run_binned re-gates on the real H before using it."""
    with _obs_span("plan_fused_steps"):
        steps = _fused_step_arrays(plan)
    if steps is None:
        return plan
    with _obs_span("plan_to_device",
                   bytes=sum(int(v.nbytes) for v in steps.values())):
        return dataclasses.replace(
            plan, **{k: jnp.asarray(v) for k, v in steps.items()})


def _fused_step_arrays(plan: BinnedPlan):
    """Host arrays of the fused step list (BinnedPlan's ``f_*`` fields),
    or None where the plan cannot fuse."""
    geom = plan.geom
    if not (geom is not None and geom.flat and geom.ch == geom.ch2):
        return None
    G, C2 = plan.p2_obi.shape
    if C2 * geom.ch2 > _FUSE_MAX_STG_ROWS:
        return None
    CH, RB, KD, bpg = geom.ch, geom.rb, geom.kd, plan.bins_per_group
    srcl = np.asarray(plan.p1_srcl).reshape(G, -1)
    dstl = np.asarray(plan.p2_dstl).reshape(G, -1)
    blk = np.asarray(plan.p1_blk)
    blk2 = np.asarray(plan.p1_blk2)
    dsrc = np.asarray(plan.p1_dsrc)
    ddst = np.asarray(plan.p1_ddst)
    obi = np.asarray(plan.p2_obi)
    first = np.asarray(plan.p2_first)
    C1 = blk.shape[1]
    # Real (non-pad) chunks: a real phase-1 chunk's first unit row is a
    # live edge (srcl >= 0); a real phase-2 chunk either opens its bin
    # (first=1 — required even for empty bins: it zeroes the window) or
    # carries live rows.  Pad chunks are skipped outright.
    p1_real = [[c for c in range(C1) if srcl[g, c * CH] >= 0]
               for g in range(G)]
    p2_real = [[q for q in range(C2)
                if first[g, q] == 1
                or (dstl[g, q * CH:(q + 1) * CH] < RB).any()]
               for g in range(G)]
    steps = [(0, 0, c) for c in p1_real[0]]
    for g in range(G):
        a = [(1, g, q) for q in p2_real[g]]
        b = ([(0, g + 1, c) for c in p1_real[g + 1]]
             if g + 1 < G else [])
        for i in range(max(len(a), len(b))):
            if i < len(a):
                steps.append(a[i])
            if i < len(b):
                steps.append(b[i])
    S = _pad_to(max(len(steps), 1), 8)
    f_meta = np.zeros((S, 4), np.int32)
    f_rows = np.full((S, CH), RB, np.int32)   # pad steps: masked p2 no-op
    f_blk = np.zeros(S, np.int32)
    f_blk2 = np.zeros(S, np.int32)
    f_obi = np.zeros(S, np.int32)
    f_dsrc = np.full((S, KD), -1, np.int32)
    f_ddst = np.full((S, KD), -1, np.int32)
    f_meta[:, 0] = 1                           # pad steps are kind=p2
    cur_blk = cur_blk2 = cur_obi = 0
    for i, (kind, g, c) in enumerate(steps):
        if kind == 0:
            cur_blk, cur_blk2 = int(blk[g, c]), int(blk2[g, c])
            f_meta[i] = (0, g % 2, 0, 0)
            f_rows[i] = srcl[g, c * CH:(c + 1) * CH]
            f_dsrc[i] = dsrc[g, c]
            f_ddst[i] = ddst[g, c]
        else:
            cur_obi = g * bpg + int(obi[g, c])
            f_meta[i] = (1, g % 2, int(first[g, c]), c)
            f_rows[i] = dstl[g, c * CH:(c + 1) * CH]
        f_blk[i], f_blk2[i], f_obi[i] = cur_blk, cur_blk2, cur_obi
    if len(steps) < S:                         # pad: revisit the last bin
        f_meta[len(steps):, 1] = steps[-1][1] % 2 if steps else 0
        f_blk[len(steps):] = cur_blk
        f_blk2[len(steps):] = cur_blk2
        f_obi[len(steps):] = cur_obi
    return dict(f_meta=f_meta, f_rows=f_rows.reshape(S * CH, 1), f_blk=f_blk,
                f_blk2=f_blk2, f_obi=f_obi, f_dsrc=f_dsrc, f_ddst=f_ddst)


# ---------------------------------------------------------------------------
# Phase-1 kernel: one-hot expand + slot-scatter to staging.
# ---------------------------------------------------------------------------

def _onehot_dot(t, xv, dims, exact: bool):
    """One-hot contraction at either precision.

    fast: single bf16 pass (the designed feature rounding).  exact: split
    the fp32 operand into hi/mid/lo bf16 (bf16 roundings of successive
    residuals; 3 x 8 mantissa bits cover fp32's 24), dot each against the
    exact one-hot factor, sum in fp32 — bit-exact row selection/summation
    up to fp32 accumulation order."""
    if not exact:
        return jax.lax.dot_general(t, xv.astype(jnp.bfloat16), dims,
                                   preferred_element_type=jnp.float32)
    xf = xv.astype(jnp.float32)
    hi = xf.astype(jnp.bfloat16)
    r1 = xf - hi.astype(jnp.float32)
    mid = r1.astype(jnp.bfloat16)
    lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    out = jax.lax.dot_general(t, hi, dims,
                              preferred_element_type=jnp.float32)
    out += jax.lax.dot_general(t, mid, dims,
                               preferred_element_type=jnp.float32)
    out += jax.lax.dot_general(t, lo, dims,
                               preferred_element_type=jnp.float32)
    return out


def _stg_dtype(exact: bool):
    return jnp.float32 if exact else jnp.bfloat16


# dot_general dimension numbers of the two one-hot contractions
_DOT_DIM0 = (((0,), (0,)), ((), ()))    # t[K, M], v[K, H] -> [M, H]
_DOT_MM = (((1,), (0,)), ((), ()))      # s[M, K] @ v[K, H]


def _idx_row(ref, c):
    """Chunk c's indices as [1, width], the chunk on the lane axis.  The
    index operands ([C, width], a chunk a row) ride in (8, width) blocks
    indexed c // 8, as `off` does: eight chunks' rows a fetch, read as
    stored, 4 bytes an index where a (width, 1) column block tiled to
    128 lanes read 512; this chunk's row is c % 8."""
    return ref[pl.ds(c % 8, 1), :]  # roclint: allow(mosaic-align) — a one-sublane vector load out of a VMEM block, not a DMA; Mosaic compiles it for the v5e


def _onehot_rows(idx, n: int):
    """[n, width] bf16 one-hot of a [1, width] index row: entry (r, j) is
    1 iff idx[j] == r.  Indices outside [0, n) (the pad values -1 and RB,
    a flat chunk's other block) give an all-zero column."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (n, idx.shape[1]), 0)
    return (sub == idx).astype(jnp.bfloat16)


def _p1_kernel_simple(blk_ref, off_ref, srcl_ref, x_ref, stg_ref, gbuf,
                      offbuf, sems, *, exact: bool = False,
                      geom: Geometry = None):
    """Single-buffered fallback (ROC_BINNED_NO_PIPELINE=1): issue all slot
    DMAs then drain them in the same chunk.  No cross-chunk overlap, but
    structurally identical to the skeleton measured on hardware — keep as
    the bisection baseline if the pipelined kernel misbehaves on a new
    Mosaic version."""
    CH, SB, SLOT, NSLOT = geom.ch, geom.sb, geom.slot, geom.nslot  # noqa
    c = pl.program_id(0)

    t = _onehot_rows(_idx_row(srcl_ref, c), SB)          # [SB, CH]
    gbuf[0] = _onehot_dot(t, x_ref[:], _DOT_DIM0,
                          exact).astype(_stg_dtype(exact))

    def issue(s, _):
        @pl.when(off_ref[c % 8, s] >= 0)
        def _():
            pltpu.make_async_copy(
                gbuf.at[0].at[pl.ds(s * SLOT, SLOT)],
                stg_ref.at[pl.ds(off_ref[c % 8, s] * SLOT, SLOT)],
                sems.at[0]).start()
        return 0
    jax.lax.fori_loop(0, NSLOT, issue, 0)

    def drain(s, _):
        @pl.when(off_ref[c % 8, s] >= 0)
        def _():
            pltpu.make_async_copy(
                gbuf.at[0].at[pl.ds(s * SLOT, SLOT)],
                stg_ref.at[pl.ds(off_ref[c % 8, s] * SLOT, SLOT)],
                sems.at[0]).wait()
        return 0
    jax.lax.fori_loop(0, NSLOT, drain, 0)


def _p1_kernel(blk_ref, off_ref, srcl_ref, x_ref, stg_ref, gbuf, offbuf,
               sems, *, exact: bool = False, geom: Geometry = None):
    """Double-buffered: the slot DMAs issued for chunk c drain at chunk
    c+2 (same gbuf parity), so the writes of one chunk overlap the next
    chunk's one-hot matmul.  ``offbuf`` keeps each parity's issued offsets
    (the wait must reconstruct the same descriptors); pad slots carry
    offset -1 and are skipped — per-block chunk rounding makes them
    20-40% of all slots, so not writing them matters."""
    CH, SB, SLOT, NSLOT = geom.ch, geom.sb, geom.slot, geom.nslot  # noqa
    c = pl.program_id(0)
    par = c % 2

    def drain_parity(p):
        def drain(s, _):
            @pl.when(offbuf[p, s] >= 0)
            def _():
                pltpu.make_async_copy(
                    gbuf.at[p].at[pl.ds(s * SLOT, SLOT)],
                    stg_ref.at[pl.ds(offbuf[p, s] * SLOT, SLOT)],
                    sems.at[p]).wait()
            return 0
        jax.lax.fori_loop(0, NSLOT, drain, 0)

    @pl.when(c >= 2)            # chunk c-2 used this parity's buffers
    def _():
        drain_parity(par)

    t = _onehot_rows(_idx_row(srcl_ref, c), SB)          # [SB, CH]
    gbuf[par] = _onehot_dot(t, x_ref[:], _DOT_DIM0,
                            exact).astype(_stg_dtype(exact))

    # off rides in (8, NSLOT) SMEM blocks; this chunk's row is c % 8.
    def issue(s, _):
        offbuf[par, s] = off_ref[c % 8, s]
        @pl.when(off_ref[c % 8, s] >= 0)
        def _():
            pltpu.make_async_copy(
                gbuf.at[par].at[pl.ds(s * SLOT, SLOT)],
                stg_ref.at[pl.ds(off_ref[c % 8, s] * SLOT, SLOT)],
                sems.at[par]).start()
        return 0
    jax.lax.fori_loop(0, NSLOT, issue, 0)

    # Last chunk: drain everything still in flight (both parities) —
    # pallas does not wait for manual DMAs at grid end.
    @pl.when(c == pl.num_programs(0) - 1)
    def _():
        drain_parity(par)

        @pl.when(c >= 1)
        def _():
            drain_parity(1 - par)


@partial(jax.jit, static_argnames=("nchunks", "stg_rows", "interpret",
                                   "exact", "geom"))
def _p1_run(x, blk, off, srcl, nchunks: int, stg_rows: int,
            interpret: bool = False, exact: bool = False,
            geom: Geometry = None):
    kernel = _p1_kernel_simple \
        if os.environ.get("ROC_BINNED_NO_PIPELINE") else _p1_kernel
    kernel = partial(kernel, exact=exact, geom=geom)
    H = x.shape[-1]
    st = _stg_dtype(exact)
    CH, SB, NSLOT = geom.ch, geom.sb, geom.nslot                   # noqa
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                  # blk [C1]
        grid=(nchunks,),
        in_specs=[
            pl.BlockSpec((8, NSLOT), lambda c, blk: (c // 8, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((8, CH), lambda c, blk: (c // 8, 0)),
            pl.BlockSpec((SB, H), lambda c, blk: (blk[c], 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((2, CH, H), st),
                        pltpu.SMEM((2, NSLOT), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((stg_rows, H), st),
        compiler_params=_vmem_params(_p1_vmem_bytes(geom, H, exact)),
        interpret=interpret,
    )(blk, off, srcl, x)


def _flat_copy(gbuf, stg_ref, sems, p, v, du, start: bool,
               unit: int = _UNIT):
    """One size-classed staging copy from a packed descriptor: v encodes
    cls<<16 | source unit, du is the destination unit.  Three static
    branches — pl.ds sizes must be compile-time — of 16/4/1 units
    (128/32/8 rows fp32, 256/64/16 rows bf16; either way every slice is
    whole sublane tiles of the staging dtype)."""
    cls = v // 65536
    su = v - cls * 65536
    for ci, csz in enumerate(_DMA_CLS):
        @pl.when(cls == ci)
        def _(csz=csz):
            cp = pltpu.make_async_copy(
                gbuf.at[p].at[pl.ds(su * unit, csz * unit)],
                stg_ref.at[pl.ds(du * unit, csz * unit)],
                sems.at[p])
            (cp.start if start else cp.wait)()


def _p1_flat_kernel(blk_ref, blk2_ref, dsrc_ref, ddst_ref, srcl_ref,
                    x_ref, x2_ref, stg_ref, gbuf, dbs, dbd, sems, *,
                    exact: bool = False, geom: Geometry = None,
                    pipeline: bool = True):
    """Flat-schedule phase 1: every grid step is a full-width chunk.  The
    one-hot expands against TWO x blocks (srcl in [0, SB) hits the
    primary, [SB, 2SB) the secondary — a chunk spans at most two source
    blocks by plan construction; -1 pad rows match nothing and stage
    exact zeros), then the chunk scatters to bin-major staging via the
    plan's size-classed copy run list (KD descriptors, SMEM).  Double
    buffering mirrors _p1_kernel: copies issued for chunk c drain at
    c+2, with dbs/dbd keeping each parity's descriptors for the wait;
    pipeline=False is the ROC_BINNED_NO_PIPELINE bisection baseline."""
    CH, SB, KD = geom.ch, geom.sb, geom.kd                         # noqa
    U = geom.unit_rows
    st = staging_dtype(geom, exact)
    c = pl.program_id(0)
    par = c % 2 if pipeline else 0

    def drain_parity(p):
        def drain(e, _):
            @pl.when(dbs[p, e] >= 0)
            def _():
                _flat_copy(gbuf, stg_ref, sems, p, dbs[p, e], dbd[p, e],
                           start=False, unit=U)
            return 0
        jax.lax.fori_loop(0, KD, drain, 0)

    if pipeline:
        @pl.when(c >= 2)        # chunk c-2 used this parity's buffers
        def _():
            drain_parity(par)

    sl = _idx_row(srcl_ref, c)
    gbuf[par] = _onehot_dot(_onehot_rows(sl, SB), x_ref[:], _DOT_DIM0,
                            exact).astype(st)

    @pl.when(blk2_ref[c] != blk_ref[c])
    def _():
        # secondary-block rows (disjoint from the primary's by the
        # +SB encoding, so the sum is exact row selection — each row is
        # rounded to the staging dtype exactly once)
        gbuf[par] = (gbuf[par].astype(jnp.float32) + _onehot_dot(
            _onehot_rows(sl - SB, SB), x2_ref[:], _DOT_DIM0,
            exact)).astype(st)

    # descriptors ride in (8, KD) SMEM blocks; this chunk's row is c % 8
    def issue(e, _):
        v = dsrc_ref[c % 8, e]
        dbs[par, e] = v
        dbd[par, e] = ddst_ref[c % 8, e]

        @pl.when(v >= 0)
        def _():
            _flat_copy(gbuf, stg_ref, sems, par, v, ddst_ref[c % 8, e],
                       start=True, unit=U)
        return 0
    jax.lax.fori_loop(0, KD, issue, 0)

    if pipeline:
        @pl.when(c == pl.num_programs(0) - 1)
        def _():
            drain_parity(par)

            @pl.when(c >= 1)
            def _():
                drain_parity(1 - par)
    else:
        drain_parity(0)


@partial(jax.jit, static_argnames=("nchunks", "stg_rows", "interpret",
                                   "exact", "geom"))
def _p1_flat_run(x, blk, blk2, dsrc, ddst, srcl, nchunks: int,
                 stg_rows: int, interpret: bool = False,
                 exact: bool = False, geom: Geometry = None):
    pipeline = not os.environ.get("ROC_BINNED_NO_PIPELINE")
    kernel = partial(_p1_flat_kernel, exact=exact, geom=geom,
                     pipeline=pipeline)
    H = x.shape[-1]
    CH, SB, KD = geom.ch, geom.sb, geom.kd                         # noqa
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # blk, blk2 [C1]
        grid=(nchunks,),
        in_specs=[
            pl.BlockSpec((8, KD), lambda c, blk, blk2: (c // 8, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((8, KD), lambda c, blk, blk2: (c // 8, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((8, CH), lambda c, blk, blk2: (c // 8, 0)),
            pl.BlockSpec((SB, H), lambda c, blk, blk2: (blk[c], 0)),
            pl.BlockSpec((SB, H), lambda c, blk, blk2: (blk2[c], 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        # flat staging dtype follows the geometry: fp32 for 8-row units
        # (bf16 (16,128) tiles would tear), bf16 for the 16-row unit
        # variant; gbuf matches so DMA src/dst dtypes agree
        scratch_shapes=[pltpu.VMEM((2, CH, H), staging_dtype(geom, exact)),
                        pltpu.SMEM((2, KD), jnp.int32),
                        pltpu.SMEM((2, KD), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((stg_rows, H),
                                       staging_dtype(geom, exact)),
        compiler_params=_vmem_params(_p1_vmem_bytes(geom, H, exact)),
        interpret=interpret,
    )(blk, blk2, dsrc, ddst, srcl, x, x)


# ---------------------------------------------------------------------------
# Phase-2 kernel: sequential staging read + windowed one-hot scatter.
# ---------------------------------------------------------------------------

def _p2_kernel(obi_ref, first_ref, dstl_ref, stg_ref, out_ref, *,
               exact: bool = False, geom: Geometry = None):
    CH2, RB = geom.ch2, geom.rb                                    # noqa
    c = pl.program_id(0)

    @pl.when(first_ref[c] == 1)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # Zero-mask pad/garbage rows BEFORE the dot: a 0 one-hot coefficient
    # alone would still propagate NaN garbage (0 * NaN = NaN).  The mask
    # is per staging ROW and the indices are a row themselves, so the one
    # predicate is turned in VMEM (CH2 values a step; whole (8, 128)
    # tiles, which is what Mosaic transposes).
    d = _idx_row(dstl_ref, c)                            # [1, CH2]
    pad = jnp.broadcast_to((d == RB).astype(jnp.int32), (8, CH2)).T[:, :1]
    rows = jnp.where(pad != 0, _stg_dtype(exact)(0), stg_ref[:])
    out_ref[:] += _onehot_dot(_onehot_rows(d, RB), rows, _DOT_MM, exact)


@partial(jax.jit, static_argnames=("nchunks", "out_rows", "interpret",
                                   "exact", "geom"))
def _p2_run(stg, obi, first, dstl, nchunks: int, out_rows: int,
            interpret: bool = False, exact: bool = False,
            geom: Geometry = None):
    H = stg.shape[-1]
    CH2, RB = geom.ch2, geom.rb                                    # noqa
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # obi, first
        grid=(nchunks,),
        in_specs=[
            pl.BlockSpec((8, CH2), lambda c, obi, first: (c // 8, 0)),
            pl.BlockSpec((CH2, H), lambda c, obi, first: (c, 0)),
        ],
        out_specs=pl.BlockSpec((RB, H), lambda c, obi, first: (obi[c], 0)),
    )
    return pl.pallas_call(
        partial(_p2_kernel, exact=exact, geom=geom), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((out_rows, H), jnp.float32),
        compiler_params=_vmem_params(_p2_vmem_bytes(geom, H, exact)),
        interpret=interpret,
    )(obi, first, dstl, stg)


# ---------------------------------------------------------------------------
# Fused pipeline: phase-1/phase-2 steps interleaved in ONE grid, staging
# resident in VMEM (flat plans whose whole group fits the budget).
# ---------------------------------------------------------------------------

def _fused_kernel(blk_ref, blk2_ref, obi_ref, meta_ref, dsrc_ref, ddst_ref,
                  rows_ref, x_ref, x2_ref, out_ref, gbuf, stgbuf, sems, *,
                  exact: bool = False, geom: Geometry = None):
    """One grid step = one plan-scheduled step: kind 0 (phase 1) expands
    a chunk and copies it into the VMEM-resident staging parity of its
    group; kind 1 (phase 2) scatter-adds one staging chunk of that parity
    into the resident out bin.  Group parities alternate, so phase 2 of
    group g reads parity g%2 while phase 1 of group g+1 fills the other —
    the interleave order (plan-built, _attach_fused) guarantees p1(g)
    precedes p2(g) and p2(g) completes before p1(g+2) reuses its parity.
    The out index (global bin) is nondecreasing, so out windows are never
    revisited after writeback; every bin opens with first=1, which zeroes
    the fetched garbage."""
    CH, SB, RB, KD = geom.ch, geom.sb, geom.rb, geom.kd            # noqa
    U = geom.unit_rows
    st = staging_dtype(geom, exact)
    c = pl.program_id(0)
    kind = meta_ref[c % 8, 0]
    par = meta_ref[c % 8, 1]
    first = meta_ref[c % 8, 2]
    sq = meta_ref[c % 8, 3]

    @pl.when(kind == 0)
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, (CH, SB), 1)
        sl = rows_ref[:]
        t1 = (lane == sl).astype(jnp.bfloat16)
        gbuf[:] = _onehot_dot(t1, x_ref[:], (((1,), (0,)), ((), ())),
                              exact).astype(st)

        @pl.when(blk2_ref[c] != blk_ref[c])
        def _():
            t2 = (lane == sl - SB).astype(jnp.bfloat16)
            gbuf[:] = (gbuf[:].astype(jnp.float32) + _onehot_dot(
                t2, x2_ref[:], (((1,), (0,)), ((), ())), exact)).astype(st)

        # VMEM->VMEM staging copies: issue all, drain all within the step
        # (the overlap is across phases here, not across copies)
        def issue(e, _):
            v = dsrc_ref[c % 8, e]

            @pl.when(v >= 0)
            def _():
                cls = v // 65536
                su = v - cls * 65536
                du = ddst_ref[c % 8, e]
                for ci, csz in enumerate(_DMA_CLS):
                    @pl.when(cls == ci)
                    def _(csz=csz):
                        pltpu.make_async_copy(
                            gbuf.at[pl.ds(su * U, csz * U)],
                            stgbuf.at[par].at[
                                pl.ds(du * U, csz * U)],
                            sems.at[0]).start()
            return 0
        jax.lax.fori_loop(0, KD, issue, 0)

        def drain(e, _):
            v = dsrc_ref[c % 8, e]

            @pl.when(v >= 0)
            def _():
                cls = v // 65536
                su = v - cls * 65536
                du = ddst_ref[c % 8, e]
                for ci, csz in enumerate(_DMA_CLS):
                    @pl.when(cls == ci)
                    def _(csz=csz):
                        pltpu.make_async_copy(
                            gbuf.at[pl.ds(su * U, csz * U)],
                            stgbuf.at[par].at[
                                pl.ds(du * U, csz * U)],
                            sems.at[0]).wait()
            return 0
        jax.lax.fori_loop(0, KD, drain, 0)

    @pl.when(kind == 1)
    def _():
        @pl.when(first == 1)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        dl = rows_ref[:]
        chunk = stgbuf[par, pl.ds(sq * CH, CH)]
        rows = jnp.where(dl == RB, jnp.float32(0), chunk)
        lane = jax.lax.broadcasted_iota(jnp.int32, (CH, RB), 1)
        s_t = (lane == dl).astype(jnp.bfloat16)
        out_ref[:] += _onehot_dot(s_t, rows, (((0,), (0,)), ((), ())),
                                  exact)


@partial(jax.jit, static_argnames=("nsteps", "c2", "out_rows", "interpret",
                                   "exact", "geom"))
def _fused_run(x, blk, blk2, obi, meta, dsrc, ddst, rows, nsteps: int,
               c2: int, out_rows: int, interpret: bool = False,
               exact: bool = False, geom: Geometry = None):
    H = x.shape[-1]
    CH, SB, RB, KD = geom.ch, geom.sb, geom.rb, geom.kd            # noqa
    srows = c2 * geom.ch2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                  # blk, blk2, obi [S]
        grid=(nsteps,),
        in_specs=[
            pl.BlockSpec((8, 4), lambda c, b, b2, o: (c // 8, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((8, KD), lambda c, b, b2, o: (c // 8, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((8, KD), lambda c, b, b2, o: (c // 8, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((CH, 1), lambda c, b, b2, o: (c, 0)),
            pl.BlockSpec((SB, H), lambda c, b, b2, o: (b[c], 0)),
            pl.BlockSpec((SB, H), lambda c, b, b2, o: (b2[c], 0)),
        ],
        out_specs=pl.BlockSpec((RB, H), lambda c, b, b2, o: (o[c], 0)),
        scratch_shapes=[pltpu.VMEM((CH, H), staging_dtype(geom, exact)),
                        pltpu.VMEM((2, srows, H),
                                   staging_dtype(geom, exact)),
                        pltpu.SemaphoreType.DMA((1,))],
    )
    return pl.pallas_call(
        partial(_fused_kernel, exact=exact, geom=geom),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((out_rows, H), jnp.float32),
        interpret=interpret,
    )(blk, blk2, obi, meta, dsrc, ddst, rows, x, x)


def _fused_vmem_ok(geom: Geometry, Hp: int, c2: int) -> bool:
    """Trace-time gate for actually RUNNING a stored fused schedule at
    this width: both staging parities + gbuf + the one-hot intermediates
    + two x blocks + the out window must fit the VMEM budget."""
    srows = c2 * geom.ch2
    stg = staging_itemsize(geom, False)
    need = (2 * srows * Hp * stg + geom.ch * Hp * stg
            + max(geom.ch * geom.sb, geom.ch2 * geom.rb) * 2
            + 2 * geom.sb * Hp * 4 + geom.rb * Hp * 4)
    return need <= _VMEM_BUDGET


# one-shot: the eager path is a silent ~9x dispatch-overhead footgun
# (1.65 s vs 184 ms jitted at Reddit scale, docs/PERF.md) — warn once
# per process, never per call.
_EAGER_WARNED = [False]


def _unfused(out_g):
    """Keep a scan body's phase-2 call out of XLA's output fusion.  With
    more than one bin group XLA fuses the Mosaic call with the scan's
    stacking dynamic-update-slice into one kCustom fusion, and that
    fusion is compiled under the 16 MiB default scoped-VMEM limit — the
    kernel's own vmem_limit_bytes is not carried over (seen compiling the
    Reddit-shape step for a v5e: "Scoped allocation with size 18.25M and
    limit 16.00M").  The barrier costs one extra copy of the [rows, H]
    group output."""
    return jax.lax.optimization_barrier(out_g)


def run_binned(x, plan: BinnedPlan, interpret: bool = False,
               precision: str = "fast"):
    """out[v] = sum over in-edges of x[src] via the two-phase schedule.

    x: [table_rows, H] (any float dtype) -> [num_rows, H] in x.dtype.
    fp32 accumulation; precision "fast" rounds features once to bf16,
    "exact" keeps fp32 end to end via 3-way bf16 splits (module doc).
    A bf16 input makes the two identical, so exact quietly degrades to
    the cheaper fast path there.

    Call under jit (the trainer always does): measured on v5e at Reddit
    scale, the eager path pays ~6x in scan dispatch overhead (1.65 s vs
    213 ms jitted — docs/PERF.md)."""
    if not _EAGER_WARNED[0] and not isinstance(x, jax.core.Tracer):
        _EAGER_WARNED[0] = True
        warnings.warn(
            "run_binned called outside a jit trace: the eager scan path "
            "pays ~9x in dispatch overhead (1.65 s vs 184 ms jitted at "
            "Reddit scale, docs/PERF.md) — wrap the caller in jax.jit.",
            stacklevel=2)
    if precision not in ("fast", "exact"):
        # same rule as ops.aggregate.matmul_precision: a silent fallthrough
        # to the fast path would drop the fp32-exact guarantee
        raise ValueError(f"precision={precision!r}: must be 'fast' or "
                         f"'exact'")
    exact = precision == "exact" and x.dtype == jnp.float32
    if precision == "exact" and x.dtype not in (jnp.float32, jnp.bfloat16):
        # bf16 degrades to fast losslessly (identical semantics); any
        # other dtype would silently round through bf16 staging
        raise ValueError(f"precision='exact' supports float32/bfloat16 "
                         f"inputs, got {x.dtype}")
    # Mosaic requires DMA slices lane-aligned to the (8,128) tile: the slot
    # DMAs out of gbuf slice the H axis, so H must be a multiple of 128
    # (observed hard error at H=41: "Slice shape along dimension 2 must be
    # aligned to tiling (128)").  Pad features up and strip at the end —
    # the extra lanes ride the same tiles the hardware moves anyway.
    H = x.shape[-1]
    Hp = _pad_to(H, 128)
    geom = plan.geom or _default_geom()
    if exact and geom.flat and geom.unit == 16:
        # the 16-row unit exists only to make bf16 staging tile-legal;
        # routing fp32-exact through it would round every staged row
        raise ValueError(
            "precision='exact' is incompatible with a unit=16 (bf16 "
            "staging) flat geometry: pick a unit=0 flat preset or "
            "precision='fast'")
    G, C1 = plan.p1_blk.shape
    C2 = plan.p2_obi.shape[1]
    xp = jnp.pad(x, ((0, _pad_to(plan.table_rows, geom.sb) - x.shape[0]),
                     (0, Hp - H)))
    stg_rows = C2 * geom.ch2

    if geom.flat:
        out_rows = G * plan.bins_per_group * geom.rb
        if (plan.f_meta is not None
                and not os.environ.get("ROC_BINNED_NO_FUSE")
                and _fused_vmem_ok(geom, Hp, C2)):
            # fused pipeline: one grid, staging VMEM-resident, phases of
            # adjacent groups interleaved (gating re-checked against the
            # REAL padded width — the plan-build gate used a model H)
            S = int(plan.f_blk.shape[0])
            with scopes.scope("fused"):
                out = _fused_run(xp, plan.f_blk, plan.f_blk2, plan.f_obi,
                                 plan.f_meta, plan.f_dsrc, plan.f_ddst,
                                 plan.f_rows, S, C2, out_rows, interpret,
                                 exact, geom)
            return out[:plan.num_rows, :H].astype(x.dtype)

        def fbody(_, gplan):
            srcl, blk, blk2, dsrc, ddst, dstl, obi, first = gplan
            with scopes.scope("p1_flat"):
                stg = _p1_flat_run(xp, blk, blk2, dsrc, ddst, srcl, C1,
                                   stg_rows, interpret, exact, geom)
            with scopes.scope("p2"):
                out_g = _p2_run(stg, obi, first, dstl, C2,
                                plan.bins_per_group * geom.rb, interpret,
                                exact, geom)
            return None, _unfused(out_g)

        _, outs = jax.lax.scan(
            fbody, None,
            (plan.p1_srcl, plan.p1_blk, plan.p1_blk2,
             plan.p1_dsrc, plan.p1_ddst,
             plan.p2_dstl, plan.p2_obi, plan.p2_first))
        out = outs.reshape(out_rows, Hp)
        return out[:plan.num_rows, :H].astype(x.dtype)

    def body(_, gplan):
        srcl, off, blk, dstl, obi, first = gplan
        with scopes.scope("p1"):
            stg = _p1_run(xp, blk, off, srcl, C1, stg_rows, interpret,
                          exact, geom)
        with scopes.scope("p2"):
            out_g = _p2_run(stg, obi, first, dstl, C2,
                            plan.bins_per_group * geom.rb, interpret,
                            exact, geom)
        return None, _unfused(out_g)

    _, outs = jax.lax.scan(
        body, None,
        (plan.p1_srcl, plan.p1_off, plan.p1_blk,
         plan.p2_dstl, plan.p2_obi, plan.p2_first))
    out = outs.reshape(G * plan.bins_per_group * geom.rb, Hp)
    return out[:plan.num_rows, :H].astype(x.dtype)


def pad_binned_plan(plan: BinnedPlan, C1: int, C2: int) -> BinnedPlan:
    """Pad a plan's chunk counts up to (C1, C2) with canonical no-ops so
    per-shard plans can be stacked into one static shard_map program
    (the binned analog of segment_sum.pad_chunks).

    Pad phase-1 chunks: block 0, all slots skipped (-1).  Pad phase-2
    chunks: revisit the last bin with first=0 and every row masked (RB)."""
    geom = plan.geom or _default_geom()
    G, c1 = plan.p1_blk.shape
    c2 = plan.p2_obi.shape[1]
    assert C1 >= c1 and C2 >= c2 and C1 % 8 == 0
    d1, d2 = C1 - c1, C2 - c2
    if d1 == 0 and d2 == 0:
        return plan
    if geom.flat:
        # flat pads: every slot masked (-1 -> one-hot no-match -> zero
        # row), no staging copies (dsrc/ddst -1), phase 2 revisits the
        # last bin fully masked.  Fused arrays stay valid — they index
        # only real chunks, and staging chunk ids are a prefix of the
        # padded layout — so keep them.
        return dataclasses.replace(
            plan,
            p1_srcl=jnp.pad(plan.p1_srcl, ((0, 0), (0, d1), (0, 0)),
                            constant_values=-1),
            p1_blk=jnp.pad(plan.p1_blk, ((0, 0), (0, d1))),
            p1_blk2=jnp.pad(plan.p1_blk2, ((0, 0), (0, d1))),
            p1_dsrc=jnp.pad(plan.p1_dsrc, ((0, 0), (0, d1), (0, 0)),
                            constant_values=-1),
            p1_ddst=jnp.pad(plan.p1_ddst, ((0, 0), (0, d1), (0, 0)),
                            constant_values=-1),
            p2_dstl=jnp.pad(plan.p2_dstl, ((0, 0), (0, d2), (0, 0)),
                            constant_values=geom.rb),
            p2_obi=jnp.pad(plan.p2_obi, ((0, 0), (0, d2)), mode="edge"),
            p2_first=jnp.pad(plan.p2_first, ((0, 0), (0, d2))))
    return BinnedPlan(
        p1_srcl=jnp.pad(plan.p1_srcl, ((0, 0), (0, d1), (0, 0))),
        p1_off=jnp.pad(plan.p1_off, ((0, 0), (0, d1), (0, 0)),
                       constant_values=-1),
        p1_blk=jnp.pad(plan.p1_blk, ((0, 0), (0, d1))),
        p2_dstl=jnp.pad(plan.p2_dstl, ((0, 0), (0, d2), (0, 0)),
                        constant_values=geom.rb),
        p2_obi=jnp.pad(plan.p2_obi, ((0, 0), (0, d2)), mode="edge"),
        p2_first=jnp.pad(plan.p2_first, ((0, 0), (0, d2))),
        num_rows=plan.num_rows, table_rows=plan.table_rows,
        bins_per_group=plan.bins_per_group, geom=plan.geom)


# -- incremental cell re-cut (dynamic-graph deltas, roc_tpu/serve/delta) ----
#
# The builders above are whole-graph; serving-time edge churn must not
# rebuild (minutes of host work at scale) or retrace (new buffers = new
# jit cache entry).  The delta path instead re-cuts ONE (source-block x
# destination-bin) cell at a time: a plan's cells are contiguous,
# capacity-padded row ranges of p1_srcl / p2_dstl whose positions are a
# pure function of the BUILD-TIME edge list and geometry, so rewriting a
# cell's rows in place (live edges compacted first, pad values after)
# reproduces the builder's semantics exactly while every other array —
# p1_off / p1_blk / p1_dsrc / p1_ddst / p2_obi / p2_first — stays
# untouched (they encode the cell LAYOUT, not the cell CONTENTS).
# plan_cell_layout re-derives that layout with builder-identical
# arithmetic; patch_plan_cells rewrites one cell into host copies of the
# two content arrays, which the caller device_puts into the SAME padded
# shapes (same treedef, same jit cache — zero retraces by construction).


class CellOverflowError(Exception):
    """An edge delta does not fit a cell's build-time slot padding (or
    lands in a cell the plan never cut).  Not a failure: the caller's
    escalation ladder answers with a full replan (roc_tpu/serve/delta)."""


@dataclasses.dataclass
class CellLayout:
    """Per-cell row geometry of one built plan direction.

    ``cell_ptr[i]:cell_ptr[i+1]`` indexes the flat row maps for cell i
    (capacity rows, in in-cell order):
      row_p1  row into the group's [C1*CH] phase-1 srcl rows
      row_stg row into the group's [C2*CH2] staging rows
      row_sec flat-schedule secondary-block addend (0 or sb; slot: 0)
    ``pad_srcl`` is the builder's value for unwritten p1 rows (slot
    schedule 0 — staged garbage masked at phase 2; flat -1 — exact-zero
    one-hot row)."""
    num_rows: int
    table_rows: int
    bins_per_group: int
    geom: Geometry
    G: int
    C1: int
    C2: int
    num_bins: int
    num_blocks: int
    cell_blk: np.ndarray    # [ncell] int64 source block
    cell_bin: np.ndarray    # [ncell] int64 GLOBAL destination bin
    cell_cap: np.ndarray    # [ncell] int64 padded row capacity
    cell_ptr: np.ndarray    # [ncell+1] int64 prefix into the row maps
    row_p1: np.ndarray      # [sum(cap)] int64
    row_stg: np.ndarray     # [sum(cap)] int64
    row_sec: np.ndarray     # [sum(cap)] int64
    pad_srcl: int

    def __post_init__(self):
        k = self.cell_blk * self.num_bins + self.cell_bin
        self._korder = np.argsort(k)
        self._ksorted = k[self._korder]

    @property
    def ncell(self) -> int:
        return len(self.cell_blk)

    def cells_of(self, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
        """Cell index of each (src, dst) edge; -1 where the plan never
        cut that (block, bin) cell (caller escalates to a replan)."""
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        q = (src // self.geom.sb) * self.num_bins + dst // self.geom.rb
        pos = np.searchsorted(self._ksorted, q)
        pos = np.minimum(pos, max(len(self._ksorted) - 1, 0))
        out = np.full(len(q), -1, np.int64)
        if len(self._ksorted):
            hit = self._ksorted[pos] == q
            out[hit] = self._korder[pos[hit]]
        return out


def plan_cell_layout(edge_src: np.ndarray, edge_dst: np.ndarray,
                     num_rows: int, table_rows: int,
                     geom: Geometry = None,
                     group_row_target: int = _GROUP_ROW_TARGET
                     ) -> CellLayout:
    """Re-derive a built plan's per-cell row layout from its BUILD-TIME
    edge list (the same arrays the plan was built from, in the same
    order) with builder-identical arithmetic — every formula below
    mirrors _build_binned_plan_numpy / _build_flat_plan_numpy, and the
    delta manager verifies the claim by re-rendering the content arrays
    from this layout and comparing them to the plan's (so native-builder
    drift refuses the patch path instead of corrupting it)."""
    geom = (geom or _default_geom()).check()
    if geom.grt:
        group_row_target = geom.grt
    SB, CH, SLOT, RB, CH2 = geom[:5]                   # noqa: N806
    edge_src = np.asarray(edge_src, np.int64)
    edge_dst = np.asarray(edge_dst, np.int64)
    E = edge_src.shape[0]
    num_bins = max(-(-num_rows // RB), 1)
    num_blocks = max(-(-table_rows // SB), 1)
    bins_per_group = max(min(
        num_bins,
        int(group_row_target / max(E / num_bins, 1)),
        _K2_CAP // num_blocks), 1)
    G = -(-num_bins // bins_per_group)

    bin_of = edge_dst // RB
    blk_of = edge_src // SB
    grp_of = bin_of // bins_per_group
    order = np.lexsort((bin_of, blk_of, grp_of))
    s_bin, s_blk = bin_of[order], blk_of[order]
    cell_key = (grp_of[order] * num_blocks + s_blk) * num_bins + s_bin
    uniq, cell_start, cell_cnt = np.unique(
        cell_key, return_index=True, return_counts=True)
    ncell = len(uniq)
    cell_g = uniq // (num_bins * num_blocks)
    cell_blk = (uniq // num_bins) % num_blocks
    cell_gbin = uniq % num_bins
    cell_lbin = cell_gbin - cell_g * bins_per_group
    bin_idx = cell_g * bins_per_group + cell_lbin
    gb_key = uniq // num_bins
    gb_uniq, gb_inv = np.unique(gb_key, return_inverse=True)
    gb_g = gb_uniq // num_blocks

    if geom.flat:
        U = geom.unit_rows                              # noqa: N806
        UC, U2 = CH // U, CH2 // U                      # noqa: N806
        cell_units = -(-cell_cnt // U)
        cell_cap = cell_units * U
        dense_bin_units = np.zeros(G * bins_per_group, np.int64)
        np.add.at(dense_bin_units, bin_idx, cell_units)
        dense_bin_chunks = np.maximum(-(-dense_bin_units // U2), 1)
        C2 = int(max(int(dense_bin_chunks.reshape(                 # noqa
            G, bins_per_group).sum(1).max(initial=0)), 1))
        bin_g = np.repeat(np.arange(G), bins_per_group)
        bin_chunk_base = _prefix_within_runs(dense_bin_chunks, bin_g)
        bo = np.argsort(bin_idx, kind="stable")
        cell_off_in_bin = np.zeros(ncell, np.int64)
        cell_off_in_bin[bo] = _prefix_within_runs(cell_units[bo],
                                                  bin_idx[bo])
        cell_stg_unit = bin_chunk_base[bin_idx] * U2 + cell_off_in_bin

        gb_units = np.zeros(len(gb_uniq), np.int64)
        np.add.at(gb_units, gb_inv, cell_units)
        c1_per_g, segs = _flat_pack(gb_g, gb_units, UC, G, segments=True)
        C1 = int(_pad_to(max(int(c1_per_g.max(initial=0)), 1), 8))  # noqa
        seg_stream, seg_chunk, seg_pos, seg_take = segs.T
        seg_g = gb_g[seg_stream]
        seg_blk = gb_uniq[seg_stream] % num_blocks
        p1_blk = np.zeros((G, C1), np.int64)
        opens = seg_pos == 0
        p1_blk[seg_g[opens], seg_chunk[opens]] = seg_blk[opens]

        total_units = int(cell_units.sum())
        cell_unit_base = np.cumsum(cell_units) - cell_units
        seg_start = np.cumsum(seg_take) - seg_take
        in_seg = np.arange(total_units) - np.repeat(seg_start, seg_take)
        unit_chunk = np.repeat(seg_chunk, seg_take)
        unit_pos = np.repeat(seg_pos, seg_take) + in_seg

        cell_ptr = np.concatenate([[0], np.cumsum(cell_cap)])
        tot = int(cell_ptr[-1])
        rc = np.repeat(np.arange(ncell), cell_cap)
        ri = np.arange(tot) - np.repeat(cell_ptr[:-1], cell_cap)
        uid = cell_unit_base[rc] + ri // U
        row_p1 = unit_chunk[uid] * CH + unit_pos[uid] * U + ri % U
        row_stg = cell_stg_unit[rc] * U + ri
        row_sec = SB * (p1_blk[cell_g[rc], unit_chunk[uid]]
                        != cell_blk[rc]).astype(np.int64)
        pad_srcl = -1
    else:
        NSLOT, SLOT2 = geom.nslot, geom.slot2           # noqa: N806
        cell_slots = -(-cell_cnt // SLOT)
        cell_cap = cell_slots * SLOT
        gb_slots = np.zeros(len(gb_uniq), np.int64)
        np.add.at(gb_slots, gb_inv, cell_slots)
        gb_chunks = -(-gb_slots // NSLOT)
        c1_per_g = np.zeros(G, np.int64)
        np.add.at(c1_per_g, gb_g, gb_chunks)
        C1 = int(_pad_to(max(int(c1_per_g.max(initial=0)), 1), 8))  # noqa
        gb_chunk_base = _prefix_within_runs(gb_chunks, gb_g)
        cell_p1_slot = _prefix_within_runs(cell_slots, gb_key)

        dense_bin_slots = np.zeros(G * bins_per_group, np.int64)
        np.add.at(dense_bin_slots, bin_idx, cell_slots)
        dense_bin_chunks = np.maximum(-(-dense_bin_slots // SLOT2), 1)
        C2 = int(max(int(dense_bin_chunks.reshape(                  # noqa
            G, bins_per_group).sum(1).max(initial=0)), 1))
        bin_g = np.repeat(np.arange(G), bins_per_group)
        bin_chunk_base = _prefix_within_runs(dense_bin_chunks, bin_g)
        bo = np.argsort(bin_idx, kind="stable")
        cell_off_in_bin = np.zeros(ncell, np.int64)
        cell_off_in_bin[bo] = _prefix_within_runs(cell_slots[bo],
                                                  bin_idx[bo])
        cell_stg_slot = bin_chunk_base[bin_idx] * SLOT2 + cell_off_in_bin

        cell_ptr = np.concatenate([[0], np.cumsum(cell_cap)])
        tot = int(cell_ptr[-1])
        rc = np.repeat(np.arange(ncell), cell_cap)
        ri = np.arange(tot) - np.repeat(cell_ptr[:-1], cell_cap)
        base_p1 = gb_chunk_base[gb_inv] * CH + cell_p1_slot * SLOT
        row_p1 = base_p1[rc] + ri
        row_stg = cell_stg_slot[rc] * SLOT + ri
        row_sec = np.zeros(tot, np.int64)
        pad_srcl = 0

    del cell_start
    return CellLayout(
        num_rows=num_rows, table_rows=table_rows,
        bins_per_group=bins_per_group, geom=geom, G=G, C1=C1, C2=C2,
        num_bins=num_bins, num_blocks=num_blocks,
        cell_blk=cell_blk, cell_bin=cell_gbin,
        cell_cap=cell_cap.astype(np.int64), cell_ptr=cell_ptr,
        row_p1=row_p1, row_stg=row_stg, row_sec=row_sec,
        pad_srcl=pad_srcl)


def empty_cell_arrays(layout: CellLayout):
    """Pad-initialized host copies of the two content arrays — what the
    builders start from before writing any edge (slot p1 rows 0, flat
    -1; staging rows RB = phase-2 masked)."""
    p1 = np.full((layout.G, layout.C1 * layout.geom.ch),
                 layout.pad_srcl, np.int32)
    p2 = np.full((layout.G, layout.C2 * layout.geom.ch2),
                 layout.geom.rb, np.int32)
    return p1, p2


def patch_plan_cells(layout: CellLayout, p1_srcl: np.ndarray,
                     p2_dstl: np.ndarray, ci: int,
                     src: np.ndarray, dst: np.ndarray) -> None:
    """Rewrite ONE cell of the host content arrays in place: the cell's
    live edges (in global-order; values must land in this cell) occupy
    its first len(src) rows, the rest revert to pad values.  Raises
    CellOverflowError when the edges exceed the cell's build-time
    capacity — the escalation ladder's trigger, never a partial write."""
    lo, hi = int(layout.cell_ptr[ci]), int(layout.cell_ptr[ci + 1])
    cap = hi - lo
    n = len(src)
    if n > cap:
        raise CellOverflowError(
            f"cell {ci} (blk={int(layout.cell_blk[ci])}, "
            f"bin={int(layout.cell_bin[ci])}): {n} edges exceed the "
            f"build-time capacity of {cap} rows")
    g = int(layout.cell_bin[ci]) // layout.bins_per_group
    blk = int(layout.cell_blk[ci])
    bn = int(layout.cell_bin[ci])
    p1v = np.full(cap, layout.pad_srcl, np.int32)
    p2v = np.full(cap, layout.geom.rb, np.int32)
    if n:
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        p1v[:n] = (src - blk * layout.geom.sb
                   + layout.row_sec[lo:lo + n]).astype(np.int32)
        p2v[:n] = (dst - bn * layout.geom.rb).astype(np.int32)
    p1_srcl[g, layout.row_p1[lo:hi]] = p1v
    p2_dstl[g, layout.row_stg[lo:hi]] = p2v
