"""Crash-consistent dynamic-graph deltas for the serving engine.

Three pieces, one discipline (journal BEFORE memory, memory BEFORE
device, device swap under the plan lock):

  DeltaJournal   append-only write-ahead log: one CRC32-framed record
                 per applied batch, monotone sequence numbers, fsync
                 before acknowledge (fault.durable discipline).  Open
                 truncates a torn tail (a crash mid-append); CRC
                 mismatch with bytes after it, or a sequence gap, is
                 bit rot — typed DeltaJournalError, never a guess.
  _PlanPatcher   host-side mutable view of one BinnedPlan direction:
                 binned.plan_cell_layout re-derives the plan's per-cell
                 row geometry, per-cell member lists track live edges in
                 global order, binned.patch_plan_cells re-cuts ONLY the
                 cells a delta touches.  The patched arrays device_put
                 into the SAME padded shapes — same treedef, same jit
                 cache, zero retraces, zero plan rebuilds.
  DeltaManager   validation (out-of-range -> DeltaError, nothing
                 journaled), warn-once idempotence (re-add live /
                 retire dead = counted no-op), the escalation ladder
                 (cell overflow -> background full replan on the
                 mutated graph while the OLD plan keeps serving ->
                 atomic swap at a window boundary, swap + journal
                 checkpoint one crash-consistent unit), restart replay,
                 obs spans + counters + the delta-apply ledger pair +
                 the watchdog delta EWMA.

Chaos sites (roc_tpu/fault):
  delta.apply                 transient reject before the journal write
  delta.journal.append/.fsync transient I/O faults inside the retried
                              append (recovered by fault.retrying)
  delta.journal.kill_record   kill -9 before any record byte lands
  delta.journal.kill_fsync    kill -9 after the write, before fsync
  delta.journal.kill_ack      kill -9 after fsync, before the patch
  delta.replan.slow           stall the background replan (tests pin
                              that the old plan keeps serving)
  delta.swap.kill_pre/_post   kill -9 either side of the plan swap
  delta.ckpt.write/kill_tmp/kill_rename   the snapshot writer
                              (train.checkpoint.save_arrays)
  delta.ckpt.kill_snap        kill -9 between snapshot and truncate

Restart replays the journal over the frozen artifacts (or the latest
snapshot) through the SAME apply machinery and reaches the exact served
state — tests/test_delta.py pins every window above bit-for-bit.
"""

from __future__ import annotations

import dataclasses
import os
import struct
import threading
import warnings
import zlib
from typing import Optional

import jax.numpy as jnp
import numpy as np

from roc_tpu import fault, obs
from roc_tpu.analysis import witness as _witness
from roc_tpu.graph.csr import from_edges
from roc_tpu.ops.pallas import binned
from roc_tpu.train import checkpoint as _ckpt

__all__ = ["DeltaError", "DeltaJournalError", "DeltaJournal",
           "DeltaManager"]


class DeltaError(ValueError):
    """A rejected delta batch (malformed/out-of-range input) or a delta
    operation against an engine that cannot accept one.  Rejected
    batches are never journaled and never partially applied."""


class DeltaJournalError(RuntimeError):
    """A delta journal that cannot be trusted: bad magic/header, CRC
    bit rot with valid bytes after it, a sequence gap, or a snapshot
    newer than the journal's base.  (A torn TAIL is not an error — the
    crash window the WAL exists for — it is truncated on open.)"""


# -- journal framing --------------------------------------------------------
# header: magic, base_seq, crc32(magic + base_seq)   [atomic via rename]
# record: u32 len | payload | u32 crc32(payload)
#   payload: u64 seq, u32 n_add, u32 n_ret, then (n_add + n_ret) little-
#   endian int64 (src, dst) pairs, adds first.
_MAGIC = b"RDJ1"
_HDR = struct.Struct("<4sQI")
_LEN = struct.Struct("<I")
_REC = struct.Struct("<QII")


class DeltaJournal:
    """Append-only delta WAL (format above).  Not thread-safe on its
    own; DeltaManager serializes every call under its mutation lock."""

    def __init__(self, path: str):
        self.path = path
        self.base_seq = 0
        self.last_seq = 0
        self.records: list = []   # [(seq, add[n,2], ret[n,2])]
        self.torn_bytes = 0       # truncated on open (0 = clean)
        if os.path.exists(path):
            self._scan()
        else:
            self._write_header(0)
        self._f = open(path, "r+b")
        self._size = os.path.getsize(path)

    # -- open ---------------------------------------------------------------
    def _write_header(self, base_seq: int) -> None:
        tmp = self.path + ".tmp"
        hdr = _MAGIC + struct.pack("<Q", base_seq)
        hdr += _LEN.pack(zlib.crc32(hdr) & 0xFFFFFFFF)

        def _w():
            with open(tmp, "wb") as f:
                f.write(hdr)
        fault.retrying("delta.journal.create", _w)
        fault.fsync_replace(tmp, self.path)
        self.base_seq = self.last_seq = base_seq
        self.records = []

    def _scan(self) -> None:
        with open(self.path, "rb") as f:
            data = f.read()
        if len(data) < _HDR.size:
            raise DeltaJournalError(
                f"delta journal {self.path!r}: truncated header "
                f"({len(data)} bytes) — the header write is atomic, so "
                f"this is corruption, not a crash window")
        magic, base_seq, hcrc = _HDR.unpack(data[:_HDR.size])
        if magic != _MAGIC:
            raise DeltaJournalError(
                f"delta journal {self.path!r}: bad magic {magic!r}")
        if hcrc != zlib.crc32(data[:_HDR.size - 4]) & 0xFFFFFFFF:
            raise DeltaJournalError(
                f"delta journal {self.path!r}: header CRC mismatch "
                f"(bit rot)")
        self.base_seq = prev = base_seq
        off = good = _HDR.size
        n = len(data)
        while off < n:
            end = off + _LEN.size
            if end > n:
                break                                   # torn tail
            (rlen,) = _LEN.unpack(data[off:end])
            if end + rlen + _LEN.size > n:
                break                                   # torn tail
            rec = data[end:end + rlen]
            (rcrc,) = _LEN.unpack(data[end + rlen:end + rlen + _LEN.size])
            if zlib.crc32(rec) & 0xFFFFFFFF != rcrc:
                if end + rlen + _LEN.size == n:
                    break                               # torn final frame
                raise DeltaJournalError(
                    f"delta journal {self.path!r}: CRC mismatch at offset "
                    f"{off} with valid frames after it — bit rot, not a "
                    f"torn tail; the journal cannot be trusted")
            if rlen < _REC.size:
                raise DeltaJournalError(
                    f"delta journal {self.path!r}: undersized record at "
                    f"offset {off}")
            seq, na, nr = _REC.unpack(rec[:_REC.size])
            if rlen != _REC.size + (na + nr) * 16:
                raise DeltaJournalError(
                    f"delta journal {self.path!r}: record length disagrees "
                    f"with its edge counts at offset {off}")
            if seq != prev + 1:
                raise DeltaJournalError(
                    f"delta journal {self.path!r}: sequence gap "
                    f"({prev} -> {seq}) — records were lost")
            pay = np.frombuffer(rec, dtype="<i8", offset=_REC.size)
            add = pay[:2 * na].reshape(na, 2).astype(np.int64)
            ret = pay[2 * na:].reshape(nr, 2).astype(np.int64)
            self.records.append((seq, add, ret))
            prev = seq
            off = good = end + rlen + _LEN.size
        self.last_seq = prev
        if off < n or good < n:
            self.torn_bytes = n - good
            fault.emit_event("delta_journal_torn_tail", path=self.path,
                             dropped_bytes=int(self.torn_bytes))
            with open(self.path, "r+b") as f:
                f.truncate(good)
                os.fsync(f.fileno())

    # -- append -------------------------------------------------------------
    def append(self, seq: int, add: np.ndarray, ret: np.ndarray) -> None:
        """Durably frame one batch BEFORE any in-memory patch.  The three
        kill sites cover: nothing written / written-not-fsynced / fsynced-
        not-applied — restart replay handles each (tests pin all three)."""
        add = np.ascontiguousarray(add, dtype="<i8").reshape(-1, 2)
        ret = np.ascontiguousarray(ret, dtype="<i8").reshape(-1, 2)
        rec = _REC.pack(seq, len(add), len(ret)) \
            + add.tobytes() + ret.tobytes()
        frame = _LEN.pack(len(rec)) + rec \
            + _LEN.pack(zlib.crc32(rec) & 0xFFFFFFFF)
        off = self._size

        def _w():
            fault.point("delta.journal.kill_record")
            self._f.seek(off)
            self._f.truncate(off)
            fault.point("delta.journal.append")
            self._f.write(frame)
            self._f.flush()
            fault.point("delta.journal.kill_fsync")
            fault.point("delta.journal.fsync")
            os.fsync(self._f.fileno())
        fault.retrying("delta.journal.append", _w)
        fault.point("delta.journal.kill_ack")
        self._size = off + len(frame)
        self.last_seq = seq
        self.records.append((seq, add.astype(np.int64),
                             ret.astype(np.int64)))

    def truncate_to(self, seq: int) -> None:
        """Fold replayed history into a snapshot: atomically replace the
        journal with an empty one whose base_seq is ``seq``."""
        self._f.close()
        self._write_header(seq)
        self._f = open(self.path, "r+b")
        self._size = os.path.getsize(self.path)

    def records_after(self, seq: int):
        """Resident records with sequence number > ``seq``, in order —
        the replication-log read API (roc_tpu/fleet/replog.py seals
        these into shipped segments).  Records folded into a snapshot by
        ``truncate_to`` are gone from here by design: a follower that
        needs them catches up from the snapshot instead."""
        return [(s, a, r) for s, a, r in self.records if s > seq]

    def close(self) -> None:
        self._f.close()


# -- one plan direction -----------------------------------------------------

def _strip_fused(plan):
    """Drop the fused step lists: they inline copies of srcl/dstl, so a
    patched plan must run the two-pass path (run_binned takes it on
    f_meta=None).  Done at enable time, BEFORE
    the first trace (a treedef change after warmup would retrace)."""
    strip = {f: None for f in binned._PLAN_DATA_FIELDS
             if f.startswith("f_")}
    return dataclasses.replace(plan, **strip)


class _PlanPatcher:
    """Host-side mutable content arrays + per-cell member lists for one
    BinnedPlan direction.  ``swap`` orients edges: the bwd plan is built
    on (dst, src)."""

    def __init__(self, plan, base_src: np.ndarray, base_dst: np.ndarray,
                 swap: bool):
        self.swap = swap
        self.geom = plan.geom or binned._default_geom()
        self.layout = binned.plan_cell_layout(
            base_src, base_dst, plan.num_rows, plan.table_rows, self.geom)
        lay = self.layout
        G, C1 = plan.p1_blk.shape
        C2 = plan.p2_obi.shape[1]
        if (lay.G, lay.C1, lay.C2, lay.bins_per_group) != \
                (G, C1, C2, plan.bins_per_group):
            raise DeltaError(
                f"re-derived cell layout shape (G={lay.G}, C1={lay.C1}, "
                f"C2={lay.C2}, bpg={lay.bins_per_group}) disagrees with "
                f"the built plan (G={G}, C1={C1}, C2={C2}, "
                f"bpg={plan.bins_per_group}); refusing the patch path")
        # np.asarray on resident plan buffers is the enable-time host
        # copy, outside any traced code
        self.p1 = np.asarray(plan.p1_srcl).reshape(G, -1).astype(  # roclint: allow(host-sync) — enable-time host copy of resident plan buffers, untraced
            np.int32).copy()
        self.p2 = np.asarray(plan.p2_dstl).reshape(G, -1).astype(  # roclint: allow(host-sync) — enable-time host copy of resident plan buffers, untraced
            np.int32).copy()
        cells = lay.cells_of(base_src, base_dst)
        if (cells < 0).any():
            raise DeltaError("base edge outside every built cell "
                             "(layout drift); refusing the patch path")
        self.members = [[] for _ in range(lay.ncell)]
        for gi, ci in enumerate(cells):
            self.members[ci].append(gi)

    def orient(self, src, dst):
        return (dst, src) if self.swap else (src, dst)

    def stage(self, store_src, store_dst, add_gi, ret_gi):
        """Tentative member lists for one batch; None => escalate (an
        add lands outside every built cell or overflows its capacity).
        Commits nothing."""
        touched: dict = {}
        lay = self.layout
        for gi in add_gi:
            s, d = self.orient(store_src[gi], store_dst[gi])
            ci = int(lay.cells_of(np.asarray([s]), np.asarray([d]))[0])  # roclint: allow(host-sync) — host ints, no device array
            if ci < 0:
                return None
            lst = touched.get(ci)
            if lst is None:
                lst = touched[ci] = list(self.members[ci])
            lst.append(gi)
            if len(lst) > int(lay.cell_cap[ci]):
                return None
        for gi in ret_gi:
            s, d = self.orient(store_src[gi], store_dst[gi])
            ci = int(lay.cells_of(np.asarray([s]), np.asarray([d]))[0])  # roclint: allow(host-sync) — host ints, no device array
            assert ci >= 0, "retiring an edge no cell contains"
            lst = touched.get(ci)
            if lst is None:
                lst = touched[ci] = list(self.members[ci])
            lst.remove(gi)
        return touched

    def commit(self, store_src, store_dst, touched: dict) -> int:
        """Adopt staged member lists and re-cut exactly those cells."""
        for ci, lst in touched.items():
            self.members[ci] = lst
            s, d = self.orient(
                np.asarray([store_src[g] for g in lst], np.int64),  # roclint: allow(host-sync) — host-side cell regrouping over python lists, untraced
                np.asarray([store_dst[g] for g in lst], np.int64))  # roclint: allow(host-sync) — host edge store, no device array
            binned.patch_plan_cells(self.layout, self.p1, self.p2,
                                    ci, s, d)
        return len(touched)

    def render(self, store_src, store_dst):
        """Re-render both content arrays from the member lists alone —
        the verification oracle (enable + snapshot restore compare this
        against the actual arrays before trusting the patch path)."""
        p1, p2 = binned.empty_cell_arrays(self.layout)
        for ci, lst in enumerate(self.members):
            s, d = self.orient(
                np.asarray([store_src[g] for g in lst], np.int64),  # roclint: allow(host-sync) — host-side cell regrouping over python lists, untraced
                np.asarray([store_dst[g] for g in lst], np.int64))  # roclint: allow(host-sync) — host edge store, no device array
            binned.patch_plan_cells(self.layout, p1, p2, ci, s, d)
        return p1, p2

    def verify(self, store_src, store_dst, what: str) -> None:
        p1, p2 = self.render(store_src, store_dst)
        if not (np.array_equal(p1, self.p1)
                and np.array_equal(p2, self.p2)):
            raise DeltaError(
                f"{what}: plan content arrays disagree with the cell "
                f"layout re-derivation; refusing the patch path")

    def device_arrays(self):
        lay = self.layout
        return (jnp.asarray(self.p1.reshape(lay.G, lay.C1, lay.geom.ch)),
                jnp.asarray(self.p2.reshape(lay.G, lay.C2, lay.geom.ch2)))


class _ReplanTicket:
    """Join handle for one background replan."""

    def __init__(self):
        self._done = threading.Event()
        self.error: Optional[BaseException] = None

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    @property
    def done(self) -> bool:
        return self._done.is_set()


# -- the manager ------------------------------------------------------------

_COUNTER_KEYS = ("batches", "applied_adds", "applied_retires",
                 "noop_adds", "noop_retires", "rejected", "cells_patched",
                 "replans", "swaps", "checkpoints", "replayed")


class DeltaManager:
    """Owns delta state for one ServeEngine: journal, patchers, global
    live-edge store, escalation, snapshot/restore, counters.

    ``get_gdata``/``set_gdata`` read/install the engine's resident
    DenseGraphData; installs happen under ``plan_lock`` — the same lock
    the serve worker holds for a whole window, so queries never see a
    torn plan (the atomic-swap-at-a-window-boundary contract)."""

    def __init__(self, get_gdata, set_gdata, plan_lock, num_nodes: int,
                 journal_path: Optional[str] = None, watchdog=None,
                 ledger_key: Optional[str] = None, verbose: bool = False):
        self._get_gdata = get_gdata
        self._set_gdata = set_gdata
        self._plan_lock = plan_lock
        self.num_nodes = int(num_nodes)
        self.watchdog = watchdog
        self.verbose = verbose
        self._ledger_key = ledger_key or obs.ledger.content_key(
            model="delta", nodes=num_nodes)
        self._mu = _witness.trace("DeltaManager._mu", threading.Lock())
        self._ticket: Optional[_ReplanTicket] = None
        self._replan_thread: Optional[threading.Thread] = None
        self._broken: Optional[BaseException] = None
        self._closed = False
        self._replaying = False
        self._noop_warned = False
        self.counters = {k: 0 for k in _COUNTER_KEYS}

        gd = get_gdata()
        self._check_supported(gd)
        # frozen-artifact base: the edge list the resident plans were
        # built from (enable-time host copy, outside any traced code)
        base_src = np.asarray(gd.edge_src, np.int64)  # roclint: allow(host-sync) — enable-time host copy of the frozen edge list
        base_dst = np.asarray(gd.edge_dst, np.int64)  # roclint: allow(host-sync) — enable-time host copy of the frozen edge list
        in_deg = np.rint(np.asarray(gd.in_degree)).astype(np.int64)  # roclint: allow(host-sync) — enable-time host copy of the frozen edge list

        self.journal = DeltaJournal(journal_path) if journal_path else None
        self._snap_path = (journal_path + ".snapshot.npz"
                           if journal_path else None)

        snap = None
        if self._snap_path and os.path.exists(self._snap_path):
            try:
                snap = _ckpt.load_arrays(self._snap_path)
            except _ckpt.CheckpointError as e:
                raise DeltaJournalError(
                    f"delta snapshot {self._snap_path!r} failed "
                    f"verification: {e}") from e

        if snap is not None:
            self._restore_from_snapshot(gd, snap)
        else:
            fwd = _strip_fused(gd.plans.fwd)
            bwd = _strip_fused(gd.plans.bwd)
            self._fwd = _PlanPatcher(fwd, base_src, base_dst, swap=False)
            self._bwd = _PlanPatcher(bwd, base_src, base_dst, swap=True)
            self._adopt_base(base_src, base_dst, in_deg, rebuilt=False,
                             seq=self.journal.base_seq if self.journal
                             else 0)
            self._fwd.verify(self._src, self._dst, "enable(fwd)")
            self._bwd.verify(self._src, self._dst, "enable(bwd)")
            self._install(fwd, bwd)

        if self.journal is not None:
            base = self.journal.base_seq
            if base > self._seq:
                raise DeltaJournalError(
                    f"delta journal base_seq {base} is ahead of the "
                    f"snapshot seq {self._seq} — records were lost")
            self._replaying = True
            try:
                for seq, add, ret in self.journal.records:
                    if seq <= self._seq:
                        continue
                    self.apply(add, ret, wait_replan=True)
                    self.counters["replayed"] += 1
            finally:
                self._replaying = False

    # -- setup helpers ------------------------------------------------------
    @staticmethod
    def _check_supported(gd) -> None:
        if gd is None or gd.backend != "binned" or gd.plans is None:
            raise DeltaError(
                "dynamic deltas require the binned aggregation backend "
                "with resident plans (streamed and xla/matmul engines "
                "have no patchable cells)")
        if getattr(gd.plans, "mm", None) is not None:
            raise DeltaError(
                "dynamic deltas do not support hybrid (hub-split) plans: "
                "the matmul side has no cells to re-cut")
        if gd.gat_plans is not None:
            raise DeltaError(
                "dynamic deltas do not support plan-backend GAT "
                "attention (edge-list plans are not cell-addressable)")

    def _adopt_base(self, base_src, base_dst, in_deg, rebuilt: bool,
                    seq: int) -> None:
        """Reset the global live-edge store to a (plan-build) base list:
        every base edge alive, no appends."""
        self._base_src = base_src
        self._base_dst = base_dst
        self._src = base_src.tolist()
        self._dst = base_dst.tolist()
        self._alive = [True] * len(base_src)
        self._refs: dict = {}
        for gi, (s, d) in enumerate(zip(self._src, self._dst)):
            self._refs.setdefault((s, d), []).append(gi)
        self._in_deg = in_deg
        self._rebuilt = rebuilt
        self._seq = seq

    def _install(self, fwd_plan, bwd_plan) -> None:
        """device_put patched arrays into the SAME padded shapes and
        swap the resident gdata under the plan lock."""
        f1, f2 = self._fwd.device_arrays()
        b1, b2 = self._bwd.device_arrays()
        fwd = dataclasses.replace(fwd_plan, p1_srcl=f1, p2_dstl=f2)
        bwd = dataclasses.replace(bwd_plan, p1_srcl=b1, p2_dstl=b2)
        ind = jnp.asarray(self._in_deg, jnp.float32)
        with self._plan_lock:
            gd = self._get_gdata()
            plans = gd.plans._replace(fwd=fwd, bwd=bwd)
            self._set_gdata(dataclasses.replace(
                gd, plans=plans, in_degree=ind))
        self._fwd_plan = fwd
        self._bwd_plan = bwd

    def _restore_from_snapshot(self, gd, snap) -> None:
        arrays, extra = snap
        if extra.get("kind") != "delta-snapshot":
            raise DeltaJournalError(
                f"{self._snap_path!r} is not a delta snapshot")
        base_src = arrays["base_src"].astype(np.int64)
        base_dst = arrays["base_dst"].astype(np.int64)
        if extra["rebuilt"]:
            # reconstructing the EXACT geometry the snapshot's plans were
            # built with — consulting the tuned tier here could disagree
            # with the journaled state and break replay parity
            # roclint: allow(hand-rolled-geometry) — journaled geometry must replay bit-identically; the tuned tier could disagree
            gf = binned.Geometry(*extra["geom_fwd"])
            # roclint: allow(hand-rolled-geometry) — journaled geometry must replay bit-identically; the tuned tier could disagree
            gb = binned.Geometry(*extra["geom_bwd"])
            fwd = _strip_fused(binned.build_binned_plan(
                base_src, base_dst, gd.plans.fwd.num_rows,
                gd.plans.fwd.table_rows, geom=gf, tuned_ok=False))
            bwd = _strip_fused(binned.build_binned_plan(
                base_dst, base_src, gd.plans.bwd.num_rows,
                gd.plans.bwd.table_rows, geom=gb, tuned_ok=False))
        else:
            fwd = _strip_fused(gd.plans.fwd)
            bwd = _strip_fused(gd.plans.bwd)
        self._fwd = _PlanPatcher(fwd, base_src, base_dst, swap=False)
        self._bwd = _PlanPatcher(bwd, base_src, base_dst, swap=True)
        self._adopt_base(base_src, base_dst,
                         arrays["in_degree"].astype(np.int64),
                         rebuilt=bool(extra["rebuilt"]),
                         seq=int(extra["seq"]))
        # live list replaces the all-alive base membership
        live_src = arrays["live_src"].astype(np.int64)
        live_dst = arrays["live_dst"].astype(np.int64)
        self._src = live_src.tolist()
        self._dst = live_dst.tolist()
        self._alive = [True] * len(live_src)
        self._refs = {}
        for gi, (s, d) in enumerate(zip(self._src, self._dst)):
            self._refs.setdefault((s, d), []).append(gi)
        for p in (self._fwd, self._bwd):
            cells = p.layout.cells_of(*p.orient(live_src, live_dst))
            if (cells < 0).any():
                raise DeltaJournalError(
                    "snapshot live edge outside every built cell")
            p.members = [[] for _ in range(p.layout.ncell)]
            for gi, ci in enumerate(cells):
                p.members[ci].append(gi)
        self._fwd.p1 = arrays["fwd_p1"].astype(np.int32)
        self._fwd.p2 = arrays["fwd_p2"].astype(np.int32)
        self._bwd.p1 = arrays["bwd_p1"].astype(np.int32)
        self._bwd.p2 = arrays["bwd_p2"].astype(np.int32)
        self._fwd.verify(self._src, self._dst, "snapshot(fwd)")
        self._bwd.verify(self._src, self._dst, "snapshot(bwd)")
        for k, v in extra.get("counters", {}).items():
            if k in self.counters:
                self.counters[k] = int(v)
        self._install(fwd, bwd)

    # -- the one write path -------------------------------------------------
    def apply(self, add_edges=None, retire_edges=None,
              wait_replan: bool = False) -> dict:
        """Apply one delta batch.  Contract: validate-or-reject (nothing
        journaled on reject), journal BEFORE memory, patch in place with
        zero retraces / zero plan rebuilds, escalate to a background
        replan on cell overflow.  Returns a result dict (seq, mode,
        per-op counts, cells patched, replan ticket when escalated)."""
        with self._mu:
            if self._closed:
                raise DeltaError("delta manager is closed")
            if self._broken is not None:
                raise DeltaError(
                    "delta manager is in a crashed state (a previous "
                    "apply or replan died mid-flight); restart and "
                    "replay the journal") from self._broken
            if self._ticket is not None and not self._ticket.done:
                # a replan is in flight: the OLD plan serves queries,
                # but mutations serialize behind the swap
                # roclint: allow(lock-blocking) — mutations MUST serialize behind the in-flight replan under _mu; queries never take _mu, so serving stays live
                self._ticket.wait()
            if self._ticket is not None:
                if self._ticket.error is not None:
                    raise DeltaError(
                        "background replan failed; restart and replay "
                        "the journal") from self._ticket.error
                self._ticket = None
            add = self._validate(add_edges, "add_edges")
            ret = self._validate(retire_edges, "retire_edges")
            # roclint: allow(lock-blocking) — pre-WAL chaos site: a kill here unwinds through `with _mu` releasing it, and the journal has not advanced, so restart replays cleanly
            fault.point("delta.apply")   # transient chaos: reject pre-WAL
            eff_add, eff_ret, noop_add, noop_ret = self._classify(add, ret)
            self.counters["noop_adds"] += noop_add
            self.counters["noop_retires"] += noop_ret
            if (noop_add or noop_ret) and not self._noop_warned \
                    and not self._replaying:
                self._noop_warned = True
                warnings.warn(
                    "delta batch contained idempotent no-ops (re-adding "
                    "a live edge / retiring a dead one); counted in "
                    "delta counters, not an error (warning once)",
                    RuntimeWarning, stacklevel=3)
            if not eff_add and not eff_ret:
                self.counters["batches"] += 1
                return {"seq": self._seq, "mode": "noop",
                        "applied_adds": 0, "applied_retires": 0,
                        "noop_adds": noop_add, "noop_retires": noop_ret,
                        "cells_patched": 0}
            seq = self._seq + 1
            if self.journal is not None and not self._replaying:
                # roclint: allow(lock-blocking) — WAL-before-memory IS the commit point: the fsync'd append must complete under _mu or a racing apply could journal seq+1 before seq is durable
                self.journal.append(seq, add, ret)
            try:
                with obs.span("delta_apply", adds=len(eff_add),
                              retires=len(eff_ret)) as sp:
                    # roclint: allow(lock-blocking) — the in-memory commit matching the WAL record above; it reaches kill windows and checkpoint fsync by design, and a crash inside poisons the manager for replay
                    result = self._apply_effective(seq, eff_add, eff_ret)
            except BaseException as e:
                # past the WAL: a failure here leaves memory behind the
                # journal — poison the manager; restart replays exactly
                self._broken = e
                raise
            self.counters["batches"] += 1
            self.counters["applied_adds"] += len(eff_add)
            self.counters["applied_retires"] += len(eff_ret)
            result.update(noop_adds=noop_add, noop_retires=noop_ret,
                          applied_adds=len(eff_add),
                          applied_retires=len(eff_ret))
            if not self._replaying:
                self._note_obs(sp.dur_s, result)
            ticket = result.get("ticket")
        if ticket is not None and wait_replan:
            ticket.wait()
            if ticket.error is not None:
                raise DeltaError("replan failed") from ticket.error
        return result

    def _validate(self, edges, what: str) -> np.ndarray:
        if edges is None:
            return np.zeros((0, 2), np.int64)
        try:
            arr = np.asarray(edges)  # roclint: allow(host-sync) — caller batch ingress, host data
            if arr.size == 0:
                return np.zeros((0, 2), np.int64)
            if not np.issubdtype(arr.dtype, np.integer):
                raise ValueError(f"dtype {arr.dtype} is not integral")
            arr = arr.reshape(-1, 2).astype(np.int64)
        except (ValueError, TypeError) as e:
            self.counters["rejected"] += 1
            raise DeltaError(
                f"{what} must be an [n, 2] integer array of (src, dst) "
                f"node ids: {e}") from e
        if arr.size and (arr.min() < 0 or arr.max() >= self.num_nodes):
            self.counters["rejected"] += 1
            raise DeltaError(
                f"{what} node ids out of range [0, {self.num_nodes}): "
                f"min={arr.min()}, max={arr.max()} (batch rejected, "
                f"journal untouched)")
        return arr

    def _classify(self, add: np.ndarray, ret: np.ndarray):
        """Split a validated batch into effective ops and idempotent
        no-ops, honoring within-batch ordering (adds land before
        retires, duplicates collapse)."""
        eff_add, eff_ret = [], []
        noop_add = noop_ret = 0
        pend: dict = {}   # (s, d) -> net live delta within this batch
        for s, d in add.tolist():
            live = len(self._refs.get((s, d), ())) + pend.get((s, d), 0)
            if live > 0:
                noop_add += 1
            else:
                eff_add.append((s, d))
                pend[(s, d)] = pend.get((s, d), 0) + 1
        for s, d in ret.tolist():
            live = len(self._refs.get((s, d), ())) + pend.get((s, d), 0)
            if live <= 0:
                noop_ret += 1
            else:
                eff_ret.append((s, d))
                pend[(s, d)] = pend.get((s, d), 0) - 1
        return eff_add, eff_ret, noop_add, noop_ret

    def _apply_effective(self, seq: int, eff_add, eff_ret) -> dict:
        # allocate store slots for adds; resolve retire targets (the
        # most recently added live instance, which both patchers agree
        # on because member lists preserve global order)
        add_gi = []
        for s, d in eff_add:
            gi = len(self._src)
            self._src.append(s)
            self._dst.append(d)
            self._alive.append(True)
            self._refs.setdefault((s, d), []).append(gi)
            add_gi.append(gi)
        ret_gi = []
        try:
            for s, d in eff_ret:
                ret_gi.append(self._refs[(s, d)][-1])
            fwd_touch = self._fwd.stage(self._src, self._dst,
                                        add_gi, ret_gi)
            bwd_touch = self._bwd.stage(self._src, self._dst,
                                        add_gi, ret_gi)
        except BaseException:
            self._rollback_adds(add_gi, eff_add)
            raise
        if fwd_touch is None or bwd_touch is None:
            # capacity exhausted: the batch is journaled and lands via
            # the full replan; bookkeeping commits now, arrays at swap
            self._commit_store(seq, eff_add, eff_ret)
            ticket = self._escalate()
            return {"seq": seq, "mode": "replanning", "cells_patched": 0,
                    "ticket": ticket}
        self._commit_store(seq, eff_add, eff_ret)
        cells = self._fwd.commit(self._src, self._dst, fwd_touch)
        cells += self._bwd.commit(self._src, self._dst, bwd_touch)
        self.counters["cells_patched"] += cells
        self._install(self._fwd_plan, self._bwd_plan)
        return {"seq": seq, "mode": "applied", "cells_patched": cells}

    def _rollback_adds(self, add_gi, eff_add) -> None:
        for gi, (s, d) in zip(reversed(add_gi), reversed(eff_add)):
            self._refs[(s, d)].pop()
            if not self._refs[(s, d)]:
                del self._refs[(s, d)]
            self._src.pop()
            self._dst.pop()
            self._alive.pop()

    def _commit_store(self, seq: int, eff_add, eff_ret) -> None:
        # adds already landed in the store during staging; their degree
        # counts land here so a staging failure never half-applies
        for s, d in eff_add:
            self._in_deg[d] += 1
        for s, d in eff_ret:
            gi = self._refs[(s, d)].pop()
            if not self._refs[(s, d)]:
                del self._refs[(s, d)]
            self._alive[gi] = False
            self._in_deg[d] -= 1
        self._seq = seq

    def _live_edges(self):
        src = np.asarray([s for s, a in zip(self._src, self._alive) if a],  # roclint: allow(host-sync) — host edge store
                         np.int64)
        dst = np.asarray([d for d, a in zip(self._dst, self._alive) if a],  # roclint: allow(host-sync) — host edge store
                         np.int64)
        return src, dst

    # -- escalation ladder --------------------------------------------------
    def _escalate(self) -> _ReplanTicket:
        self.counters["replans"] += 1
        ticket = _ReplanTicket()
        self._ticket = ticket
        if self._replaying:
            self._replan_worker(ticket)
            if ticket.error is not None:
                raise DeltaError("replay replan failed") from ticket.error
        else:
            t = threading.Thread(target=self._replan_worker,
                                 args=(ticket,), daemon=True,
                                 name="roc-delta-replan")
            self._replan_thread = t
            t.start()
        return ticket

    def _replan_worker(self, ticket: _ReplanTicket) -> None:
        """Full replan on the mutated graph.  Runs OFF the serve path:
        the old plan keeps answering queries until the swap, which
        happens under the plan lock at a window boundary.  Swap +
        journal checkpoint are one crash-consistent unit — the kill
        windows either side replay exactly (tests pin both)."""
        try:
            fault.point("delta.replan.slow")
            live_src, live_dst = self._live_edges()
            csr = from_edges(self.num_nodes, live_src, live_dst)
            base_src = np.asarray(csr.col_idx, np.int64)  # roclint: allow(host-sync) — host CSR
            base_dst = np.asarray(csr.dst_idx, np.int64)  # roclint: allow(host-sync) — host CSR
            fwd = _strip_fused(binned.build_binned_plan(
                base_src, base_dst, self._fwd.layout.num_rows,
                self._fwd.layout.table_rows,
                geom=self._fwd.geom, tuned_ok=False))
            bwd = _strip_fused(binned.build_binned_plan(
                base_dst, base_src, self._bwd.layout.num_rows,
                self._bwd.layout.table_rows,
                geom=self._bwd.geom, tuned_ok=False))
            pf = _PlanPatcher(fwd, base_src, base_dst, swap=False)
            pb = _PlanPatcher(bwd, base_src, base_dst, swap=True)
            in_deg = self._in_deg
            ind = jnp.asarray(in_deg, jnp.float32)
            with self._plan_lock:
                # roclint: allow(lock-blocking) — the swap kill windows sit INSIDE the plan lock on purpose: the crash-consistency drill proves a kill at either edge of the atomic swap unwinds (releasing the lock via `with`) without serving a torn plan
                fault.point("delta.swap.kill_pre")
                gd = self._get_gdata()
                self._set_gdata(dataclasses.replace(
                    gd, plans=gd.plans._replace(fwd=fwd, bwd=bwd),
                    in_degree=ind))
                # roclint: allow(lock-blocking) — see kill_pre above: same sanctioned kill window, post-swap edge
                fault.point("delta.swap.kill_post")
            self._fwd, self._bwd = pf, pb
            self._fwd_plan, self._bwd_plan = fwd, bwd
            self._adopt_base(base_src, base_dst, in_deg, rebuilt=True,
                             seq=self._seq)
            self.counters["swaps"] += 1
            if not self._replaying:
                self.checkpoint()
        except BaseException as e:           # incl. SimulatedCrash
            ticket.error = e
            self._broken = e
        finally:
            ticket._done.set()

    # -- snapshot + truncate (one crash-consistent unit) --------------------
    def checkpoint(self) -> None:
        """Fold the journal into a verified snapshot: durable snapshot
        write (train.checkpoint.save_arrays — the PR 14 protocol), then
        journal truncate.  A kill between the two leaves snapshot(seq=S)
        + full journal; restart skips replay of records <= S."""
        if self.journal is None:
            return
        live_src, live_dst = self._live_edges()
        arrays = dict(
            base_src=self._base_src, base_dst=self._base_dst,
            live_src=live_src, live_dst=live_dst,
            fwd_p1=self._fwd.p1, fwd_p2=self._fwd.p2,
            bwd_p1=self._bwd.p1, bwd_p2=self._bwd.p2,
            in_degree=self._in_deg)
        extra = dict(kind="delta-snapshot", seq=int(self._seq),
                     rebuilt=bool(self._rebuilt),
                     geom_fwd=[int(v) for v in tuple(self._fwd.geom)],
                     geom_bwd=[int(v) for v in tuple(self._bwd.geom)],
                     counters={k: int(v) for k, v in self.counters.items()})
        _ckpt.save_arrays(self._snap_path, arrays, extra,
                          site="delta.ckpt")
        fault.point("delta.ckpt.kill_snap")
        self.journal.truncate_to(self._seq)
        self.counters["checkpoints"] += 1

    # -- observability ------------------------------------------------------
    def _note_obs(self, dur_s: float, result: dict) -> None:
        led = obs.get_ledger()
        cells = max(int(result.get("cells_patched", 0)), 1)
        # host-side patch cost model: per-batch fixed overhead + per-cell
        # re-cut + device_put of the two content arrays
        led.predict("delta-apply", self._ledger_key,
                    2e-4 + 2e-4 * cells, "s")
        led.measure("delta-apply", self._ledger_key, dur_s, "s")
        if self.watchdog is not None:
            alert = self.watchdog.observe_delta(self.counters["batches"],
                                                dur_s)
            if alert is not None and self.verbose:
                print(f"# watchdog: delta apply {alert['apply_s']*1e3:.2f} "
                      f"ms is {alert['ratio']:.2f}x its EWMA")

    @property
    def applied_seq(self) -> int:
        """Watermark: the highest delta sequence number whose effects are
        visible to queries (the fleet router reads this for its freshness
        floor; roc_tpu/fleet/replica.py exports it per replica)."""
        return self._seq

    @property
    def snapshot_path(self) -> Optional[str]:
        """Where `checkpoint()` writes the live-edge snapshot (None when
        running volatile).  The fleet snapshot protocol ships this file
        plus the truncated journal to a catching-up replica."""
        return self._snap_path

    def stats(self) -> dict:
        out = dict(self.counters)
        out["seq"] = self._seq
        out["rebuilt"] = self._rebuilt
        out["live_edges"] = int(sum(self._alive))
        out["journal"] = self.journal.path if self.journal else None
        return out

    # -- lifecycle ----------------------------------------------------------
    def close(self) -> None:
        """Finish-or-journal: wait out any in-flight apply (the mutation
        lock), join the background replan, close the journal.  Called by
        ServeEngine.close() BEFORE the queue drains."""
        with self._mu:
            if self._closed:
                return
            self._closed = True
            if self._ticket is not None and not self._ticket.done:
                # roclint: allow(lock-blocking) — close() is finish-or-journal: holding _mu while the last replan drains keeps a racing apply() from slipping a mutation into a closing manager
                self._ticket.wait()
            if self._replan_thread is not None:
                # the ticket resolves in the worker's finally; join past
                # it so process exit never tears down the runtime under
                # a thread still unwinding device code
                # roclint: allow(lock-blocking) — same close() barrier: the replan worker never takes _mu, so joining it under _mu cannot deadlock, and it must be dead before the journal closes
                self._replan_thread.join(timeout=60.0)
                self._replan_thread = None
            if self.journal is not None:
                self.journal.close()
