"""ServeEngine: frozen-params node-query serving over the plan cache.

The serving bet (ROADMAP "inference serving path"): the training hot
path IS the serving hot path.  The engine loads a checkpoint through
`train.frozen.load_frozen` (weights only, no optimizer arrays), builds
graph data through the SAME backend resolution as training, pulls binned
plans from the content-keyed disk cache — a warm cache means cold start
is a cache load plus ONE jit trace and ZERO plan rebuilds (pinned:
`cold_start_stats["plan_builds"]` diffs the builder's process counter) —
and then answers node-level queries by running the existing
binned forward exactly as eval does, gathering the queried
rows in-graph.  No kernel changes; that is the point.

Shape discipline: query batches are bucketed to a power-of-two ladder
capped at ``-serve-batch`` and padded to the bucket, so an arbitrary
request stream compiles at most ``len(buckets)`` serve_step variants and
the RetraceGuard can assert zero retraces after `warmup()`
(tests/test_serve.py pins a 100-request mixed-size stream).  Params stay
device-resident for the engine's lifetime; the per-call query-index
buffer is donated to the step on TPU (it is consumed once per dispatch).

Graphs that don't fit in-core serve through the streaming executor's
slot machinery (`config.stream`): each drained window sweeps the
host-resident shards through the frozen padded device slots — the same
rotation eval uses — and gathers the queried rows on the host.

Dynamic-graph deltas (``delta_journal=`` at construction): edge
appends/retires between requests journal to a write-ahead log, re-cut
only the touched binned cells host-side, and device_put into the SAME
padded buffers — zero retraces, zero plan rebuilds; a restart replays
the journal to the exact served state.  Plan swaps (both the per-batch
patch install and the escalation ladder's full-replan swap) happen
under ``_plan_lock``, which the serve worker holds for a whole window —
queries never see a torn plan.  See roc_tpu/serve/delta.py and
docs/DESIGN.md §Dynamic deltas.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from roc_tpu import fault, obs
from roc_tpu.analysis import retrace as _retrace
from roc_tpu.analysis import witness as _witness
from roc_tpu.device import on_tpu
from roc_tpu.graph.datasets import Dataset
from roc_tpu.models.model import Model
from roc_tpu.serve.queue import MicrobatchQueue, ServeFuture
from roc_tpu.train.config import Config
from roc_tpu.train.frozen import FrozenBundle, load_frozen

# Feed the watchdog's serve-latency EWMA once per this many windows —
# p99 over a single window of a few requests is noise, not a tail.
_P99_FEED_WINDOWS = 8


def bucket_sizes(batch: int):
    """The padded-shape ladder: powers of two up to ``batch`` (inclusive,
    ``batch`` itself always last even when not a power of two)."""
    out, b = [], 1
    while b < batch:
        out.append(b)
        b *= 2
    out.append(int(batch))
    return out


class ServeEngine:
    """Microbatched node-query engine over frozen params + plan cache."""

    def __init__(self, config: Config, dataset: Dataset, model: Model,
                 checkpoint_path: Optional[str] = None,
                 watchdog=None, start_queue: bool = True,
                 delta_journal: Optional[str] = None):
        from roc_tpu.ops.pallas import binned as _B
        self.config = config
        self.dataset = dataset
        self.model = model
        self.watchdog = watchdog
        self.buckets = bucket_sizes(config.serve_batch)
        self._lat_buf: list = []
        self._p99_windows = 0
        # Serve worker holds this for a whole window; delta installs and
        # the replan swap take it — atomic swap at a window boundary.
        self._plan_lock = _witness.trace("ServeEngine._plan_lock",
                                         threading.RLock())
        self.deltas = None
        # The engine's own trace counter: note_trace("serve_step") fires
        # only while jax is tracing, so the guard's counts ARE the trace
        # count.  Never self-arms (tests arm their own); close() exits it.
        self._guard = _retrace.RetraceGuard(warmup=1 << 30,
                                            on_violation="record")
        self._guard.__enter__()
        builds0 = _B.plan_build_count()
        with obs.span("serve_cold_start") as sp:
            self.bundle: FrozenBundle = load_frozen(
                config, dataset, model, checkpoint_path)
            # Delta enable BEFORE the first trace: the manager strips the
            # fused step lists (a treedef change) and installs patched
            # plan arrays; doing it here keeps the jit cache warm for
            # every later patch (same shapes, same treedef).
            if delta_journal is not None:
                from roc_tpu.serve.delta import DeltaManager
                if self.bundle.stream_trainer is not None:
                    from roc_tpu.serve.delta import DeltaError
                    raise DeltaError(
                        "dynamic deltas require the in-core binned "
                        "engine; the streamed executor reshards from "
                        "host-resident edges instead")
                self.deltas = DeltaManager(
                    lambda: self.bundle.gdata, self._install_gdata,
                    self._plan_lock, self.bundle.num_nodes,
                    journal_path=delta_journal or None,
                    watchdog=watchdog, verbose=config.verbose)
            self._build_serve_step()
            # one trace on the smallest bucket proves the program compiles
            # before the first request lands; warmup() traces the rest
            if self.bundle.stream_trainer is None:
                self._serve_rows(np.zeros(1, np.int32))
        self.cold_start_stats = {
            "cold_start_s": round(sp.dur_s, 6),
            "plan_builds": _B.plan_build_count() - builds0,
            "traces": int(sum(self._guard.counts.values())),
            "buckets": list(self.buckets),
        }
        # Ledger pair: serving p50 predicted from the forward-only
        # roofline bound (one full-graph forward per window — the query
        # gather rides it for free), measured from observed request p50
        # at each watchdog feed.  `python -m roc_tpu.obs calibration`
        # then covers serving next to the training-side models.
        g = dataset.graph
        fl, nb = obs.roofline.forward_flops_bytes(
            model, g.num_nodes, g.num_edges, config.aggregate_precision)
        self._roofline_p50_s = obs.roofline.roofline_time(fl, nb)
        self._ledger_key = obs.ledger.content_key(
            model=config.model, nodes=g.num_nodes, edges=g.num_edges,
            precision=config.aggregate_precision, batch=config.serve_batch)
        obs.get_ledger().predict("serve-p50", self._ledger_key,
                                 self._roofline_p50_s, "s")
        self.queue = None
        if start_queue:
            self.queue = MicrobatchQueue(
                self._serve_rows, batch=config.serve_batch,
                wait_ms=config.serve_wait_ms, on_window=self._note_window,
                queue_max=config.serve_queue_max)

    def _install_gdata(self, gdata) -> None:
        """Swap the resident graph data (delta patch install / replan
        swap).  Caller holds ``_plan_lock``; FrozenBundle passes gdata
        as a jit arg per dispatch, so a same-treedef replacement hits
        the existing compiled program."""
        self.bundle.gdata = gdata

    # -- the jitted query step --------------------------------------------
    def _build_serve_step(self):
        if self.bundle.stream_trainer is not None:
            self._serve_step = None
            return
        from roc_tpu.train.driver import make_gctx
        model = self.model
        n = self.bundle.num_nodes
        # qidx is consumed once per dispatch — donate it where donation
        # is implemented (TPU); on CPU the hint would only warn.
        donate = (4,) if on_tpu() else ()

        @partial(jax.jit, donate_argnums=donate)
        def serve_step(params, x, gdata, valid, qidx):
            _retrace.note_trace("serve_step")
            logits = model.apply(params, x, make_gctx(gdata, n),
                                 train=False)
            del valid  # padding rows are sliced off after the sync
            return jnp.take(logits, qidx, axis=0)

        self._serve_step = serve_step

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _serve_rows(self, ids: np.ndarray) -> np.ndarray:
        """Serve one drained window: [k] node ids -> [k, C] logits.
        Chunks larger than the top bucket split across dispatches; each
        dispatch pays exactly one device round trip."""
        fault.point("serve.fn")   # chaos site: a window-level serve
        ids = ids.reshape(-1)     # failure resolves to its futures, the
        if ids.size == 0:         # worker survives (tests pin this)
            return np.zeros((0, self.dataset.num_classes), np.float32)
        nn = self.bundle.num_nodes
        if ids.min() < 0 or ids.max() >= nn:
            raise IndexError(f"query ids must be in [0, {nn})")
        with obs.span("serve_window", n=int(ids.size)) as sp, \
                self._plan_lock:
            if self.bundle.stream_trainer is not None:
                # out-of-core: one slot sweep per window, gather on host.
                # This is the window's ONE sanctioned batch-boundary sync.
                logits = self.bundle.predict_logits()
                out = np.asarray(logits)[ids]  # roclint: allow(host-sync) — the window's ONE sanctioned batch-boundary sync
            else:
                parts = []
                cap = self.buckets[-1]
                for lo in range(0, ids.size, cap):
                    chunk = ids[lo:lo + cap]
                    b = self.bucket_for(chunk.size)
                    qidx = np.zeros(b, np.int32)
                    qidx[:chunk.size] = chunk
                    res = self._serve_step(
                        self.bundle.params, self.bundle.x,
                        self.bundle.gdata, jnp.int32(chunk.size),
                        jnp.asarray(qidx))
                    # the window's ONE sanctioned batch-boundary sync:
                    # exactly one result fetch per dispatched chunk
                    res = np.asarray(res)  # roclint: allow(host-sync) — one result fetch per dispatched chunk — the sanctioned window sync
                    parts.append(res[:chunk.size])
                out = parts[0] if len(parts) == 1 else np.concatenate(parts)
        del sp
        return out

    # -- request API ------------------------------------------------------
    def submit(self, node_ids: Sequence[int],
               deadline_s: Optional[float] = None) -> ServeFuture:
        assert self.queue is not None, "engine built with start_queue=False"
        return self.queue.submit(node_ids, deadline_s=deadline_s)

    def query(self, node_ids: Sequence[int], timeout: float = 60.0):
        assert self.queue is not None, "engine built with start_queue=False"
        return self.queue.query(node_ids, timeout)

    def warmup(self):
        """Trace every bucket now, so the first real request stream can
        assert zero retraces (RetraceGuard) from its very first window."""
        if self.bundle.stream_trainer is not None:
            self.bundle.predict_logits()
            return
        for b in self.buckets:
            self._serve_rows(np.zeros(b, np.int32))

    # -- observability ----------------------------------------------------
    def _note_window(self, latencies):
        self._lat_buf.extend(latencies)
        self._p99_windows += 1
        if self._p99_windows < _P99_FEED_WINDOWS:
            return
        lats = sorted(self._lat_buf)
        p99 = lats[min(int(0.99 * (len(lats) - 1)), len(lats) - 1)]
        self._p99_windows = 0
        del self._lat_buf[:]
        led = obs.get_ledger()
        led.predict("serve-p50", self._ledger_key, self._roofline_p50_s, "s")
        led.measure("serve-p50", self._ledger_key, lats[len(lats) // 2], "s")
        if self.watchdog is None:
            return
        alert = self.watchdog.observe_serve(self.queue.windows, p99)
        if alert is not None and self.config.verbose:
            print(f"# watchdog: serve p99 {alert['p99_s'] * 1e3:.2f} ms is "
                  f"{alert['ratio']:.2f}x its EWMA "
                  f"({alert['ewma_s'] * 1e3:.2f} ms)")

    def stats(self) -> dict:
        q = self.queue
        out = {
            "cold_start": dict(self.cold_start_stats),
            "windows": q.windows if q else 0,
            "requests": q.served if q else 0,
            "traces": int(sum(self._guard.counts.values())),
        }
        if self.deltas is not None:
            out["deltas"] = self.deltas.stats()
        return out

    # -- dynamic deltas ---------------------------------------------------
    def apply_delta(self, add_edges=None, retire_edges=None,
                    wait_replan: bool = False) -> dict:
        """Apply one dynamic-graph delta batch.  CONTRACT:

        - ``add_edges`` / ``retire_edges`` are [n, 2] integer arrays of
          (src, dst) node ids.  Out-of-range ids or a malformed shape
          reject the WHOLE batch with :class:`~roc_tpu.serve.delta.
          DeltaError`; a rejected batch is never journaled and never
          partially applied.
        - Validated batches are framed into the write-ahead journal
          (CRC32, monotone seq, fsync) BEFORE any in-memory patch; a
          restart replays the journal over the frozen artifacts to the
          exact served state (requires ``delta_journal=<path>`` at
          construction — ``delta_journal=""`` runs volatile and loses
          deltas on restart, tests pin both behaviors).
        - The patch re-cuts ONLY the touched (block, bin) cells and
          device_puts into the SAME padded buffers: zero retraces, zero
          plan rebuilds (both test-pinned).  Re-adding a live edge or
          retiring a dead one is a counted no-op, warned once.
        - On cell-capacity exhaustion the batch escalates: a background
          full replan runs on the mutated graph while the OLD plan keeps
          serving, then swaps atomically at a window boundary; pass
          ``wait_replan=True`` to block until the swap lands.
        - Concurrent with queries: installs and swaps happen under the
          window-held plan lock.  Concurrent mutations serialize.

        Returns the manager's result dict (seq, mode "applied" /
        "noop" / "replanning", per-op counts, cells_patched).
        """
        if self.deltas is None:
            from roc_tpu.serve.delta import DeltaError
            raise DeltaError(
                "engine was built without delta support; construct with "
                "delta_journal=<path> (journaled) or delta_journal='' "
                "(volatile) — enabling after warmup would retrace")
        return self.deltas.apply(add_edges, retire_edges,
                                 wait_replan=wait_replan)

    def delta_stats(self) -> dict:
        return self.deltas.stats() if self.deltas is not None else {}

    def delta_seq(self) -> int:
        """Applied-delta watermark (0 without delta support) — the
        per-replica freshness signal the fleet router dispatches on."""
        return self.deltas.applied_seq if self.deltas is not None else 0

    def pending(self) -> int:
        """Requests queued but not yet drained — the engine's share of
        the router's least-loaded dispatch signal."""
        return self.queue.depth() if self.queue is not None else 0

    def checkpoint_deltas(self) -> None:
        """Fold the delta journal into a verified snapshot + truncate
        (one crash-consistent unit; see DeltaManager.checkpoint)."""
        if self.deltas is not None:
            self.deltas.checkpoint()

    # -- lifecycle --------------------------------------------------------
    def close(self):
        # Order matters (the close/in-flight-mutation race): first the
        # delta manager — an apply that already hit the journal finishes
        # its patch (finish-or-journal, never torn); then the queue
        # drains, resolving every pending future against the final plan;
        # the guard exits last.
        if self.deltas is not None:
            self.deltas.close()
        if self.queue is not None:
            self.queue.close()
        self._guard.__exit__(None, None, None)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
