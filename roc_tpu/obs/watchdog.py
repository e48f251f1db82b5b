"""Runtime perf watchdog: EWMA epoch-time regressions + shard stragglers.

The dynamic counterpart to the static analyzer's gates (roc_tpu/analysis):
PR 3 proves a program *can't* silently grow collectives or retraces, but
the round-5 8.5x forced-vs-auto anomaly (docs/PERF.md) was harness state —
byte-identical HLO, wildly different wall-clock — which only a runtime
detector can catch.  The watchdog keeps an EWMA of epoch wall time and
flags any epoch slower than ``ratio`` x the mean; on binned runs the EWMA
can be *seeded* from the committed kernel-budget predictions
(tools/kernel_budgets.json steps_total x the measured per-grid-step
overhead), so the very first epochs are already checked against what the
cost model says the kernel floor should be.

Per-shard stragglers: `observe_shards` flags any probe time above
``straggler_ratio`` x the shard median — the balancer feeds it the same
probe samples its cost model fits, so a straggler alert lands in the
telemetry JSONL next to the balance round that should fix it.

Alerts are plain dicts (JSONL-ready, same `{"type": ...}` envelope as
balance telemetry once emitted through the registry); the driver prints
them under -v and `verdict()` stamps the bench artifact.
"""

from __future__ import annotations

import json
import os
from typing import List, Optional

DEFAULT_RATIO = 2.0        # alert when epoch > ratio x EWMA
DEFAULT_ALPHA = 0.25       # EWMA smoothing (higher = adapts faster)
DEFAULT_WARMUP = 2         # unseeded: observe this many epochs first
                           # (epoch 0 carries compile time; never judge it)
STRAGGLER_RATIO = 2.0      # shard alert when t > ratio x median shard time
# Calibration drift: alert when a cost model's measured/predicted ratio
# EWMA leaves this band.  Wide on purpose — the analytic models are
# order-of-magnitude instruments (the step-count models sit at exactly
# 1.0; the time models carry TPU-fit constants) and the alert exists for
# "the model stopped describing reality", not for 20% noise.
CALIBRATION_BAND = (0.5, 2.0)


class PerfWatchdog:
    """EWMA slow-epoch detector + per-shard straggler check."""

    def __init__(self, ratio: float = DEFAULT_RATIO,
                 alpha: float = DEFAULT_ALPHA,
                 warmup: int = DEFAULT_WARMUP,
                 seed_s: Optional[float] = None,
                 straggler_ratio: float = STRAGGLER_RATIO,
                 calibration_band=CALIBRATION_BAND):
        self.ratio = float(ratio)
        self.alpha = float(alpha)
        self.warmup = int(warmup)
        self.straggler_ratio = float(straggler_ratio)
        self.seeded = bool(seed_s and seed_s > 0)
        self.ewma: Optional[float] = float(seed_s) if self.seeded else None
        self.observed = 0
        self.alerts: List[dict] = []
        # stream-stall EWMA (fraction of epoch wall spent blocked on
        # host->device prefetch; stream executor runs only)
        self.stall_ewma: Optional[float] = None
        self.stall_observed = 0
        # spill-stall EWMA (fraction of epoch wall spent blocked on
        # boundary-store spill writes; -stream-spill runs only)
        self.spill_ewma: Optional[float] = None
        self.spill_observed = 0
        # serving p99-latency EWMA (serve engine runs only)
        self.serve_ewma: Optional[float] = None
        self.serve_observed = 0
        # delta-apply latency EWMA (dynamic-graph serving runs only)
        self.delta_ewma: Optional[float] = None
        self.delta_observed = 0
        # fleet replication-lag EWMA (roc_tpu/fleet router runs only)
        self.fleet_ewma: Optional[float] = None
        self.fleet_observed = 0
        # per-cost-model measured/predicted ratio EWMAs (ledger feed)
        self.calibration_band = (float(calibration_band[0]),
                                 float(calibration_band[1]))
        self.calib_ewma: dict = {}
        self.calib_observed: dict = {}
        # non-finite step guard feed (roc_tpu/fault): total skipped steps
        self.nonfinite_steps = 0

    # -- checkpoint round trip (roc_tpu/fault crash-consistent resume) ----
    _STATE_KEYS = ("ewma", "observed", "seeded", "stall_ewma",
                   "stall_observed", "spill_ewma", "spill_observed",
                   "serve_ewma", "serve_observed",
                   "delta_ewma", "delta_observed",
                   "fleet_ewma", "fleet_observed",
                   "calib_ewma", "calib_observed", "nonfinite_steps")

    def state_dict(self) -> dict:
        """JSON-able EWMA state for the checkpoint `extra` record, so a
        resumed run's watchdog is armed from epoch one instead of
        re-warming (and judging post-resume epochs against nothing)."""
        return {k: getattr(self, k) for k in self._STATE_KEYS}

    def load_state(self, state: dict) -> None:
        """Restore `state_dict` output; unknown/missing keys ignored (old
        checkpoints predate the watchdog extra)."""
        if not isinstance(state, dict):
            return
        for k in self._STATE_KEYS:
            if k in state:
                setattr(self, k, state[k])

    def observe_epoch(self, epoch: int, wall_s: float) -> Optional[dict]:
        """Feed one epoch's wall time; returns an alert dict or None."""
        wall_s = float(wall_s)
        armed = self.ewma is not None and \
            (self.seeded or self.observed >= self.warmup)
        alert = None
        if armed and wall_s > self.ratio * self.ewma:
            alert = {"kind": "slow-epoch", "epoch": int(epoch),
                     "wall_s": wall_s, "ewma_s": float(self.ewma),
                     "ratio": wall_s / self.ewma}
            self.alerts.append(alert)
            # Clamp the outlier's pull on the mean: one anomaly must not
            # poison the baseline it was measured against (or the NEXT
            # slow epoch would look fine by comparison).
            wall_s = self.ratio * self.ewma
        if self.observed >= 1 or self.seeded:
            # epoch 0 of an unseeded run carries jit compile time; start
            # the average at the first post-compile epoch
            self.ewma = wall_s if self.ewma is None else \
                self.alpha * wall_s + (1.0 - self.alpha) * self.ewma
        self.observed += 1
        return alert

    def observe_stream(self, epoch: int,
                       stall_frac: float) -> Optional[dict]:
        """Feed one streamed epoch's stall fraction (stream executor:
        stall_s / epoch wall).  Straggler-style alert when it exceeds
        ``ratio`` x its own EWMA — the signal that prefetch stopped hiding
        transfers (store contention, a slow host read, ring too shallow).
        Near-zero baselines are floored so a 0.001 -> 0.003 wiggle on a
        fully-overlapped run doesn't page anyone."""
        frac = float(stall_frac)
        armed = self.stall_ewma is not None and \
            self.stall_observed >= self.warmup
        baseline = max(self.stall_ewma or 0.0, 0.02)
        alert = None
        if armed and frac > self.ratio * baseline:
            alert = {"kind": "stream-stall", "epoch": int(epoch),
                     "stall_frac": frac, "ewma": float(self.stall_ewma),
                     "ratio": frac / baseline}
            self.alerts.append(alert)
            frac = self.ratio * baseline  # clamp, as observe_epoch does
        if self.stall_observed >= 1:
            # epoch 0 stalls on every first-touch transfer while the jit
            # compiles; never let it set the baseline
            self.stall_ewma = frac if self.stall_ewma is None else \
                self.alpha * frac + (1.0 - self.alpha) * self.stall_ewma
        self.stall_observed += 1
        return alert

    def observe_spill(self, epoch: int,
                      stall_frac: float) -> Optional[dict]:
        """Feed one spilled epoch's spill-stall fraction (stream executor
        under -stream-spill: boundary-store write seconds / epoch wall —
        the reads overlap on the prefetch ring, the writes block the
        consumer).  Alert when it exceeds ``ratio`` x its own EWMA: the
        signal that the spill device stopped keeping up (NVMe throttling,
        a full page cache flushing synchronously, a competing writer).
        Near-zero baselines floored and epoch 0 excluded, mirroring
        observe_stream."""
        frac = float(stall_frac)
        armed = self.spill_ewma is not None and \
            self.spill_observed >= self.warmup
        baseline = max(self.spill_ewma or 0.0, 0.02)
        alert = None
        if armed and frac > self.ratio * baseline:
            alert = {"kind": "spill-stall", "epoch": int(epoch),
                     "stall_frac": frac, "ewma": float(self.spill_ewma),
                     "ratio": frac / baseline}
            self.alerts.append(alert)
            frac = self.ratio * baseline  # clamp, as observe_epoch does
        if self.spill_observed >= 1:
            # epoch 0 pays first-touch page faults for every store while
            # the jit compiles; never let it set the baseline
            self.spill_ewma = frac if self.spill_ewma is None else \
                self.alpha * frac + (1.0 - self.alpha) * self.spill_ewma
        self.spill_observed += 1
        return alert

    def observe_serve(self, window: int, p99_s: float) -> Optional[dict]:
        """Feed one serving p99 sample (the engine aggregates a few
        windows of per-request latencies before each feed —
        serve/engine.py _note_window).  Alert when the p99 exceeds
        ``ratio`` x its own EWMA: queueing collapse or a slow device
        dispatch shows up in the tail long before the mean moves.
        Observation 0 carries warmup-trace and first-touch noise and
        never sets the baseline, mirroring observe_stream."""
        p99 = float(p99_s)
        armed = self.serve_ewma is not None and \
            self.serve_observed >= self.warmup
        alert = None
        if armed and p99 > self.ratio * self.serve_ewma:
            alert = {"kind": "serve-latency", "window": int(window),
                     "p99_s": p99, "ewma_s": float(self.serve_ewma),
                     "ratio": p99 / self.serve_ewma}
            self.alerts.append(alert)
            p99 = self.ratio * self.serve_ewma  # clamp, as observe_epoch
        if self.serve_observed >= 1:
            self.serve_ewma = p99 if self.serve_ewma is None else \
                self.alpha * p99 + (1.0 - self.alpha) * self.serve_ewma
        self.serve_observed += 1
        return alert

    def observe_delta(self, batch: int, apply_s: float) -> Optional[dict]:
        """Feed one delta-apply wall time (serve/delta.py feeds every
        applied batch; replay batches are excluded — restart replay is
        bulk work, not a serving-path sample).  Alert when an apply
        exceeds ``ratio`` x its own EWMA — a patch that suddenly re-cuts
        far more cells, or journal fsync latency, shows up here before
        it backs up the mutation path.  Observation 0 carries the
        first device_put/allocation noise and never sets the baseline,
        mirroring observe_serve."""
        t = float(apply_s)
        armed = self.delta_ewma is not None and \
            self.delta_observed >= self.warmup
        alert = None
        if armed and t > self.ratio * self.delta_ewma:
            alert = {"kind": "delta-apply", "batch": int(batch),
                     "apply_s": t, "ewma_s": float(self.delta_ewma),
                     "ratio": t / self.delta_ewma}
            self.alerts.append(alert)
            t = self.ratio * self.delta_ewma  # clamp, as observe_epoch
        if self.delta_observed >= 1:
            self.delta_ewma = t if self.delta_ewma is None else \
                self.alpha * t + (1.0 - self.alpha) * self.delta_ewma
        self.delta_observed += 1
        return alert

    def observe_fleet(self, event: int, lag_s: float,
                      shed_rate: float = 0.0) -> Optional[dict]:
        """Feed one fleet replication-lag sample (roc_tpu/fleet/router.py
        feeds the seal-to-applied wall per shipped segment, worst
        follower).  Alert when the lag exceeds ``ratio`` x its own EWMA
        — a follower falling behind shows up here before the freshness
        floor starts starving the dispatcher.  The alert carries the
        router's current shed rate so autoscale decisions in the JSONL
        are reconstructable.  Observation 0 carries first-segment
        device_put/trace noise and never sets the baseline, mirroring
        observe_serve."""
        lag = float(lag_s)
        armed = self.fleet_ewma is not None and \
            self.fleet_observed >= self.warmup
        alert = None
        if armed and lag > self.ratio * self.fleet_ewma:
            alert = {"kind": "fleet-lag", "event": int(event),
                     "lag_s": lag, "ewma_s": float(self.fleet_ewma),
                     "ratio": lag / self.fleet_ewma,
                     "shed_rate": float(shed_rate)}
            self.alerts.append(alert)
            lag = self.ratio * self.fleet_ewma  # clamp, as observe_epoch
        if self.fleet_observed >= 1:
            self.fleet_ewma = lag if self.fleet_ewma is None else \
                self.alpha * lag + (1.0 - self.alpha) * self.fleet_ewma
        self.fleet_observed += 1
        return alert

    def observe_nonfinite(self, epoch: int,
                          consecutive: int) -> Optional[dict]:
        """Feed one skipped (non-finite loss/grad) step from the in-graph
        guard (roc_tpu/fault).  Always alerts — a NaN step is never
        expected behavior — with the current consecutive-skip streak so
        the escalation ladder's state is visible in the JSONL."""
        self.nonfinite_steps += 1
        alert = {"kind": "nonfinite", "epoch": int(epoch),
                 "consecutive": int(consecutive),
                 "total": int(self.nonfinite_steps)}
        self.alerts.append(alert)
        return alert

    def observe_shards(self, epoch: int, times_s) -> List[dict]:
        """Feed per-shard probe times (balance/manager.py's samples);
        returns straggler alerts (possibly empty)."""
        times = [float(t) for t in times_s if t and t > 0]
        if len(times) < 2:
            return []
        med = sorted(times)[len(times) // 2]
        if med <= 0:
            return []
        alerts = []
        for part, t in enumerate(times):
            if t > self.straggler_ratio * med:
                alerts.append({"kind": "straggler", "epoch": int(epoch),
                               "part": part, "time_s": t,
                               "median_s": med, "ratio": t / med})
        self.alerts.extend(alerts)
        return alerts

    def observe_calibration(self, model: str, ratio: float,
                            epoch: int = -1) -> Optional[dict]:
        """Feed one joined (cost model, measured/predicted ratio) pair
        from the calibration ledger; returns a drift alert when the
        model's ratio EWMA leaves ``calibration_band``.  Per-model warmup
        mirrors observe_epoch: the first ``warmup`` pairs only build the
        EWMA (a model's very first joins may carry compile-epoch noise),
        later pairs are judged."""
        r = float(ratio)
        if r <= 0:
            return None    # a non-positive ratio is a broken pair, not drift
        model = str(model)
        ew = self.calib_ewma.get(model)
        self.calib_ewma[model] = r if ew is None else \
            self.alpha * r + (1.0 - self.alpha) * ew
        seen = self.calib_observed.get(model, 0) + 1
        self.calib_observed[model] = seen
        lo, hi = self.calibration_band
        cur = self.calib_ewma[model]
        if seen <= self.warmup or lo <= cur <= hi:
            return None
        alert = {"kind": "calibration-drift", "epoch": int(epoch),
                 "model": model, "ewma_ratio": float(cur),
                 "band_lo": lo, "band_hi": hi}
        self.alerts.append(alert)
        return alert

    def verdict(self) -> str:
        """"nonfinite" outranks everything (numerics beat perf), then
        "regressed" if any slow-epoch fired, then "straggler", then
        "stream-stall", then "spill-stall", then "serve-latency", then
        "delta-apply", then "fleet-lag", then "calibration-drift", "ok"
        otherwise — stamped into bench artifacts."""
        kinds = {a["kind"] for a in self.alerts}
        if "nonfinite" in kinds:
            return "nonfinite"
        if "slow-epoch" in kinds:
            return "regressed"
        if "straggler" in kinds:
            return "straggler"
        if "stream-stall" in kinds:
            return "stream-stall"
        if "spill-stall" in kinds:
            return "spill-stall"
        if "serve-latency" in kinds:
            return "serve-latency"
        if "delta-apply" in kinds:
            return "delta-apply"
        if "fleet-lag" in kinds:
            return "fleet-lag"
        if "calibration-drift" in kinds:
            return "calibration-drift"
        return "ok"


# -- budget seeding --------------------------------------------------------

_BUDGETS_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "tools",
    "kernel_budgets.json")


def seed_for_graph(num_rows: int, num_edges: int,
                   geometry: str = "default",
                   path: str = "") -> Optional[float]:
    """Predicted binned-kernel time (seconds per aggregation pass) for a
    graph shape pinned in tools/kernel_budgets.json: the committed
    schedule counts (padded rows, steps per phase) priced by the binned
    cost model itself (`_binned_cost_model`, re-fit from chip times in
    PR 24), so this seed and the geometry policy cannot disagree.  None
    when the shape isn't pinned — the EWMA then warms up from measured
    epochs instead.  This is a *floor* on the epoch (one aggregation
    pass, no linears), so seeding only arms the "order of magnitude off"
    detector early; it never replaces measured epochs, which take over
    after one EWMA step."""
    try:
        with open(path or _BUDGETS_PATH, encoding="utf-8") as f:
            budgets = json.load(f)
        from roc_tpu.ops.pallas.binned import (GEOM_PRESETS,
                                               _binned_cost_model,
                                               _default_geom)
        for entry in budgets.values():
            if entry.get("num_rows") == num_rows and \
                    entry.get("num_edges") == num_edges:
                geo = entry["geometries"].get(geometry)
                if geo:
                    geom = (_default_geom() if geometry == "default"
                            else GEOM_PRESETS[geometry])
                    return float(_binned_cost_model(
                        geo["padded_rows"], geom,
                        steps1=geo["steps_phase1"],
                        steps2=geo["steps_phase2"]))
    except (OSError, ValueError, KeyError, ImportError):
        # seeding is strictly best-effort: no budgets file / unpinned
        # shape degrades to measured-epoch warmup, the documented
        # fallback, not an error  # roclint: allow(silent-swallow) — documented best-effort seeding fallback, not an error path
        pass
    return None
