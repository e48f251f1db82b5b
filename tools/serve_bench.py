#!/usr/bin/env python
"""Serving bench: p50/p99 latency at offered QPS + cold-start artifact.

Emits `BENCH_SERVE.json` (schema gated by `tools/perf_ledger.py --check`;
serving latency is never a training claim):

  {"metric": "serve_p50", "value": ..., "unit": "s",
   "p50_s": ..., "p99_s": ..., "qps_offered": ..., "qps_achieved": ...,
   "cold_start_s": ..., "plan_builds": ..., "platform": ...,
   "delta": {"apply_p50_s": ..., "apply_p99_s": ..., "batches": ...,
             "applied_adds": ..., "applied_retires": ..., "replans": ...},
   "fleet": {"replicas": ..., "p50_s": ..., "p99_s": ..., "shed": ...,
             "shed_rate": ..., "lag_p50_s": ..., "lag_p99_s": ...,
             "segments_shipped": ..., "scale_events": ...},   # --fleet N
   "measured_at": ...}

The cold start reported is the WARM-cache cold start (the serving
contract: cache load + one trace, zero plan rebuilds).  The first engine
build of a fresh checkout populates the plan cache; the bench then tears
it down and times a second build, which is the number a restarting
replica would see.  The load phase is open-loop (roc_tpu/serve/loadgen)
so overload shows up in the tail instead of throttling the offer rate.

  python tools/serve_bench.py                 # bench, write BENCH_SERVE.json
  python tools/serve_bench.py --fleet 3       # + replicated-fleet sweep:
                                              # open-loop QPS against the
                                              # fleet router, "fleet" block
                                              # in the artifact
  python tools/serve_bench.py --selftest      # tiny CPU run into a tmp
                                              # root, schema-validated via
                                              # perf_ledger.check (preflight)

The delta block times `apply_delta` on a SEPARATE volatile delta-enabled
engine (the serve-latency numbers stay pure static-graph; a delta-enabled
engine runs the unfused two-pass plan).  Chaos is never armed here —
bench numbers exclude fault legs, per the PR 14 convention.

The fleet block (``--fleet N`` / ROC_SERVE_BENCH_FLEET) stands up one
primary + N-1 followers on in-proc transports behind the FleetRouter and
repeats the open-loop sweep against the ROUTER, with delta churn pumped
through the replication log every few requests — so the numbers price
dispatch + sibling retry + replication on top of the single-engine
serve path: p50/p99 through the router, shed rate (typed FleetOverloaded
at submit, counted — never silent), replication lag p50/p99
(seal-to-applied, from the segment headers), and autoscale events.

Knobs (env, matching bench.py's style): ROC_SERVE_BENCH_DATASET,
ROC_SERVE_BENCH_REQUESTS, ROC_SERVE_BENCH_QPS, ROC_SERVE_BATCH,
ROC_SERVE_WAIT_MS, ROC_SERVE_BENCH_CKPT (optional checkpoint to serve),
ROC_SERVE_BENCH_DELTAS (delta batches to time, default 40),
ROC_SERVE_BENCH_FLEET (replica count for the fleet sweep; 0 = skip).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _env(name, default, cast):
    try:
        return cast(os.environ.get(name, default))
    except ValueError:
        raise SystemExit(f"{name} must be {cast.__name__}")


def run_bench(dataset: str, n_requests: int, qps: float,
              ckpt: str = "", fleet: int = 0) -> dict:
    """Build engine (twice — populate then warm-start), offer load,
    return the BENCH_SERVE payload."""
    import jax

    from roc_tpu.graph import datasets
    from roc_tpu.models import build_model
    from roc_tpu.serve import ServeEngine, run_load
    from roc_tpu.train.config import Config

    cfg = Config(dataset=dataset, layers=[], model="gcn")
    ds = datasets.get(dataset, seed=cfg.seed)
    cfg.layers = [ds.features.shape[1], 16, ds.num_classes]
    model = build_model(cfg.model, cfg.layers, cfg.dropout_rate, cfg.aggr,
                        heads=cfg.heads)

    # first build populates the content-keyed plan cache (and jit cache
    # for this process — so the warm timing below is generous on trace
    # time; plan_builds is the honest zero-rebuild pin)
    ServeEngine(cfg, ds, model, checkpoint_path=ckpt or None,
                start_queue=False).close()

    with ServeEngine(cfg, ds, model, checkpoint_path=ckpt or None) as eng:
        eng.warmup()
        stats = run_load(eng, n_requests=n_requests, qps=qps)
        cs = eng.cold_start_stats
        payload = {
            "metric": "serve_p50",
            "value": stats["p50_s"],
            "unit": "s",
            "p50_s": stats["p50_s"],
            "p99_s": stats["p99_s"],
            "mean_s": stats["mean_s"],
            "n_requests": stats["n"],
            "qps_offered": stats["qps_offered"],
            "qps_achieved": stats["qps_achieved"],
            "cold_start_s": cs["cold_start_s"],
            "plan_builds": cs["plan_builds"],
            "serve_batch": cfg.serve_batch,
            "serve_wait_ms": cfg.serve_wait_ms,
            "buckets": cs["buckets"],
            "platform": jax.default_backend(),
            # artifact timestamp, not a measurement record (the ledger
            # pairing lives in the engine); mirrors bench.py's waiver
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),  # roclint: allow(unledgered-prediction) — artifact timestamp, not a measurement record
        }
    payload["delta"] = _bench_deltas(cfg, ds, model, ckpt)
    if fleet >= 2:
        payload["fleet"] = _bench_fleet(cfg, ds, model, ckpt, fleet,
                                        n_requests, qps)
    return payload


def _bench_deltas(cfg, ds, model, ckpt: str) -> dict:
    """Time apply_delta on a volatile delta-enabled engine: mixed
    add/retire churn, p50/p99 of the per-batch apply wall."""
    import warnings

    import numpy as np

    from roc_tpu.serve import ServeEngine

    n_batches = _env("ROC_SERVE_BENCH_DELTAS", "40", int)
    rng = np.random.default_rng(17)
    n = ds.graph.num_nodes
    times = []
    # deltas exist only for the binned backend; pin it regardless of
    # what the serve phase's auto-resolution picked
    import dataclasses
    cfg = dataclasses.replace(cfg, aggregate_backend="binned")
    with ServeEngine(cfg, ds, model, checkpoint_path=ckpt or None,
                     start_queue=False, delta_journal="") as eng:
        eng.warmup()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            for _ in range(n_batches):
                adds = rng.integers(0, n, (2, 2))
                rets = None
                if rng.random() < 0.25:
                    k = int(rng.integers(0, len(eng.deltas._src)))
                    rets = np.asarray([[eng.deltas._src[k],
                                        eng.deltas._dst[k]]])
                # apply latency is the artifact being measured; spans
                # cannot time it (percentiles need the raw samples)
                t0 = time.perf_counter()  # roclint: allow(raw-timing) — apply-latency percentiles need the raw samples; spans cannot
                eng.apply_delta(adds, rets, wait_replan=True)
                times.append(time.perf_counter() - t0)  # roclint: allow(raw-timing) — apply-latency percentiles need the raw samples; spans cannot
        st = eng.delta_stats()
    lat = sorted(times)
    return {
        "apply_p50_s": lat[len(lat) // 2],
        "apply_p99_s": lat[min(int(0.99 * (len(lat) - 1)), len(lat) - 1)],
        "batches": int(st["batches"]),
        "applied_adds": int(st["applied_adds"]),
        "applied_retires": int(st["applied_retires"]),
        "noops": int(st["noop_adds"] + st["noop_retires"]),
        "cells_patched": int(st["cells_patched"]),
        "replans": int(st["replans"]),
    }


def _bench_fleet(cfg, ds, model, ckpt: str, n_replicas: int,
                 n_requests: int, qps: float) -> dict:
    """Open-loop sweep against the fleet router: primary + followers on
    in-proc transports, delta churn pumped mid-stream.  Shed and lag are
    first-class outputs, not failures."""
    import dataclasses
    import warnings

    import numpy as np

    from roc_tpu.fleet import FleetRouter, InProcTransport, Replica, \
        ReplicationLog
    from roc_tpu.obs.watchdog import PerfWatchdog
    from roc_tpu.serve.loadgen import percentile
    from roc_tpu.serve.queue import Overloaded

    assert n_replicas >= 2, "--fleet wants at least 2 replicas"
    cfg = dataclasses.replace(cfg, aggregate_backend="binned")
    tmp = tempfile.mkdtemp(prefix="roc_fleet_bench_")
    wd = PerfWatchdog()
    reps = [Replica(f"bench-{i}", cfg, ds, model, ckpt or None,
                    os.path.join(tmp, f"bench-{i}.wal"), watchdog=wd)
            for i in range(n_replicas)]
    replog = ReplicationLog(reps[0].engine)
    for rep in reps[1:]:
        rep.transport = replog.attach(InProcTransport())
    router = FleetRouter(reps[0], reps[1:], replog, freshness_floor=0,
                         max_retries=1, watchdog=wd)
    rng = np.random.default_rng(23)
    n = ds.graph.num_nodes
    futures, lags = [], []
    shed = 0
    try:
        for rep in reps:
            rep.engine.warmup()
        # open-loop offer schedule (same anchor discipline as
        # serve/loadgen.run_load; raw clock for the same reason)
        t0 = time.perf_counter()  # roclint: allow(raw-timing) — open-loop offer schedule anchor, same discipline as loadgen
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            for i in range(n_requests):
                target = t0 + i / qps
                delay = target - time.perf_counter()  # roclint: allow(raw-timing) — open-loop offer schedule anchor, same discipline as loadgen
                if delay > 0:
                    time.sleep(delay)
                if i % 10 == 5:   # delta churn rides the query stream
                    router.apply_delta(rng.integers(0, n, (2, 2)), None)
                    live = [r for r in reps[1:] if r.alive]
                    lags.append(max((r.last_lag_s for r in live),
                                    default=0.0))
                k = int((1, 3, 8)[i % 3])
                try:
                    futures.append(router.submit(
                        rng.integers(0, n, size=k)))
                except Overloaded:
                    shed += 1   # typed backpressure is an output here
        for f in futures:
            f.result(120.0)
        wall = time.perf_counter() - t0  # roclint: allow(raw-timing) — open-loop offer schedule anchor, same discipline as loadgen
        lats = sorted(f.latency_s for f in futures)
        lags.sort()
        st = router.stats()
        return {
            "replicas": int(n_replicas),
            "n_requests": int(n_requests),
            "p50_s": round(percentile(lats, 0.50), 6),
            "p99_s": round(percentile(lats, 0.99), 6),
            "qps_offered": round(qps, 3),
            "qps_achieved": round(len(futures) / max(wall, 1e-9), 3),
            "shed": int(shed),
            "shed_rate": round(shed / max(n_requests, 1), 6),
            "sibling_retries": int(st["sibling_retries"]),
            "lag_p50_s": round(percentile(lags, 0.50), 6),
            "lag_p99_s": round(percentile(lags, 0.99), 6),
            "segments_shipped": int(st["replog"]["segments_shipped"]),
            "records_shipped": int(st["replog"]["records_shipped"]),
            "catch_ups": int(st["catch_ups"]),
            "scale_events": len(st["scale_events"]),
        }
    finally:
        router.close()


def write_artifact(payload: dict, root: str = ROOT) -> str:
    path = os.path.join(root, "BENCH_SERVE.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def selftest() -> int:
    """Tiny CPU end-to-end into a tmp root; the artifact must pass the
    perf-ledger schema gate byte-for-byte as a real run's would."""
    tmp = tempfile.mkdtemp(prefix="roc_serve_bench_")
    os.environ["ROC_PLAN_CACHE_DIR"] = os.path.join(tmp, "plan_cache")
    os.environ["ROC_PLAN_CACHE_MIN_EDGES"] = "0"
    os.environ.setdefault("ROC_SERVE_BATCH", "8")
    os.environ.setdefault("ROC_SERVE_WAIT_MS", "1.0")
    payload = run_bench("roc-audit", n_requests=40, qps=500.0, fleet=3)
    path = write_artifact(payload, root=tmp)
    assert payload["plan_builds"] == 0, (
        f"warm cold start rebuilt {payload['plan_builds']} plan(s)")
    assert payload["delta"]["batches"] > 0 and \
        payload["delta"]["apply_p50_s"] > 0, "delta block did not measure"
    fl = payload["fleet"]
    assert fl["replicas"] == 3 and fl["segments_shipped"] > 0 and \
        fl["lag_p99_s"] > 0, "fleet block did not measure replication"
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import perf_ledger
    errs = perf_ledger.check(root=tmp)
    assert not errs, f"BENCH_SERVE.json failed the schema gate: {errs}"
    dl = payload["delta"]
    print(f"# serve_bench selftest: OK — p50={payload['p50_s'] * 1e3:.2f}ms "
          f"p99={payload['p99_s'] * 1e3:.2f}ms at "
          f"{payload['qps_offered']} qps offered, warm cold start "
          f"{payload['cold_start_s']:.3f}s, plan_builds=0; delta apply "
          f"p50={dl['apply_p50_s'] * 1e3:.2f}ms "
          f"p99={dl['apply_p99_s'] * 1e3:.2f}ms over {dl['batches']} "
          f"batches, replans={dl['replans']}; fleet({fl['replicas']}) "
          f"p99={fl['p99_s'] * 1e3:.2f}ms shed_rate={fl['shed_rate']:.3f} "
          f"lag_p99={fl['lag_p99_s'] * 1e3:.2f}ms over "
          f"{fl['segments_shipped']} segments ({path})")
    return 0


def main(argv) -> int:
    if "--selftest" in argv:
        return selftest()
    fleet = _env("ROC_SERVE_BENCH_FLEET", "0", int)
    if "--fleet" in argv:
        i = argv.index("--fleet")
        if i + 1 >= len(argv):
            raise SystemExit("--fleet needs a replica count")
        fleet = int(argv[i + 1])
    payload = run_bench(
        _env("ROC_SERVE_BENCH_DATASET", "roc-audit", str),
        _env("ROC_SERVE_BENCH_REQUESTS", "200", int),
        _env("ROC_SERVE_BENCH_QPS", "100.0", float),
        ckpt=os.environ.get("ROC_SERVE_BENCH_CKPT", ""),
        fleet=fleet)
    path = write_artifact(payload)
    print(json.dumps(payload))
    print(f"# serve_bench: wrote {path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
