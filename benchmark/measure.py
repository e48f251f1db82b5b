"""Run a cell several times, as the driver does, and print the spread.

    python3 benchmark/measure.py --workload gcn-reddit.regular \\
        --seeds 1,2,3,4,5,6 --sets 2 --log-dir chiprun_out/regular

Each run is a new process of the manifest's command (this parent never
imports JAX: a parent that has touched JAX holds the chip).  Per set and
metric it prints the median and the spread the driver uses, the distance
between the quartiles over the median; a bound is about five times the
widest spread over the cells and never under 1 %.  The first run of the
first set compiles, so its `setup_s` is reported apart.  `--trace-seed N`
adds one traced run, whose `.xplane.pb` stays under the log directory.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values: list) -> float:
    """Distance between the quartiles over the median."""
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4, method="inclusive")
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else float("nan")


def run_once(command, workload, seed, seconds, trace, log_dir, extra):
    os.makedirs(log_dir, exist_ok=True)
    log = os.path.join(log_dir, f"{workload}.seed{seed}.trace{trace}.log")
    argv = command + ["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)] + extra
    t0 = time.time()
    with open(log, "w", encoding="utf-8") as f:
        rc = subprocess.run(argv, cwd=ROOT, stdout=f,
                            stderr=subprocess.STDOUT).returncode
    wall = time.time() - t0
    with open(log, encoding="utf-8") as f:
        lines = f.read().splitlines()
    result = None
    if rc == 0 and lines:
        try:
            result = json.loads(lines[-1])
        except ValueError:
            pass
    print(f"run {workload} seed {seed} trace {trace}: rc {rc}, {wall:.1f} s "
          f"wall; {json.dumps(result) if result else lines[-3:]}", flush=True)
    return result


def main(argv) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="1,2,3,4,5,6")
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace-seed", type=int, default=None)
    p.add_argument("--log-dir", default="chiprun_out/measure")
    p.add_argument("--manifest", default="BENCHMARK.json")
    p.add_argument("--rehearse-cpu", action="store_true")
    a = p.parse_args(argv)
    with open(os.path.join(ROOT, a.manifest), encoding="utf-8") as f:
        m = json.load(f)
    seconds = a.seconds if a.seconds is not None else m["run_seconds"]
    extra = ["--manifest", a.manifest] if a.manifest != "BENCHMARK.json" \
        else []
    if a.rehearse_cpu:
        extra.append("--rehearse-cpu")
    seeds = [int(s) for s in a.seeds.split(",") if s]
    first = True
    for k in range(a.sets):
        results = []
        for i, seed in enumerate(seeds):
            r = run_once(m["command"], a.workload, seed + 100 * k, seconds, 0,
                         a.log_dir, extra)
            if r is not None:
                r["_first"] = first
                results.append(r)
            first = False
        names = sorted({n for r in results for n in r["metrics"]})
        print(f"== {a.workload} set {k + 1}: {len(results)} of "
              f"{len(seeds)} runs gave a result; correct "
              f"{[r['correct'] for r in results]}")
        for n in names:
            vals = [r["metrics"][n]["value"] for r in results
                    if n in r["metrics"]
                    and not (n == "setup_s" and r["_first"])]
            if not vals:
                continue
            print(f"   {n:24s} median {statistics.median(vals):.6g}  "
                  f"spread {100 * spread(vals):.3f} %  min {min(vals):.6g}  "
                  f"max {max(vals):.6g}  n {len(vals)}")
        cold = [r["metrics"]["setup_s"]["value"] for r in results
                if r["_first"] and "setup_s" in r["metrics"]]
        if cold:
            print(f"   setup_s of the compiling run: {cold[0]:.3f}")
    if a.trace_seed is not None:
        run_once(m["command"], a.workload, a.trace_seed, seconds, 1,
                 a.log_dir, extra + ["--keep-trace", "--out", os.path.join(
                     a.log_dir, f"{a.workload}.traced")])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
