"""Schema check for BENCH_SERVE.json (written by tools/serve_bench.py).

  python tools/perf_ledger.py --check    # preflight gate; exit 1 on a
                                         # malformed artifact

The artifact is produced outside the test suite, so a field rename in
the bench would otherwise surface months later; preflight pins the
schema instead.  (The driver's per-PR record is PERF_LEDGER.jsonl at the
repo root — written by the driver, read by every session, never by this
tool.)

Only the standard library is used — this must run in the barest
environment (the bench box, CI, a laptop reading a checkout).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Serving artifact schema.
_SERVE_REQUIRED = {"metric": str, "value": (int, float), "unit": str,
                   "p50_s": (int, float), "p99_s": (int, float),
                   "qps_offered": (int, float), "cold_start_s": (int, float),
                   "delta": dict,
                   # schema table, not a measurement record
                   "measured_at": str}  # roclint: allow(unledgered-prediction) — schema table, not a measurement record
# Nested dynamic-delta block (apply latency + escalation counters,
# measured fault-free by tools/serve_bench.py's volatile delta engine).
_SERVE_DELTA_REQUIRED = {"apply_p50_s": (int, float),
                         "apply_p99_s": (int, float),
                         "batches": int, "replans": int}
# Optional replicated-fleet block (--fleet N sweep against the router);
# validated only when present — a plain single-engine bench run stays a
# valid artifact without it.
_SERVE_FLEET_REQUIRED = {"replicas": int,
                         "p50_s": (int, float), "p99_s": (int, float),
                         "qps_offered": (int, float),
                         "shed": int, "shed_rate": (int, float),
                         "lag_p50_s": (int, float),
                         "lag_p99_s": (int, float),
                         "segments_shipped": int,
                         "scale_events": int}
SERVE_ARTIFACT = "BENCH_SERVE.json"


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def check(root: str = ROOT) -> list:
    """Schema violations in ``<root>/BENCH_SERVE.json`` (absent = none)."""
    errs = []
    serve = os.path.join(root, SERVE_ARTIFACT)
    if os.path.exists(serve):
        try:
            d = _load(serve)
            for field, typ in _SERVE_REQUIRED.items():
                if not isinstance(d.get(field), typ):
                    errs.append(f"{SERVE_ARTIFACT}: {field} missing or "
                                f"not {getattr(typ, '__name__', typ)}")
            for field, typ in _SERVE_DELTA_REQUIRED.items():
                if not isinstance((d.get("delta") or {}).get(field), typ):
                    errs.append(f"{SERVE_ARTIFACT}: delta.{field} missing "
                                f"or not {getattr(typ, '__name__', typ)}")
            if "fleet" in d:
                fl = d.get("fleet")
                if not isinstance(fl, dict):
                    errs.append(f"{SERVE_ARTIFACT}: fleet must be an "
                                f"object when present")
                else:
                    for field, typ in _SERVE_FLEET_REQUIRED.items():
                        if not isinstance(fl.get(field), typ):
                            errs.append(
                                f"{SERVE_ARTIFACT}: fleet.{field} missing "
                                f"or not {getattr(typ, '__name__', typ)}")
        except (OSError, ValueError) as e:
            errs.append(f"{SERVE_ARTIFACT}: unreadable ({e})")
    return errs


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--check", action="store_true",
                   help="schema-validate BENCH_SERVE.json (the default "
                        "and only mode; kept for the preflight spelling)")
    p.add_argument("--root", default=ROOT, help="repo root override")
    ns = p.parse_args(argv)
    errs = check(ns.root)
    for e in errs:
        print(f"perf_ledger: {e}", file=sys.stderr)
    if not errs:
        print(f"perf_ledger: {SERVE_ARTIFACT} ok")
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
