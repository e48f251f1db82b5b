#!/usr/bin/env python
"""roclint — static SPMD invariant checks for the roc_tpu tree.

    python tools/roclint.py [paths...]        AST lint (default: the tree)
    python tools/roclint.py --audit           collective budget audit
    python tools/roclint.py --update-budgets  regenerate budgets.json
    python tools/roclint.py --threads         lock-discipline analysis +
                                              exact-diff vs threads.json
    python tools/roclint.py --update-threads  regenerate threads.json
    python tools/roclint.py --list-waivers    inventory every roclint
                                              waiver; missing reasons fail

The lint pass is pure AST — no jax, no devices, milliseconds.  The audit
pass lowers the train/eval step of every config in the audit matrix
(roc_tpu.analysis.hlo_audit.audit_specs) and diffs collectives/dtypes/
shardings against roc_tpu/analysis/budgets.json; lowering needs no
accelerator, so both run in CPU-only CI.  The audit pins JAX to CPU with
8 forced host devices — the manifest is only meaningful under that
topology (same pin as tests/conftest.py).

Exit status: 0 clean, 1 findings/violations (lint, audit, waivers),
2 usage error, 3 thread-discipline violation (finding or threads.json
drift — the same hard-gate contract as the budget audit, on its own
code so preflight can name the failing gate).
"""

import argparse
import os
import sys

DEFAULT_PATHS = ["roc_tpu", "tools", "bench.py", "chip_smoke.py"]


def _pin_cpu_topology():
    """Must run before jax is imported anywhere in this process."""
    if "jax" in sys.modules:
        print("# roclint: jax already imported; cannot pin the 8-device "
              "CPU topology the budgets were recorded under",
              file=sys.stderr)
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def list_waivers(paths):
    """Every ``# roclint: allow(...)`` in the tree as
    ``(path, line, rules, reason)``.  The reason is whatever prose
    follows the closing paren on the same line — a waiver without one is
    unauditable and fails the inventory."""
    from roc_tpu.analysis.lint import _WAIVER_RE
    from roc_tpu.analysis.threads import _iter_py
    out = []
    for path in _iter_py(paths):
        with open(path, encoding="utf-8") as f:
            for ln, line in enumerate(f.read().splitlines(), 1):
                m = _WAIVER_RE.search(line)
                if not m:
                    continue
                if m.start() > 0 and line[m.start() - 1] == "`":
                    continue   # doc mention (``# roclint: allow(...)``)
                rules = ",".join(r.strip() for r in m.group(1).split(","))
                reason = line[m.end():].strip().lstrip("—-: ").strip()
                out.append((path, ln, rules, reason))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="roclint", description=__doc__)
    ap.add_argument("paths", nargs="*", help="files/dirs to lint "
                    "(default: roc_tpu tools bench.py chip_smoke.py)")
    ap.add_argument("--audit", action="store_true",
                    help="lower the audit matrix and diff against "
                    "budgets.json (skips the lint pass unless paths given)")
    ap.add_argument("--update-budgets", action="store_true",
                    help="regenerate roc_tpu/analysis/budgets.json from "
                    "the current tree")
    ap.add_argument("--threads", action="store_true",
                    help="lock-discipline analysis, exact-diffed against "
                    "roc_tpu/analysis/threads.json (exit 3 on violation)")
    ap.add_argument("--update-threads", action="store_true",
                    help="regenerate roc_tpu/analysis/threads.json from "
                    "the current tree")
    ap.add_argument("--list-waivers", action="store_true",
                    help="machine-readable inventory of every "
                    "`# roclint: allow(...)` waiver; exit 1 if any is "
                    "missing a reason")
    ap.add_argument("--no-lint", action="store_true",
                    help="skip the AST lint pass")
    args = ap.parse_args(argv)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    os.chdir(repo)
    sys.path.insert(0, repo)

    rc = 0
    alt_mode = (args.audit or args.update_budgets or args.threads
                or args.update_threads or args.list_waivers)
    do_lint = not args.no_lint and (bool(args.paths) or not alt_mode)
    if do_lint:
        from roc_tpu.analysis import lint, mosaic
        paths = args.paths or DEFAULT_PATHS
        findings = sorted(lint.lint_paths(paths) + mosaic.lint_paths(paths),
                          key=lambda f: (f.path, f.line))
        for f in findings:
            print(f)
        n = len(findings)
        print(f"# roclint: {n} finding(s)", file=sys.stderr)
        if n:
            rc = 1

    if args.audit or args.update_budgets:
        _pin_cpu_topology()
        from roc_tpu.analysis import hlo_audit

        def progress(key):
            print(f"#   lowering {key}", file=sys.stderr)

        if args.update_budgets:
            budgets = hlo_audit.run_audit(progress=progress)
            hlo_audit.save_budgets(budgets)
            print(f"# roclint: wrote {len(budgets)} budget entr(y/ies) to "
                  f"{hlo_audit.BUDGETS_PATH}", file=sys.stderr)
        else:
            viol = hlo_audit.audit_against_budgets(progress=progress)
            for v in viol:
                print(f"BUDGET VIOLATION: {v}")
            print(f"# roclint audit: {len(viol)} violation(s)",
                  file=sys.stderr)
            if viol:
                rc = 1

    if args.threads or args.update_threads:
        from roc_tpu.analysis import threads as _threads
        rep = _threads.analyze_paths(args.paths or ("roc_tpu",))
        if args.update_threads:
            _threads.save_baseline(rep)
            print(f"# roclint: wrote {_threads.BASELINE_PATH} "
                  f"({len(rep.edges)} edge(s), {len(rep.guarded_by)} "
                  f"guarded-by fact(s))", file=sys.stderr)
        else:
            for f in rep.findings:
                print(f)
            drift = _threads.diff_baseline(rep)
            for line in drift:
                print(f"THREADS VIOLATION: {line}")
            print(f"# roclint threads: {len(rep.findings)} finding(s), "
                  f"{len(drift)} drift line(s), {rep.waived} waived",
                  file=sys.stderr)
            if rep.findings or drift:
                rc = 3

    if args.list_waivers:
        rows = list_waivers(args.paths or DEFAULT_PATHS)
        missing = 0
        for path, ln, rules, reason in rows:
            if not reason:
                missing += 1
                print(f"{path}:{ln}\t{rules}\tMISSING REASON")
            else:
                print(f"{path}:{ln}\t{rules}\t{reason}")
        print(f"# roclint waivers: {len(rows)} waiver(s), "
              f"{missing} missing reason(s)", file=sys.stderr)
        if missing:
            rc = max(rc, 1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
