"""Static collective auditor: budget the communication a config compiles to.

ROC gets data-race freedom and placement correctness structurally from
Legion's region requirements; the XLA/SPMD port's only guard so far was
the *runtime* numerical checker (`parallel/check.py`).  This module adds
the static half: lower the jitted train/eval step for a config (no
execution — works on a CPU dev box for TPU-shaped programs), extract
every collective / transfer op and dtype widening from the StableHLO
text, and diff the result against a checked-in per-config budget
manifest (``budgets.json``).  A GSPMD-inserted resharding, an exchange
that grew an extra all_gather, or a silent f64 upcast then fails loudly
at lint time — with the offending op's source location — instead of
surfacing months later as an unattributable perf regression.

What is budgeted per step function (train and eval separately):
  * count and total result elements for each tracked op
    (``all_gather``, ``all_reduce``, ``reduce_scatter``, ``all_to_all``,
    ``collective_permute``, ``dynamic_slice``, ``dynamic_update_slice``);
    region-form ops that print their result type on the region's closing
    line (e.g. ``all_reduce``) are budgeted count-only (elems 0);
  * lines mentioning ``f64`` and ``convert``-to-f64 upcasts (normally 0 —
    the tree is fp32/bf16 by design);
  * the entry arguments' sharding signature, read off the
    ``sdy.sharding`` annotations the Shardy partitioner emits (jax 0.9
    lowers with it by default) — a dropped or altered placement (e.g. a
    replicated tensor that should be parts-sharded) changes this string
    before it changes any op count.

Budgets are keyed ``model/dataset/p<parts>/<configured-backend>/<exchange>``
and are *lowering*-level: regenerate with ``tools/roclint.py
--update-budgets`` whenever a deliberate change alters the compiled
communication pattern (the diff in budgets.json then documents exactly
what changed).  The audit matrix lowers on CPU with 8 forced host
devices — the manifest is only comparable under that topology, which is
what conftest.py and the roclint CLI both pin.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Dict, List, Optional

BUDGETS_PATH = os.path.join(os.path.dirname(__file__), "budgets.json")

TRACKED_OPS = (
    "all_gather", "all_reduce", "reduce_scatter", "all_to_all",
    "collective_permute", "dynamic_slice", "dynamic_update_slice",
)
_OP_RES = {op: re.compile(r"\bstablehlo\." + op + r"\b")
           for op in TRACKED_OPS}
_ARROW_TENSOR_RE = re.compile(r"->\s*tensor<([^>]*)>")
_CONVERT_F64_RE = re.compile(r"stablehlo\.convert\b.*->\s*tensor<[^>]*f64")
_SHARDING_RE = re.compile(r"sdy\.sharding = #sdy\.sharding<([^>]*)>")


def _tensor_elems(body: str) -> int:
    """Element count of a ``tensor<...>`` body like ``4x24x8xf32``."""
    n = 1
    for tok in body.split("x"):
        if tok.isdigit():
            n *= int(tok)
    return n


def _main_arg_shardings(txt: str) -> List[str]:
    """Per-entry-arg sdy.sharding bodies, e.g. ``@mesh, [{"parts"}, {}]``
    ("" = unannotated), in order."""
    i = txt.find("@main(")
    if i < 0:
        return []
    j = txt.find("\n", i)
    sig = txt[i:j if j > 0 else len(txt)]
    out = []
    for seg in re.split(r"%arg\d+", sig)[1:]:
        m = _SHARDING_RE.search(seg)
        out.append(m.group(1) if m else "")
    return out


def audit_hlo_text(txt: str) -> dict:
    """Audit one StableHLO module (``Lowered.as_text()``) → budget dict."""
    ops: Dict[str, Dict[str, int]] = {}
    f64_lines = 0
    convert_f64 = 0
    for line in txt.splitlines():
        if "f64" in line:
            f64_lines += 1
            if _CONVERT_F64_RE.search(line):
                convert_f64 += 1
        for op, rx in _OP_RES.items():
            if rx.search(line):
                ent = ops.setdefault(op, {"count": 0, "elems": 0})
                ent["count"] += 1
                m = _ARROW_TENSOR_RE.search(line)
                if m:
                    ent["elems"] += _tensor_elems(m.group(1))
    return {
        "ops": ops,
        "f64_lines": f64_lines,
        "convert_f64": convert_f64,
        "arg_shardings": _main_arg_shardings(txt),
    }


def audit_lowered(lowered) -> dict:
    return audit_hlo_text(lowered.as_text())


def op_locations(lowered, op: str, limit: int = 3) -> List[str]:
    """Source locations of ``op`` in a lowered module (debug-info ASM)."""
    try:
        asm = lowered.compiler_ir().operation.get_asm(
            enable_debug_info=True, large_elements_limit=16)
    except Exception:
        return []
    rx = _OP_RES[op]
    locs: List[str] = []
    for line in asm.splitlines():
        if rx.search(line):
            m = re.search(r"loc\((.*)\)\s*$", line)
            locs.append(m.group(1) if m else line.strip()[:160])
            if len(locs) >= limit:
                break
    return locs


# -- whole-trainer audit ---------------------------------------------------

@dataclasses.dataclass
class AuditReport:
    """Audit of one built trainer: ``steps`` maps step name → budget dict;
    ``lowereds`` keeps the jax Lowered objects for source-location lookups
    (not serialized)."""
    key: Optional[str]
    steps: Dict[str, dict]
    lowereds: Dict[str, object] = dataclasses.field(default_factory=dict,
                                                    repr=False)

    def to_json(self) -> dict:
        return self.steps

    def summary(self) -> str:
        lines = [f"# audit {self.key or '<unkeyed>'}"]
        for name, st in sorted(self.steps.items()):
            opstr = ", ".join(
                f"{op}x{v['count']}({v['elems']})"
                for op, v in sorted(st["ops"].items())) or "no collectives"
            lines.append(f"#   {name}: {opstr}; f64_lines="
                         f"{st['f64_lines']} convert_f64={st['convert_f64']}")
        return "\n".join(lines)


def trainer_key(trainer) -> str:
    """Budget-manifest key for a built trainer (configured backend, not the
    resolved one, so CPU and TPU runs of the same flags share a key)."""
    cfg = trainer.config
    ds = cfg.dataset or (os.path.basename(cfg.filename)
                         if cfg.filename else "mem")
    if cfg.num_parts > 1:
        exch = "edge" if getattr(trainer, "_use_edge_shard", False) \
            else trainer._exchange_mode
    else:
        exch = "single"
    return (f"{cfg.model}/{ds}/p{cfg.num_parts}/"
            f"{cfg.aggregate_backend}/{exch}")


def lower_train_step(trainer):
    """Lower the trainer's jitted train step with its real arguments
    (lowering only — nothing runs)."""
    import jax
    import jax.numpy as jnp
    rng = jax.random.PRNGKey(0)
    alpha = jnp.float32(trainer.optimizer.alpha)
    return trainer._train_step.lower(
        trainer.params, trainer.opt_state, trainer.x, trainer.labels,
        trainer.mask, trainer.gdata, rng, alpha, jnp.float32(1.0))


def lower_steps(trainer) -> Dict[str, object]:
    """Lower the trainer's jitted train/eval steps with their real
    arguments.  Shared by the HLO audit below, the memory estimator's XLA
    cross-checks (roc_tpu/memory/estimator.py) and the trainer's
    device_scopes()."""
    lo_eval = trainer._eval_step.lower(
        trainer.params, trainer.x, trainer.labels, trainer.mask,
        trainer.gdata)
    return {"train": lower_train_step(trainer), "eval": lo_eval}


def audit_trainer(trainer, key: Optional[str] = None) -> AuditReport:
    """Lower the trainer's compiled train/eval steps with its real
    arguments and audit the StableHLO."""
    lowereds = lower_steps(trainer)
    return AuditReport(key=key or trainer_key(trainer),
                       steps={n: audit_lowered(lo)
                              for n, lo in lowereds.items()},
                       lowereds=lowereds)


def check_invariants(report: AuditReport) -> List[str]:
    """Budget-free invariants that hold for every config: no f64 anywhere
    (the tree is fp32/bf16 by design), so any ``convert``-to-f64 is a
    silent dtype widening XLA decided on its own."""
    viol = []
    for name, st in sorted(report.steps.items()):
        if st["convert_f64"]:
            viol.append(f"{report.key}/{name}: {st['convert_f64']} "
                        f"convert-to-f64 upcast(s) in the lowered program")
        elif st["f64_lines"]:
            viol.append(f"{report.key}/{name}: {st['f64_lines']} line(s) "
                        f"mention f64 in the lowered program")
    return viol


def compare_report(report: AuditReport, budget: dict) -> List[str]:
    """Diff a report against one manifest entry; [] = within budget.

    Exact-match semantics: collective counts and element totals, the f64
    counters, and the entry-arg sharding signature must all be identical.
    On a count mismatch the message carries the op's source locations from
    the debug-info ASM when available.
    """
    viol: List[str] = []
    for name in sorted(set(report.steps) | set(budget)):
        got = report.steps.get(name)
        want = budget.get(name)
        if got is None or want is None:
            viol.append(f"{report.key}/{name}: step "
                        f"{'missing from audit' if got is None else 'not in budget'}")
            continue
        for op in sorted(set(got["ops"]) | set(want["ops"])):
            g = got["ops"].get(op, {"count": 0, "elems": 0})
            w = want["ops"].get(op, {"count": 0, "elems": 0})
            if g != w:
                msg = (f"{report.key}/{name}: {op} count/elems "
                       f"{g['count']}/{g['elems']} != budget "
                       f"{w['count']}/{w['elems']}")
                lo = report.lowereds.get(name)
                if lo is not None and g["count"] > w["count"]:
                    locs = op_locations(lo, op)
                    if locs:
                        msg += f" (at {'; '.join(locs)})"
                viol.append(msg)
        for k in ("f64_lines", "convert_f64"):
            if got[k] != want.get(k, 0):
                viol.append(f"{report.key}/{name}: {k} {got[k]} != "
                            f"budget {want.get(k, 0)}")
        if got["arg_shardings"] != want.get("arg_shardings", []):
            ga, wa = got["arg_shardings"], want.get("arg_shardings", [])
            detail = []
            for i in range(max(len(ga), len(wa))):
                a = ga[i] if i < len(ga) else "<absent>"
                b = wa[i] if i < len(wa) else "<absent>"
                if a != b:
                    detail.append(f"arg{i}: {a or '<none>'} != "
                                  f"budget {b or '<none>'}")
            viol.append(f"{report.key}/{name}: entry-arg sharding "
                        f"signature changed (GSPMD resharding or dropped "
                        f"placement): {'; '.join(detail[:4])}")
    return viol


# -- the audit matrix ------------------------------------------------------

# Tiny deterministic SBM graph: big enough that every part keeps real halo
# traffic at 4 parts (96/4 = 24-node shards, avg degree 4), small enough
# that the full 24-config matrix lowers in well under a minute on CPU.
AUDIT_DATASET = dict(num_nodes=96, avg_degree=4.0, in_dim=8, num_classes=4,
                     n_train=48, n_val=24, n_test=24, seed=7)
AUDIT_LAYERS = [8, 8, 4]


@dataclasses.dataclass(frozen=True)
class AuditSpec:
    model: str
    parts: int
    backend: str     # configured -aggr-backend
    exchange: str    # halo | allgather | ring | single
    serve: bool = False  # audit the serving engine's bucketed query step
                         # instead of the trainer's train/eval steps


def audit_specs() -> List[AuditSpec]:
    """model × parts × backend × exchange matrix (ring rides matmul —
    spmd forces it; parts=1 has no exchange), plus serve rows: the
    serving engine's jitted query step at the smallest and largest
    padded buckets, so a compiled-program change on the serving path
    (an extra collective, a dtype widening, a gather blowup) diffs in
    budgets.json exactly like a training-step change would."""
    specs: List[AuditSpec] = []
    for model in ("gcn", "gat"):
        for backend in ("matmul", "binned"):
            specs.append(AuditSpec(model, 1, backend, "single"))
        for parts in (2, 4):
            for backend in ("matmul", "binned"):
                for exch in ("halo", "allgather"):
                    specs.append(AuditSpec(model, parts, backend, exch))
            specs.append(AuditSpec(model, parts, "matmul", "ring"))
        for backend in ("matmul", "binned"):
            specs.append(AuditSpec(model, 1, backend, "serve", serve=True))
    return specs


def spec_key(spec: AuditSpec) -> str:
    return (f"{spec.model}/roc-audit/p{spec.parts}/{spec.backend}/"
            f"{spec.exchange}")


def build_audit_trainer(spec: AuditSpec, *, exchange: Optional[str] = None):
    """Build (without training) the trainer for one matrix entry.
    ``exchange`` overrides the lowered exchange mode while keeping the
    spec's budget key — the seeded-mutation tests use this to audit an
    allgather program against the halo budget."""
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_model
    from roc_tpu.train.config import Config
    from roc_tpu.train.driver import make_trainer
    ds = datasets.synthetic("roc-audit", **AUDIT_DATASET)
    exch = exchange if exchange is not None else spec.exchange
    cfg = Config(dataset="roc-audit", layers=list(AUDIT_LAYERS),
                 num_epochs=1, model=spec.model, heads=2,
                 aggregate_backend=spec.backend, num_parts=spec.parts,
                 exchange=("" if exch == "single" else exch),
                 edge_shard="off", eval_every=10 ** 6, seed=3)
    model = build_model(cfg.model, cfg.layers, cfg.dropout_rate, cfg.aggr,
                        heads=cfg.heads)
    return make_trainer(cfg, ds, model)


def build_audit_engine(spec: AuditSpec):
    """Cold-start (queueless) the serving engine for one serve row."""
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_model
    from roc_tpu.serve.engine import ServeEngine
    from roc_tpu.train.config import Config
    ds = datasets.synthetic("roc-audit", **AUDIT_DATASET)
    cfg = Config(dataset="roc-audit", layers=list(AUDIT_LAYERS),
                 num_epochs=1, model=spec.model, heads=2,
                 aggregate_backend=spec.backend, edge_shard="off",
                 eval_every=10 ** 6, seed=3, serve_batch=8)
    model = build_model(cfg.model, cfg.layers, cfg.dropout_rate, cfg.aggr,
                        heads=cfg.heads)
    return ServeEngine(cfg, ds, model, start_queue=False)


def audit_serve_engine(spec: AuditSpec,
                       key: Optional[str] = None) -> AuditReport:
    """Lower the engine's serve_step at the bucket ladder's ends: the
    two programs bound the padded-shape set (middle buckets only vary
    the gather width between them)."""
    import jax.numpy as jnp
    import numpy as np
    eng = build_audit_engine(spec)
    try:
        lowereds = {}
        for b in (eng.buckets[0], eng.buckets[-1]):
            lowereds[f"serve_b{b}"] = eng._serve_step.lower(
                eng.bundle.params, eng.bundle.x, eng.bundle.gdata,
                jnp.int32(b), jnp.asarray(np.zeros(b, np.int32)))
        return AuditReport(key=key or spec_key(spec),
                           steps={n: audit_lowered(lo)
                                  for n, lo in lowereds.items()},
                           lowereds=lowereds)
    finally:
        eng.close()


def audit_spec(spec: AuditSpec, key: Optional[str] = None) -> AuditReport:
    """One matrix entry → report (trainer steps or serve buckets)."""
    if spec.serve:
        return audit_serve_engine(spec, key=key)
    return audit_trainer(build_audit_trainer(spec), key=key)


def run_audit(specs: Optional[List[AuditSpec]] = None,
              progress=None) -> Dict[str, dict]:
    """Lower + audit every matrix entry → {budget key: steps dict}."""
    out: Dict[str, dict] = {}
    for spec in specs or audit_specs():
        key = spec_key(spec)
        if progress:
            progress(key)
        out[key] = audit_spec(spec, key=key).to_json()
    return out


# -- manifest --------------------------------------------------------------

def load_budgets(path: str = BUDGETS_PATH) -> Dict[str, dict]:
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def save_budgets(budgets: Dict[str, dict], path: str = BUDGETS_PATH):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(budgets, f, indent=1, sort_keys=True)
        f.write("\n")


def audit_against_budgets(specs: Optional[List[AuditSpec]] = None,
                          path: str = BUDGETS_PATH,
                          progress=None) -> List[str]:
    """Run the matrix and diff every entry against the manifest."""
    budgets = load_budgets(path)
    if not budgets:
        return [f"no budget manifest at {path}; run "
                f"tools/roclint.py --update-budgets"]
    viol: List[str] = []
    for spec in specs or audit_specs():
        key = spec_key(spec)
        if progress:
            progress(key)
        report = audit_spec(spec, key=key)
        if key not in budgets:
            viol.append(f"{key}: not in budget manifest (run "
                        f"--update-budgets)")
            continue
        viol.extend(compare_report(report, budgets[key]))
        viol.extend(check_invariants(report))
    return viol
