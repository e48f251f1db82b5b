"""Program names for device work: the `roc.` scopes and their readers.

The host's time has `obs.span`; this module is its device half.  Every
instruction of a compiled step carries, as XLA metadata, the path of
`jax.named_scope`s it was traced under (``op_name``).  The program sets
three kinds of scope, all through :func:`scope` and nowhere else:

  op    ``roc.<index>_<kind>``: the IR op at that index of ``model.ops``
        (``roc.07_gat``), set once, in ``Model.apply``'s loop.  Outside the
        model: ``roc.loss``, ``roc.metrics`` (ops/softmax.py), ``roc.adam``
        (optim/adam.py), ``roc.exchange`` with the parts ``down``, ``wire``,
        ``up``, ``roc.allreduce`` and ``roc.rng`` (parallel/spmd.py).
  pass  ``fwd`` or ``bwd``, a scope of its own inside the hand-written
        rules (ops/edge.py, the aggregation's custom VJPs); for what JAX
        differentiates, and for ``remat``, :func:`parse` reads it off the
        transforms JAX writes into the path.
  part  inside a pass scope, where an op is more than one scan or kernel:
        :data:`PARTS`.  A part is read only after an explicit pass (or an
        op of :data:`OP_PARTS`), so a primitive that happens to be called
        ``max`` is never taken for one.

The scopes are metadata: no HLO op, the same lowered text once debug info
is stripped, the same compile-cache key.  That has a trap: JAX's cache key
strips debug info too, so an executable LOADED from the persistent cache
carries the ``op_name``s of whoever compiled it first, possibly a checkout
without a single `roc.` scope.  A map of names therefore comes from
:func:`compile_uncached`; the instruction NAMES it yields are the cached
executable's too (same stripped module, same compiler).

Readers: :func:`parse` (the one function that reads an ``op_name``),
:func:`describe_module` over an optimized HLO module's text (the map of
its instructions, its mixed fusions), :func:`lowered_counts` over a
lowered step's StableHLO.
Like the tracer this module imports no JAX: ``jax.named_scope`` is looked
up by the first scope entered.
"""

from __future__ import annotations

import contextlib
import re
from typing import Optional, Tuple

PREFIX = "roc."
PASSES = ("fwd", "bwd", "remat")
# attention (ops/edge.py): score, max, norm, u, de, dq (gat), dedq (the
# two in one scan), su (gat, tconv: score and u in one scan), src, bcast,
# edge; binned aggregation (ops/pallas/binned.py): p1, p1_flat, p2, fused;
# matmul aggregation (ops/aggregate.py): mm
PARTS = frozenset({"score", "max", "norm", "u", "de", "dq", "dedq", "su",
                   "src", "bcast", "edge", "p1", "p1_flat", "p2", "fused",
                   "mm"})
# ops whose parts follow the op itself: JAX differentiates them, so no
# explicit pass stands between
OP_PARTS = {"roc.exchange": frozenset({"down", "wire", "up"})}

_named_scope = None


def scope(*names: str):
    """Context manager: the ``jax.named_scope``s ``names``, nested in
    order (``scope("bwd", "src")``).  Tracing-time only; the one door to
    ``jax.named_scope`` in the tree, so a test swaps it for a null context
    and compares the lowered text."""
    global _named_scope
    if _named_scope is None:
        from jax import named_scope
        _named_scope = named_scope
    if len(names) == 1:
        return _named_scope(names[0])
    return _nested(names)


@contextlib.contextmanager
def _nested(names):
    with contextlib.ExitStack() as stack:
        for name in names:
            stack.enter_context(_named_scope(name))
        yield


def op_scope(index: int, kind: str) -> str:
    """The scope name of the IR op at ``index`` of ``model.ops``."""
    return f"{PREFIX}{index:02d}_{kind}"


# a path component with the transforms JAX wrapped it in: transpose(jvp(x))
_WRAPPED = re.compile(r"^((?:[A-Za-z_]\w*\()*)([^()]*)\)*$")
_JAX_TRANSFORMS = {"jvp", "transpose", "vmap", "pmap", "remat", "checkpoint",
                   "custom_jvp", "custom_vjp"}


def _unwrap(token: str) -> Tuple[tuple, str]:
    """(transforms, name) of one path component; a component wrapped in
    anything else (``jit(f)``, ``pallas_call(k)``) is no scope's."""
    m = _WRAPPED.match(token)
    if m is None:
        return (), token
    wraps = tuple(w for w in m.group(1).split("(") if w)
    if any(w not in _JAX_TRANSFORMS for w in wraps):
        return (), token
    return wraps, m.group(2)


def parse(op_name: str) -> Tuple[Optional[str], str, Optional[str]]:
    """(op, pass, part) of an instruction's ``op_name``.

    ``op``: the innermost `roc.` scope on the path, None without one.
    ``pass``: "remat" under ``rematted_computation``; else the explicit
    ``fwd`` / ``bwd`` scope after the op; else "bwd" under a
    ``transpose(...)``, "fwd" otherwise.  ``part``: the first component of
    :data:`PARTS` after the explicit pass (of :data:`OP_PARTS` after such
    an op), None elsewhere."""
    tokens = [_unwrap(t) for t in op_name.split("/")]
    at = max((i for i, (_, name) in enumerate(tokens)
              if name.startswith(PREFIX)), default=None)
    transposed = any("transpose" in wraps for wraps, _ in tokens)
    rematted = any(name == "rematted_computation" for _, name in tokens)
    if at is None:
        return None, ("remat" if rematted else
                      "bwd" if transposed else "fwd"), None
    op = tokens[at][1]
    explicit, part = None, None
    allowed = OP_PARTS.get(op)
    for _, name in tokens[at + 1:]:
        if explicit is None and name in ("fwd", "bwd"):
            explicit, allowed = name, PARTS
        elif allowed is not None and name in allowed:
            part = name
            break
    pass_ = "remat" if rematted else explicit or (
        "bwd" if transposed else "fwd")
    return op, pass_, part


# -- the optimized HLO module's text ---------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


def _computations(hlo_text: str):
    """(computation name, [(instruction name, op_name or "", line)]) of
    every computation of an HLO module's text, in order."""
    name, rows = None, []
    for line in hlo_text.splitlines():
        if name is None:
            m = _COMPUTATION.match(line)
            if m and not line.startswith((" ", "HloModule")):
                name, rows = m.group(1), []
            continue
        if line.startswith("}"):
            yield name, rows
            name = None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            found = _OP_NAME.search(line)
            rows.append((m.group(1), found.group(1) if found else "", line))
    if name is not None:
        yield name, rows


def module_name(hlo_text: str) -> str:
    """``jit_train_step`` of ``HloModule jit_train_step, ...``: what the
    trace's module line calls the program."""
    m = _MODULE.match(hlo_text)
    return m.group(1) if m else ""


_CALLED = re.compile(
    r"\b(?:body|condition|to_apply|calls|true_computation|false_computation)"
    r"=%?([\w.\-]+)|\bbranch_computations=\{([^}]*)\}")


def _scopes_by_computation(hlo_text: str) -> tuple:
    """(fused, rows): ``fused`` the set of fused computations; ``rows``
    {computation: [(instruction name, (op, pass, part))]}.  An instruction
    the compiler made without metadata (a copy, a rewritten reduction)
    inside a `while`'s body, a called or a fused computation takes the
    scope of the instruction that runs the computation: what runs inside a
    scan belongs to the scan's op."""
    computations = list(_computations(hlo_text))
    caller: dict = {}       # computation -> (its caller's computation, row)
    fused = set()
    for comp, rows in computations:
        for i, (_, _, line) in enumerate(rows):
            for m in _CALLED.finditer(line):
                names = [m.group(1)] if m.group(1) else [
                    n.strip().lstrip("%") for n in m.group(2).split(",")]
                for callee in names:
                    caller.setdefault(callee, (comp, i))
                if m.group(0).startswith("calls=") and " fusion(" in line:
                    fused.update(names)
    parsed = {comp: [parse(op_name) for _, op_name, _ in rows]
              for comp, rows in computations}
    inherited: dict = {}

    def of_caller(comp: str):
        if comp not in inherited:
            inherited[comp] = None      # guards a cycle; HLO has none
            if comp in caller:
                parent, i = caller[comp]
                own = parsed[parent][i]
                inherited[comp] = own if own[0] else of_caller(parent)
        return inherited[comp]

    out = {}
    for comp, rows in computations:
        up = of_caller(comp)
        out[comp] = [(name, scope if scope[0] or up is None else up)
                     for (name, _, _), scope in zip(rows, parsed[comp])]
    return fused, out


def describe_module(hlo_text: str) -> dict:
    """One pass over an optimized HLO module's text
    (``compiled.as_text()``), the record a `-profile` run keeps of each
    program: ``module`` (its name, what a trace's module line calls the
    program); ``scopes``, {instruction name: (op, pass, part)} of every
    instruction of the entry computation, of every `while` body and
    condition and of whatever else runs as instructions of its own, a
    fusion by its own metadata and the instructions fused into it left out
    (they are no events of a trace); ``mixed_fusions``, the fusions whose
    fused instructions carry more than one op scope: what attribution at
    fusion grain mislays (the fusion's time goes to the op of its own
    metadata, its root's)."""
    fused, rows = _scopes_by_computation(hlo_text)
    return {
        "module": module_name(hlo_text),
        "scopes": {name: scope for comp, pairs in rows.items()
                   if comp not in fused for name, scope in pairs},
        "mixed_fusions": sum(
            len({scope[0] for _, scope in rows[comp]} - {None}) > 1
            for comp in fused)}


# -- the lowered step's StableHLO ------------------------------------------

HEAVY = ("while", "custom_call", "dot_general", "gather", "scatter",
         "all_to_all", "all_reduce", "all_gather", "reduce_scatter",
         "collective_permute")
# custom calls that mark a sharding and run nothing
_MARKERS = {"Sharding", "SPMDFullToShardShape", "SPMDShardToFullShape"}
_LOC_NAME = re.compile(r'^loc\("((?:[^"\\]|\\.)*)"')


def _walk(op, found: list):
    for region in op.regions:
        for block in region.blocks:
            for inner in block.operations:
                found.append(inner.operation)
                _walk(inner.operation, found)


def lowered_counts(lowered) -> dict:
    """``whiles``, ``heavy`` and ``heavy_unscoped`` of a LOWERED step
    (``jit(f).lower(...)``; nothing compiles): the `stablehlo.while`s, the
    ops of :data:`HEAVY` (sharding markers apart) and those of them whose
    location carries no `roc.` scope.  A private function's ops count once
    each, and are scoped where every call of the function is (JAX names
    the ops of a called function relative to its call sites)."""
    module = lowered.compiler_ir(dialect="stablehlo")
    calls: dict = {}        # callee -> [(caller, the call's own scope)]
    heavy: list = []        # (function, kind, scoped by its own location)
    for func in module.body.operations:
        fname = str(func.attributes["sym_name"]).strip('"')
        ops: list = []
        _walk(func.operation, ops)
        for op in ops:
            kind = op.name
            call = kind in ("func.call", "call")
            if not call and not (kind.startswith("stablehlo.")
                                 and kind[10:] in HEAVY):
                continue
            if kind == "stablehlo.custom_call" and str(
                    op.attributes["call_target_name"]).strip('"') \
                    in _MARKERS:
                continue
            m = _LOC_NAME.match(str(op.location))
            scoped = bool(m) and PREFIX in m.group(1)
            if call:
                callee = str(op.attributes["callee"]).lstrip("@")
                calls.setdefault(callee, []).append((fname, scoped))
            else:
                heavy.append((fname, kind[10:], scoped))

    scoped_fn: dict = {}

    def fn_scoped(name: str) -> bool:      # the call graph has no cycle
        if name not in scoped_fn:
            sites = calls.get(name, [])
            scoped_fn[name] = bool(sites) and all(
                s or fn_scoped(caller) for caller, s in sites)
        return scoped_fn[name]

    return {"whiles": sum(kind == "while" for _, kind, _ in heavy),
            "heavy": len(heavy),
            "heavy_unscoped": sum(not (s or fn_scoped(f))
                                  for f, _, s in heavy)}


@contextlib.contextmanager
def _no_persistent_cache():
    """Compiles inside neither read nor write JAX's persistent cache (the
    module docstring's trap); the cache is as it was afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()        # JAX asks the flag once and remembers
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def compile_uncached(lowered) -> str:
    """The optimized HLO text of ``lowered``, from a compile of this
    process's own: its ``op_name``s are this program's.  JAX also
    remembers, in the process, the executable it made or LOADED for a
    lowered module, and lowering a step twice gives the same module: a
    compiler option at its default value keys that memo apart and
    changes nothing the compiler does."""
    with _no_persistent_cache():
        return lowered.compile(
            compiler_options={"xla_dump_max_hlo_modules": -1}).as_text()
