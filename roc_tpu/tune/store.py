"""tuned.json — the content-keyed tuned-geometry tier.

One JSON document maps *graph content* (shape + an edge-list digest, the
same key discipline as binned's ``_plan_cache_path``) to the sweep's
winning kernel config per *variant* (the storage dtype).
``choose_geometry`` consults this tier BEFORE its analytic model, and
``build_binned_plan`` cross-checks explicitly-passed geometries against
it so a stale plan-cache hit can never silently pin an untuned geometry
(warn-once + prefer the tuned config).

Location: alongside the plan cache (``<plan cache dir>/tuned.json``) so a
plan-cache hit is also a tuned-config hit; ``ROC_TUNED_PATH`` overrides,
``ROC_NO_TUNED=1`` disables the tier entirely (the analytic model stays
in charge — the tuner's own trials run this way so a previous sweep can
never steer the next one's measurements).

Schema (validate_store is the single source of truth; the preflight gate
runs it over the selftest sweep's output)::

  {"version": 1,
   "interpret": <bool — true = CI surrogate sweep, not device times>,
   "seed": <int — the surrogate seed, for reproduction>,
   "entries": {
     "<content key: edges=..|rows=..|sha=..|table_rows=..>": {
       "<variant: fp32|bf16>": {
         "geom":      [<the full Geometry tuple, len-validated>],
         "knobs":     {"dma_cls": [...], "dimension_semantics": str,
                       "depth": int},
         "modeled_s": <stage-0 analytic seconds>,
         "trial_s":   <winning confirmation-trial seconds>,
         "source":    "surrogate" | "device"}}}}

Off the chip every entry applies: the tier is a policy choice (which
schedule to build) and the CI tests exercise it under interpret.  On a
TPU only ``"source": "device"`` entries apply — a winner picked by the
CPU surrogate must not decide which kernel the chip compiles.  Entry
geometries are re-validated at lookup time (Geometry.check() + the VMEM
budget) so a hand-edited or stale file degrades to the analytic model
instead of crashing a run.
"""

from __future__ import annotations

import hashlib
import json
import os
import warnings

import numpy as np

from roc_tpu.device import on_tpu
from roc_tpu.obs.ledger import content_key
from roc_tpu.obs.tracer import span as _obs_span
from roc_tpu.ops.pallas.binned import (Geometry, _plan_cache_dir,
                                       _vmem_bytes, _VMEM_NOMINAL_CAP)

VERSION = 1
_GEOM_FIELDS = len(Geometry._fields)
_VARIANTS = ("fp32", "bf16")

# Parsed-store cache: path -> (mtime_ns, size, doc-or-None).  choose_geometry
# consults the tier on every auto pick, so the file parses once per change,
# not once per plan.  clear_cache() for tests that rewrite the file in place.
_CACHE: dict = {}
# Warn-once registry for stale-geometry / invalid-entry findings, keyed by
# (path, content key): one warning per graph per process, not per rebuild.
_WARNED: set = set()


def tuned_store_path() -> str:
    """Resolved tuned.json path; '' disables the tier.  Rides the plan
    cache's location (and its ROC_PLAN_CACHE=0 opt-out) unless
    ROC_TUNED_PATH points elsewhere; ROC_NO_TUNED=1 kills it outright."""
    if os.environ.get("ROC_NO_TUNED"):
        return ""
    p = os.environ.get("ROC_TUNED_PATH")
    if p:
        return p
    base = _plan_cache_dir()
    return os.path.join(base, "tuned.json") if base else ""


def graph_key(edge_src, edge_dst, num_rows: int, table_rows: int) -> str:
    """Content key for one graph direction: shape plus a sha1 digest over
    the int64 edge bytes — the same content discipline as the plan cache,
    so the tuned entry and the cached plan invalidate together when the
    edges change.  O(E), only paid when a store exists (lookup
    short-circuits on the parsed doc first)."""
    h = hashlib.sha1()
    h.update(np.ascontiguousarray(edge_src, np.int64).tobytes())
    h.update(np.ascontiguousarray(edge_dst, np.int64).tobytes())
    return content_key(rows=int(num_rows), table_rows=int(table_rows),
                       edges=int(len(edge_src)), sha=h.hexdigest()[:16])


def variant_key(storage_dtype: str = "fp32") -> str:
    """The per-entry variant axis: the input that changes which
    candidates choose_geometry may even consider (bf16 flat units)."""
    return storage_dtype


def validate_store(doc) -> list:
    """Schema problems in a tuned.json document (empty list = valid).
    The preflight selftest gates on this, so a field rename in the sweep
    shows up in CI, not as a silently-ignored tier on the chip."""
    problems = []
    if not isinstance(doc, dict):
        return ["document is not an object"]
    if doc.get("version") != VERSION:
        problems.append(f"version {doc.get('version')!r} != {VERSION}")
    if not isinstance(doc.get("interpret"), bool):
        problems.append("missing/non-bool 'interpret'")
    entries = doc.get("entries")
    if not isinstance(entries, dict):
        return problems + ["missing/non-object 'entries'"]
    for gkey, variants in entries.items():
        if not isinstance(variants, dict):
            problems.append(f"{gkey}: variants not an object")
            continue
        for vkey, e in variants.items():
            where = f"{gkey}[{vkey}]"
            if vkey not in _VARIANTS:
                problems.append(f"{where}: unknown variant")
            if not isinstance(e, dict):
                problems.append(f"{where}: entry not an object")
                continue
            g = e.get("geom")
            if (not isinstance(g, list) or len(g) != _GEOM_FIELDS
                    or not all(isinstance(v, int) for v in g)):
                problems.append(
                    f"{where}: geom must be {_GEOM_FIELDS} ints")
            else:
                try:
                    Geometry(*g).check()
                except AssertionError as err:
                    problems.append(f"{where}: invalid geometry ({err})")
            for f in ("modeled_s", "trial_s"):
                if not isinstance(e.get(f), (int, float)) \
                        or isinstance(e.get(f), bool):
                    problems.append(f"{where}: non-numeric {f}")
            if e.get("source") not in ("surrogate", "device"):
                problems.append(f"{where}: bad source")
            if not isinstance(e.get("knobs"), dict):
                problems.append(f"{where}: missing knobs")
    return problems


def load_store(path: str = ""):
    """Parsed + validated tuned.json, or None (no file / invalid / tier
    disabled).  Cached per (path, mtime, size); an invalid document warns
    once and reads as absent — degrade to the analytic model, never
    crash a training run over a tuning artifact."""
    path = path or tuned_store_path()
    if not path:
        return None
    try:
        st = os.stat(path)
    except OSError:
        return None
    ck = (st.st_mtime_ns, st.st_size)
    hit = _CACHE.get(path)
    if hit is not None and hit[0] == ck:
        return hit[1]
    doc = None
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = None
    if doc is not None:
        problems = validate_store(doc)
        if problems:
            _warn_once((path, "schema"),
                       f"tuned store {path}: invalid schema "
                       f"({problems[0]}); ignoring the tuned tier")
            doc = None
    _CACHE[path] = (ck, doc)
    return doc


def save_store(path: str, doc: dict) -> None:
    """Deterministic, durable atomic write: sorted keys + fixed
    separators so the same sweep produces byte-identical files (the CI
    determinism pin), tmp + fsync + rename (fault.fsync_replace) so
    readers never see a torn document and a kill never leaves an
    unflushed one."""
    from roc_tpu.fault import fsync_replace
    problems = validate_store(doc)
    if problems:
        raise ValueError(f"refusing to write invalid tuned store: "
                         f"{problems[:3]}")
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    fsync_replace(tmp, path)
    _CACHE.pop(path, None)


def merge_entries(path: str, entries: dict, interpret: bool,
                  seed: int) -> dict:
    """Fold a sweep's winners into the store at ``path`` (creating it if
    absent) and write it back.  Per (graph, variant) the newest sweep
    wins; other graphs' entries survive — the store accumulates tuned
    shapes the way the plan cache accumulates plans."""
    doc = load_store(path) or {"version": VERSION, "interpret": interpret,
                               "seed": seed, "entries": {}}
    doc["interpret"] = bool(interpret)
    doc["seed"] = int(seed)
    for gkey, variants in entries.items():
        doc["entries"].setdefault(gkey, {}).update(variants)
    save_store(path, doc)
    return doc


def _warn_once(key, msg: str) -> None:
    if key in _WARNED:
        return
    _WARNED.add(key)
    warnings.warn(msg, stacklevel=3)


def _entry_geom(path: str, gkey: str, vkey: str, e: dict):
    """Entry -> validated Geometry, or None: a surrogate entry on a TPU
    (silently — the analytic model is the intended answer there), or
    (warn-once) a stored tuple that no longer passes the live
    invariants/VMEM budget — e.g. a file from a future field layout or a
    hand-edit."""
    if e.get("source") != "device" and on_tpu():
        return None
    try:
        g = Geometry(*e["geom"]).check()
    except (AssertionError, TypeError):
        _warn_once((path, gkey, vkey),
                   f"tuned entry {vkey} for {gkey.split('|')[-1]} has an "
                   f"invalid geometry; falling back to the analytic model")
        return None
    if _vmem_bytes(g) > _VMEM_NOMINAL_CAP:
        _warn_once((path, gkey, vkey),
                   f"tuned entry {vkey} geometry {tuple(g)} exceeds the "
                   f"VMEM budget; falling back to the analytic model")
        return None
    return g


def lookup(edge_src, edge_dst, num_rows: int, table_rows: int,
           storage_dtype: str = "fp32", path: str = ""):
    """(Geometry, entry) for this graph + variant, or (None, None).
    EXACT variant match only; misses fall back to the analytic model."""
    doc = load_store(path)
    if doc is None:
        return None, None
    variants = doc["entries"].get(
        graph_key(edge_src, edge_dst, num_rows, table_rows))
    if not variants:
        return None, None
    vkey = variant_key(storage_dtype)
    e = variants.get(vkey)
    if e is None:
        return None, None
    g = _entry_geom(path or tuned_store_path(),
                    graph_key(edge_src, edge_dst, num_rows, table_rows),
                    vkey, e)
    return (g, e) if g is not None else (None, None)


def stale_plan_geom(edge_src, edge_dst, num_rows: int, table_rows: int,
                    geom: Geometry, path: str = ""):
    """Plan-cache hygiene check (build_binned_plan): the tuned geometry
    this explicitly-requested ``geom`` should yield to, or None when the
    request agrees with the tier (or no tier entry exists).

    Variant selection without the caller's storage declaration: a
    single-variant entry is unambiguous; otherwise the geometry's own
    staging unit implies the storage family (unit=16 is bf16-only by the
    Geometry invariant).  Warn-once per graph when a switch happens."""
    doc = load_store(path)
    if doc is None:
        return None
    with _obs_span("plan_key", edges=len(edge_src)):
        gkey = graph_key(edge_src, edge_dst, num_rows, table_rows)
    variants = doc["entries"].get(gkey)
    if not variants:
        return None
    storage = "bf16" if geom.unit == 16 else "fp32"
    order = [storage]
    if len(variants) == 1:
        order = list(variants)
    for vkey in order:
        e = variants.get(vkey)
        if e is None:
            continue
        tg = _entry_geom(path or tuned_store_path(), gkey, vkey, e)
        if tg is None:
            return None
        if tuple(tg) == tuple(geom):
            return None
        _warn_once((path or tuned_store_path(), gkey, "stale"),
                   f"requested plan geometry {tuple(geom)} disagrees with "
                   f"the tuned winner {tuple(tg)} for this graph "
                   f"({vkey}); building the tuned geometry instead "
                   f"(pass tuned_ok=False to force an A/B)")
        return tg
    return None


def clear_cache() -> None:
    """Drop the parsed-store cache and the warn-once registry (tests)."""
    _CACHE.clear()
    _WARNED.clear()
