"""Reading and checking a benchmark manifest (`BENCHMARK.json`, or the
rehearsal manifest in the same schema) and finding a cell's files by name.

A cell names a configuration and a traffic mix.  The configuration's file
is the manifest's ``configs[].file``; the traffic recipe is
``<dir of the configs directory>/traffic/<traffic>.json``; each per-layer
metric's reader is ``benchmark/layer_metrics/<name>.json``.  Adding a cell,
a configuration, a recipe or a metric is adding files and manifest entries;
nothing here names one.
"""

from __future__ import annotations

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYER_METRICS_DIR = os.path.join(ROOT, "benchmark", "layer_metrics")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _line(text, what: str, problems: list) -> None:
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        problems.append(f"{what}: 1 to 200 characters on one line, no tab")


def problems_in(m: dict, root: str = ROOT) -> list:
    """Every breach of the contract's static rules (an empty list: none)."""
    out: list = []
    if set(m) != TOP_KEYS:
        out.append(f"top-level keys {sorted(m)} != {sorted(TOP_KEYS)}")
        return out
    if not 1 <= len(m["paths"]) <= 16:
        out.append("paths: 1 to 16 directories")
    for p in m["paths"]:
        if not PATH_RE.match(p) or p.startswith("/") or ".." in p.split("/"):
            out.append(f"path {p!r}: relative, allowed characters only")
    under = tuple(p.rstrip("/") + "/" for p in m["paths"])
    if not 1 <= len(m["command"]) <= 32:
        out.append("command: 1 to 32 words")
    for w in m["command"]:
        _line(w, f"command word {w!r}", out)
        if w.startswith("/") or ".." in w.split("/"):
            out.append(f"command word {w!r} leaves the repo")
        if os.path.exists(os.path.join(root, w)) and "/" in w \
                and not w.startswith(under):
            out.append(f"command names {w!r}, a file outside paths")
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        out.append("run_seconds: a whole number from 1 to 51")

    def names(entries, what):
        seen = set()
        for e in entries:
            n = e.get("name", "")
            if not NAME_RE.match(n):
                out.append(f"{what} name {n!r}: bad characters or length")
            if n in seen:
                out.append(f"{what} name {n!r} appears twice")
            seen.add(n)
        return seen

    if not 1 <= len(m["configs"]) <= 24:
        out.append("configs: 1 to 24")
    cfg_names = names(m["configs"], "config")
    files = set()
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            out.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        _line(c["source"], f"config {c['name']} source", out)
        _line(c["why"], f"config {c['name']} why", out)
        if not (PATH_RE.match(c["file"]) and c["file"].startswith(under)):
            out.append(f"config file {c['file']!r} is not under paths")
        if c["file"] in files:
            out.append(f"config file {c['file']!r} is used twice")
        files.add(c["file"])
        if len(c["reduced"]) > 16 or not all(
                NAME_RE.match(k) for k in c["reduced"]):
            out.append(f"config {c['name']}: reduced keys")
        if not os.path.isfile(os.path.join(root, c["file"])):
            out.append(f"config file {c['file']!r} does not exist")

    if not 2 <= len(m["workloads"]) <= 24:
        out.append("workloads: 2 to 24 cells")
    cells = names(m["workloads"], "workload")
    pairs = set()
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            out.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        _line(w["why"], f"workload {w['name']} why", out)
        if w["config"] not in cfg_names:
            out.append(f"workload {w['name']}: no config {w['config']!r}")
        if not NAME_RE.match(w["traffic"]):
            out.append(f"workload {w['name']}: traffic name")
        if w["chips"] not in (1, 4):
            out.append(f"workload {w['name']}: chips 1 or 4")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"pair {w['config']} x {w['traffic']} appears twice")
        pairs.add((w["config"], w["traffic"]))
    used = {w.get("config") for w in m["workloads"]}
    for c in cfg_names - used:
        out.append(f"config {c!r} is used by no cell")
    four = sum(1 for w in m["workloads"] if w.get("chips") == 4)
    if four > max(1, len(m["workloads"]) // 4):
        out.append(f"{four} four-chip cells of {len(m['workloads'])}: at "
                   f"most 25 %, rounded down, and one always may")

    if not 1 <= len(m["end_to_end"]) <= 16:
        out.append("end_to_end: 1 to 16 metrics")
    if not 1 <= len(m["per_layer"]) <= 128:
        out.append("per_layer: 1 to 128 metrics")
    names(m["end_to_end"] + m["per_layer"], "metric")
    e2e = {e.get("name") for e in m["end_to_end"]}
    if "setup_s" not in e2e:
        out.append("end_to_end lacks setup_s")
    for e in m["end_to_end"]:
        if not set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"} or not {"name", "unit", "better",
                                               "bound", "source"} <= set(e):
            out.append(f"end_to_end {e.get('name')}: keys {sorted(e)}")
            continue
        if e["source"] not in ("host_clock", "device_trace"):
            out.append(f"end_to_end {e['name']}: source {e['source']!r}")
        if not 0.01 <= e["bound"] <= 0.1:
            out.append(f"end_to_end {e['name']}: bound {e['bound']}")
    for e in m["per_layer"]:
        if not set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"} or not {
                "name", "unit", "better", "source", "layer",
                "moves"} <= set(e):
            out.append(f"per_layer {e.get('name')}: keys {sorted(e)}")
            continue
        if e["source"] not in SOURCES:
            out.append(f"per_layer {e['name']}: source {e['source']!r}")
        if e["moves"] not in e2e:
            out.append(f"per_layer {e['name']}: moves {e['moves']!r}, which "
                       f"is no end-to-end metric")
        _line(e["layer"], f"per_layer {e['name']} layer", out)
    for e in m["end_to_end"] + m["per_layer"]:
        if not UNIT_RE.match(str(e.get("unit", ""))):
            out.append(f"metric {e.get('name')}: unit {e.get('unit')!r}")
        if e.get("better") not in ("lower", "higher"):
            out.append(f"metric {e.get('name')}: better lower|higher")
        for w in e.get("workloads", []):
            if w not in cells:
                out.append(f"metric {e.get('name')}: no cell {w!r}")
    if len(json.dumps(m)) > 64 * 1024:
        out.append("manifest over 64 KiB")
    return out


def cell(m: dict, name: str) -> dict:
    for w in m["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r}; the manifest has "
                   f"{[w['name'] for w in m['workloads']]}")


def config_entry(m: dict, name: str) -> dict:
    return next(c for c in m["configs"] if c["name"] == name)


def traffic_path(m: dict, cell_: dict, root: str = ROOT) -> str:
    cfg_file = os.path.join(root, config_entry(m, cell_["config"])["file"])
    return os.path.join(os.path.dirname(os.path.dirname(cfg_file)),
                        "traffic", cell_["traffic"] + ".json")


def metrics_for(m: dict, group: str, cell_name: str) -> list:
    """The manifest's ``end_to_end`` or ``per_layer`` entries that apply to
    a cell (all of them unless the entry lists ``workloads``)."""
    return [e for e in m[group]
            if "workloads" not in e or cell_name in e["workloads"]]


def layer_metric_spec(m: dict, cell_: dict, name: str,
                      root: str = ROOT) -> dict:
    """The reader's data file of one per-layer metric: beside the cell's
    configuration (``<dir of the configs directory>/layer_metrics/``) or in
    ``benchmark/layer_metrics/``."""
    cfg_file = os.path.join(root, config_entry(m, cell_["config"])["file"])
    for d in (os.path.join(os.path.dirname(os.path.dirname(cfg_file)),
                           "layer_metrics"), LAYER_METRICS_DIR):
        path = os.path.join(d, name + ".json")
        if os.path.isfile(path):
            return load(path)
    raise FileNotFoundError(f"no layer_metrics/{name}.json for the "
                            f"per-layer metric {name!r}")
