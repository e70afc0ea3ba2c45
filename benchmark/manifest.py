"""BENCHMARK.json and the data files it names, found by name.

    configs/<config>.json        sizes as run; names its builder and reference
    traffic/<traffic>.json       parameters of one mix; names its generator kind
    cells/<cell>.json            the limits that decide `correct`, the traced window
    layer_metrics/<metric>.json  names a reader (dotted path) and its arguments

The harness holds no list of cells, configurations, mixes or metrics:
a later PR adds files and one manifest entry.
"""
from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def data_file(kind: str, name: str, root: str = ROOT) -> dict:
    """`benchmark/<kind>/<name>.json`, by name."""
    if not NAME_RE.match(name):
        raise ManifestError(f"{kind} name {name!r} has characters outside "
                            "letters, digits, _ . -")
    path = os.path.join(root, "benchmark", kind, name + ".json")
    if not os.path.isfile(path):
        raise ManifestError(f"no data file {path}")
    return _load(path)


def resolve(dotted: str):
    """'pkg.module:attr' -> the attribute."""
    mod, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(mod), attr)


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = _load(os.path.join(root, "BENCHMARK.json"))
        self.cells = {w["name"]: w for w in self.doc["workloads"]}

    def cell(self, name: str) -> dict:
        """A cell is its entry in `workloads` (config, traffic, chips, why)
        and, from its own data file, the limits that decide `correct` and
        the traced window. Each key lives in one place."""
        if name not in self.cells:
            raise ManifestError(f"no cell {name!r} in BENCHMARK.json")
        own = data_file("cells", name, self.root)
        twice = sorted(set(own) & set(self.cells[name]))
        if twice:
            raise ManifestError(f"cell {name!r}: {twice} both in its file "
                                "and in BENCHMARK.json")
        return {**own, **self.cells[name]}

    def metrics_for(self, cell: str, group: str) -> list:
        """Metrics of `end_to_end` or `per_layer` that this cell reports:
        those that list it under `workloads`, and those with no such key
        whose end-to-end metric the cell reports."""
        if group == "end_to_end":
            return [m for m in self.doc["end_to_end"]
                    if cell in m.get("workloads", [cell])]
        mine = {m["name"] for m in self.metrics_for(cell, "end_to_end")}
        return [m for m in self.doc["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


def check_names(doc: dict) -> list:
    """The contract's rules on names, units and cross-references that a
    test can hold every data file to. Returns the faults found."""
    bad = []
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in doc.get(group, []):
            n = e.get("name", "")
            if not NAME_RE.match(n):
                bad.append(f"{group}: name {n!r}")
            if (group, n) in names:
                bad.append(f"{group}: {n!r} twice")
            names.add((group, n))
    for m in doc.get("end_to_end", []) + doc.get("per_layer", []):
        if not UNIT_RE.match(m.get("unit", "")):
            bad.append(f"{m.get('name')}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"{m.get('name')}: better {m.get('better')!r}")
        if m.get("source") not in SOURCES:
            bad.append(f"{m.get('name')}: source {m.get('source')!r}")
    e2e = {m["name"] for m in doc.get("end_to_end", [])}
    cells = {w["name"] for w in doc.get("workloads", [])}
    cfgs = {c["name"] for c in doc.get("configs", [])}
    for w in doc.get("workloads", []):
        if w.get("config") not in cfgs:
            bad.append(f"cell {w['name']}: config {w.get('config')!r}")
        if w.get("chips") not in (1, 4):
            bad.append(f"cell {w['name']}: chips {w.get('chips')!r}")
        if not 1 <= len(w.get("why", "")) <= 200:
            bad.append(f"cell {w['name']}: why of {len(w.get('why', ''))}")
    for m in doc.get("per_layer", []):
        if m.get("moves") not in e2e:
            bad.append(f"{m['name']}: moves {m.get('moves')!r}")
    for m in doc.get("end_to_end", []) + doc.get("per_layer", []):
        for c in m.get("workloads", []):
            if c not in cells:
                bad.append(f"{m['name']}: lists unknown cell {c!r}")
    return bad
