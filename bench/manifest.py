"""``BENCHMARK.json``: loading, lookups and the checks every entry must pass.

The harness finds everything by name: a cell's configuration file, its
traffic file under ``bench/traffic/``, and one reader per metric under
``bench/metrics/``.  :func:`validate` checks the manifest against the rules
the harness relies on; the tests run it on the committed file.
"""
from __future__ import annotations

import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def cell(manifest: dict, name: str) -> dict:
    for c in manifest["workloads"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in manifest['workloads']]}")


def config(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def reports(metric: dict, cell_name: str) -> bool:
    """Whether a cell reports ``metric``: every cell unless the metric
    lists its cells under ``workloads``."""
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end_for(manifest: dict, cell_name: str) -> list:
    return [m for m in manifest["end_to_end"] if reports(m, cell_name)]


def per_layer_for(manifest: dict, cell_name: str) -> list:
    return [m for m in manifest["per_layer"] if reports(m, cell_name)]


def validate(m: dict) -> list:
    """Every rule that the harness relies on and manifest ``m`` breaks, as
    readable strings: names and units, files found by name, and metrics
    that resolve to cells reporting what they move."""
    errs = []
    cells, e2e, layers = m["workloads"], m["end_to_end"], m["per_layer"]
    for what, items in (("config", m["configs"]), ("workload", cells),
                        ("metric", e2e + layers)):
        seen = set()
        for it in items:
            n = it["name"]
            if not NAME_RE.fullmatch(n):
                errs.append(f"{what} name {n!r} breaks the name rule")
            if n in seen:
                errs.append(f"duplicate {what} name {n!r}")
            seen.add(n)
    cfg_names = {c["name"] for c in m["configs"]}
    for c in m["configs"]:
        if not (ROOT / c["file"]).is_file():
            errs.append(f"config {c['name']}: {c['file']} missing")
    cell_names = {c["name"] for c in cells}
    for c in cells:
        if c["config"] not in cfg_names:
            errs.append(f"workload {c['name']}: unknown config {c['config']}")
        if not NAME_RE.fullmatch(c["traffic"]) or \
                not (BENCH / "traffic" / f"{c['traffic']}.json").is_file():
            errs.append(f"workload {c['name']}: no bench/traffic/"
                        f"{c['traffic']}.json")
    e2e_by_name = {x["name"]: x for x in e2e}
    if "setup_s" not in e2e_by_name:
        errs.append("setup_s is missing from end_to_end")
    for x in e2e + layers:
        if not UNIT_RE.fullmatch(x["unit"]):
            errs.append(f"metric {x['name']}: bad unit {x['unit']!r}")
        for c in x.get("workloads", []):
            if c not in cell_names:
                errs.append(f"metric {x['name']}: unknown workload {c}")
        if x["name"] != "setup_s" and \
                not (BENCH / "metrics" / f"{x['name']}.py").is_file():
            errs.append(f"metric {x['name']}: no reader bench/metrics/"
                        f"{x['name']}.py")
    for x in layers:
        moved = e2e_by_name.get(x["moves"])
        if moved is None or x["moves"] == "setup_s":
            errs.append(f"per_layer {x['name']}: moves {x['moves']!r}, not "
                        f"an end-to-end metric other than setup_s")
            continue
        for c in x.get("workloads", cell_names):
            if not reports(moved, c):
                errs.append(f"per_layer {x['name']}: cell {c} does not "
                            f"report {x['moves']}")
    return errs
