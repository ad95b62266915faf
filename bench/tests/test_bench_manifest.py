"""``BENCHMARK.json`` keeps the rules the harness and the check rely on."""
import copy
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import manifest  # noqa: E402


@pytest.fixture(scope="module")
def committed():
    return manifest.load()


def test_committed_manifest_is_valid(committed):
    assert manifest.validate(committed) == []


def test_every_config_file_matches_the_registry(committed):
    from bench.harness import registry_config
    for c in committed["configs"]:
        cfg = json.loads((manifest.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        registry_config(cfg)


def _broken(committed, edit):
    m = copy.deepcopy(committed)
    edit(m)
    return manifest.validate(m)


def _second_cell(m):
    m["workloads"].append(dict(m["workloads"][0], name="other-cell"))
    m["end_to_end"][0]["workloads"] = ["other-cell"]


@pytest.mark.parametrize("edit,needle", [
    (lambda m: m["workloads"][0].update(name="bad name"), "name rule"),
    (lambda m: m["workloads"].append(dict(m["workloads"][0])), "duplicate"),
    (lambda m: m["end_to_end"][0].update(unit="req per s"), "bad unit"),
    (lambda m: m["end_to_end"][0].update(unit="µs"), "bad unit"),
    (lambda m: m["per_layer"][0].update(moves="nope"), "moves 'nope'"),
    (lambda m: m["per_layer"][0].update(moves="setup_s"), "moves 'setup_s'"),
    (_second_cell, "does not report"),
    (lambda m: m["per_layer"][0].update(workloads=["no-such-cell"]),
     "unknown workload"),
    (lambda m: m["end_to_end"].pop(), "setup_s"),
    (lambda m: m["workloads"][0].update(traffic="no-such-mix"), "traffic"),
    (lambda m: m["workloads"][0].update(config="no-such-config"),
     "unknown config"),
    (lambda m: m["configs"][0].update(file="bench/configs/none.json"),
     "missing"),
    (lambda m: m["per_layer"][0].update(name="no_reader"), "no reader"),
])
def test_broken_manifest_is_refused(committed, edit, needle):
    errs = _broken(committed, edit)
    assert any(needle in e for e in errs), errs
