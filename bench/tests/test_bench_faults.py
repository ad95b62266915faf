"""A run with the timed path broken underneath comes out not correct.

Drives :func:`bench.harness.run_cell` (everything ``bench/run.py`` does
after its look for a chip) at a small size on the CPU, once sound and once
for each fault a Faaslet inference cell can have: a token altered where it
is produced, a call that leaves the shared state unchanged, and an answer
altered after it was counted.  With the float8 control in the program's
place the run comes out not correct, while the program's own answers on
the same sample pass."""
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, manifest  # noqa: E402


def run(cell_name, **kw):
    from repro.configs import smoke_config
    bench = manifest.load()
    cell = manifest.cell(bench, cell_name)
    arch = harness.load_config(bench, cell["config"])["registry"]
    mc = smoke_config(arch).with_overrides(vocab_size=2048)
    return harness.run_cell(bench, cell, 2 ** 33 + 17, 1.0, False,
                            t_start=time.perf_counter(), model_cfg=mc, **kw)


def test_sound_run_is_correct():
    r = run("qwen05b-fanout")
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 20
    assert r["_readings"]["compiles_in_window"] == 0
    assert r["_readings"]["cold_starts_in_window"] == 0
    assert list(r)[-3:] == ["checks", "_calls", "_readings"]


# At this size the logits spread less than at published widths: the
# program's gap reads 0.0019-0.0039 and the control's 0.031-0.040 over
# three seeds (CPU), so the limit for this size sits between them.
SMALL_TOKEN_GAP = 0.015


def test_control_in_the_programs_place_is_not_correct(monkeypatch):
    load = harness.load_config

    def small_limit(bench, name):
        cfg = load(bench, name)
        return dict(cfg, limits=dict(cfg["limits"], token_gap=SMALL_TOKEN_GAP))

    monkeypatch.setattr(harness, "load_config", small_limit)
    r = run("qwen05b-fanout", control=True)
    assert not r["correct"]
    c = r["checks"]["token_gap"]
    assert c["value"] > c["limit"]
    assert r["_readings"]["program_token_gap"] <= c["limit"]
    assert r["checks"]["stats_miscount"]["value"] <= \
        r["checks"]["stats_miscount"]["limit"]


def test_token_altered_where_produced(monkeypatch):
    from repro.models.model import Model
    logits = Model.logits
    monkeypatch.setattr(Model, "logits",
                        lambda self, p, t, extra=None: -logits(self, p, t))
    r = run("qwen05b-fanout")
    assert not r["correct"]
    c = r["checks"]["token_gap"]
    assert c["value"] > c["limit"]


def test_state_left_unchanged(monkeypatch):
    from repro.state.ddo import VectorAsync
    monkeypatch.setattr(VectorAsync, "push_delta", lambda self, wire="auto": None)
    r = run("qwen05b-fanout")
    assert not r["correct"]
    c = r["checks"]["stats_miscount"]
    assert c["value"] > c["limit"]


def test_answer_altered_after_it_was_counted(monkeypatch):
    import numpy as np
    from repro.core.host_interface import FaasmAPI
    write = FaasmAPI.write_call_output

    def altered(self, data):
        tok = int(np.frombuffer(data, np.int32)[0])
        return write(self, np.int32(tok + 1).tobytes())

    monkeypatch.setattr(FaasmAPI, "write_call_output", altered)
    r = run("qwen05b-fanout")
    assert not r["correct"]
    for name in ("token_gap", "stats_miscount"):
        c = r["checks"][name]
        assert c["value"] > c["limit"], name
