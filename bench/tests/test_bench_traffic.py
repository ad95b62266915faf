"""The seeded traffic generator: every seed gets the same work."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import traffic  # noqa: E402

MIX = {"loop": "closed", "clients": 4,
       "prompt_mix": [{"tokens": 32, "count": 10},
                      {"tokens": 128, "count": 7},
                      {"tokens": 512, "count": 3}]}


def test_prompt_blocks_keep_the_mix():
    p = traffic.Prompts(MIX, 1000, traffic.seed_seq(9, 0))
    ps = p.take(200)
    lens = [len(x) for x in ps]
    assert lens.count(32) == 100 and lens.count(128) == 70 \
        and lens.count(512) == 30
    assert all(x.dtype == np.int32 and x.min() >= 0 and x.max() < 1000
               for x in ps)
    q = traffic.Prompts(MIX, 1000, traffic.seed_seq(9, 0)).take(200)
    assert all(np.array_equal(a, b) for a, b in zip(ps, q))


@pytest.mark.parametrize("seed", [1, 2 ** 40 + 7])
def test_seeds_differ_in_order_and_ids_only(seed):
    a = traffic.Prompts(MIX, 1000, traffic.seed_seq(seed, 0)).take(40)
    b = traffic.Prompts(MIX, 1000, traffic.seed_seq(seed + 1, 0)).take(40)
    assert sorted(map(len, a)) == sorted(map(len, b))
    assert [len(x) for x in a] != [len(x) for x in b]


def test_committed_mix_loads():
    spec = traffic.load("fanout")
    assert spec["loop"] == "closed" and spec["clients"] == 16
    assert traffic.lengths(spec) == [384]


def test_only_a_closed_loop_is_driven(tmp_path, monkeypatch):
    (tmp_path / "open.json").write_text('{"loop": "open", "prompt_mix": []}')
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", tmp_path)
    with pytest.raises(ValueError):
        traffic.load("open")
