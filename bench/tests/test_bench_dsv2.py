"""The ``dsv2lite-fanout-2k`` cell: DeepSeek-V2-Lite served through
Faaslets at 2,048 tokens.

``run_cell`` at a small size on the CPU (the structure of the served
configuration at d 64, 16 routed experts of which 4 are held) comes out
correct with the program in place and not correct with the float8 control
in its place; the counts of ``bench/flops/deepseek_v2.py`` against hand
counts; the cell's readers on synthetic runs."""
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, manifest, roofline  # noqa: E402
from bench.flops import deepseek_v2 as flops  # noqa: E402
from bench.harness import reader  # noqa: E402

CELL = "dsv2lite-fanout-2k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def small():
    from repro.configs import get_config
    return get_config("deepseek-v2-lite").with_overrides(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, kv_lora_rank=32,
        qk_rope_head_dim=16, qk_nope_head_dim=16, v_head_dim=16,
        n_experts=16, experts_per_token=4, n_shared_experts=2, moe_d_ff=32,
        d_ff=96, dense_d_ff=96, vocab_size=2048, expert_shards=4)


def run(**kw):
    bench = manifest.load()
    return harness.run_cell(bench, manifest.cell(bench, CELL), 2 ** 33 + 17,
                            1.0, False, t_start=time.perf_counter(),
                            model_cfg=small(), **kw)


def test_sound_run_is_correct():
    r = run()
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 16
    # the dropless expert path compiles once per shape, never per call
    assert r["_readings"]["compiles_in_window"] == 0
    assert r["_readings"]["cold_starts_in_window"] == 0


# At this size the bfloat16 program's gap reads 0.0 and the control's
# 0.28-0.54 over three seeds (CPU), so the limit for this size sits
# between them.
SMALL_TOKEN_GAP = 0.1


def test_control_in_the_programs_place_is_not_correct(monkeypatch):
    load = harness.load_config

    def small_limit(bench, name):
        cfg = load(bench, name)
        return dict(cfg, limits=dict(cfg["limits"], token_gap=SMALL_TOKEN_GAP))

    monkeypatch.setattr(harness, "load_config", small_limit)
    r = run(control=True)
    assert not r["correct"]
    c = r["checks"]["token_gap"]
    assert c["value"] > c["limit"]
    assert r["_readings"]["program_token_gap"] <= c["limit"]


def test_mla_flash_hand_count():
    # S=4 causal: 10 query-key pairs; H=2, q/k 6 channels, v 4: scores
    # 2*6 and values 2*4 operations per pair per head
    f, b = flops.mla_flash(4, heads=2, qk_dim=6, v_dim=4)
    assert f == 2 * 10 * (2 * 6 + 2 * 4)
    # q and k: 4*2*6 each, v and o: 4*2*4 each, bf16
    assert b == 2 * (2 * 48 + 2 * 32)


def test_forward_hand_count():
    cfg = {"hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 3,
           "qk_rope_head_dim": 2, "v_head_dim": 4, "kv_lora_rank": 6,
           "intermediate_size": 16, "moe_intermediate_size": 5,
           "n_shared_experts": 2, "num_experts_per_tok": 2,
           "n_routed_experts": 2, "router_experts": 8,
           "num_hidden_layers": 3, "first_k_dense_replace": 1,
           "vocab_size": 11}
    S = 4
    proj = 2 * S * (8 * 2 * 5 + 8 * (6 + 2) + 6 * 2 * (3 + 4) + 2 * 4 * 8)
    attn = 2 * 2 * 10 * (5 + 4)
    dense = 2 * S * 8 * 16 * 3
    # 4 tokens x 2 slots, a quarter of them on the 2 of 8 experts held
    moe = 2 * S * 8 * 8 + 2 * S * 8 * 10 * 3 + 2 * 2 * 8 * 5 * 3
    assert flops.forward(cfg, S) == 3 * (proj + attn) + dense + 2 * moe \
        + 2 * 8 * 11


def test_published_forward_is_about_a_teraflop():
    cfg = json.loads((manifest.ROOT
                      / "bench/configs/deepseek-v2-lite.json").read_text())
    assert 1.0e12 < flops.forward(cfg, 2048) < 1.1e12


def test_new_cell_is_in_the_committed_manifest():
    m = manifest.load()
    assert manifest.validate(m) == []
    cell = manifest.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepseek-v2-lite", "fanout-2k", 1)
    layer = {x["name"] for x in manifest.per_layer_for(m, CELL)}
    assert {"mla_flash_roofline", "serve_mfu.dsv2", "moe_imbalance",
            "compile_ms.fanout"} <= layer
    assert not {"flash_roofline", "serve_mfu"} & layer
    cfg = harness.load_config(m, "deepseek-v2-lite")
    for key, n in cfg["published"].items():
        assert key in cfg["reduced"] and cfg[key] < n


def flash_run(events, config):
    return SimpleNamespace(trace={"kernels": {"flash_attention": events}},
                           peaks=PEAKS, config=config)


MLA = {"qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128}
EVENT = "%x = bf16[1,16,2048,128]{3,2,1,0} custom-call(...)"


def test_mla_roofline_counts_the_published_widths():
    f, b = flops.mla_flash(2048, 16, 192, 128)
    least = max(f / PEAKS["bf16_flops_per_s"], b / PEAKS["hbm_bytes_per_s"])
    run = flash_run([(0.0, 4 * least, EVENT)], MLA)
    assert reader("mla_flash_roofline")(run) == pytest.approx(25.0)
    # an event padded to 256 channels still counts 192 and 128
    padded = EVENT.replace("128]", "256]")
    assert roofline.event_shape(padded)[3] == 256
    assert reader("mla_flash_roofline")(
        flash_run([(0.0, 4 * least, padded)], MLA)) == pytest.approx(25.0)
    assert reader("mla_flash_roofline")(flash_run([], MLA)) is None


def span(name, call, **tags):
    return SimpleNamespace(name=name, t0=0.0, t1=1.0, call=call,
                           tags=tags or None)


def test_moe_imbalance_is_busiest_over_mean_per_served_call():
    rows_a = [[2, 2], [2, 2]]                 # even: 1.0
    rows_b = [[1, 3], [2, 2]]                 # busiest 3 over mean 2: 1.5
    run = SimpleNamespace(
        calls=[SimpleNamespace(cid=c, rc=rc) for c, rc in
               ((1, 0), (2, 0), (3, 1))],
        spans={1: [span("call.exec", 1),
                   span("serve.forward", 1, moe_rows=rows_a, moe_rows_max=2)],
               2: [span("serve.forward", 2, moe_rows=rows_b, moe_rows_max=3)],
               3: [span("serve.forward", 3, moe_rows=[[9, 0], [0, 0]],
                        moe_rows_max=9)]})
    assert reader("moe_imbalance")(run) == pytest.approx(1.25)


def test_moe_imbalance_is_silent_without_routing_on_the_span():
    calls = [SimpleNamespace(cid=1, rc=0)]
    dense = SimpleNamespace(calls=calls, spans={1: [span("serve.forward", 1)]})
    assert reader("moe_imbalance")(dense) is None
    assert reader("moe_imbalance")(SimpleNamespace(calls=calls,
                                                   spans=None)) is None


def test_serve_mfu_dsv2_counts_calls_settled_in_the_trace():
    cfg = json.loads((manifest.ROOT
                      / "bench/configs/deepseek-v2-lite.json").read_text())
    calls = [SimpleNamespace(rc=0, end=e, length=2048) for e in (0.5, 1.5)] \
        + [SimpleNamespace(rc=1, end=0.6, length=2048)]
    run = SimpleNamespace(trace={"t0": 0.0, "t1": 1.0}, peaks=PEAKS,
                          config=cfg, calls=calls)
    want = 100.0 * flops.forward(cfg, 2048) / PEAKS["bf16_flops_per_s"]
    assert reader("serve_mfu.dsv2")(run) == pytest.approx(want)
    assert reader("serve_mfu.dsv2")(SimpleNamespace(trace=None)) is None
