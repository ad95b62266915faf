"""DeepSeek-V2-Lite on the CPU at a small size with seeded random weights:
latent attention, YaRN rope and the expert layer that holds one shard of
the router's experts, against the float32 reference in
``bench/reference/deepseek_v2.py`` (which imports nothing of the program).

The small configuration keeps the structure of the served one: d 64, 4
heads of MLA (latent 32, rope 16, nope 16, v 16, YaRN as published), 16
routed experts top-4 with gates not renormalised, 2 shared experts, one
dense layer and 2 MoE layers, 4 experts held (shard 0 of 4)."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import correct, manifest, weights  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import ExecConfig, build_model  # noqa: E402
from repro.models.moe import moe_apply, router_topk, shared_expert_apply  # noqa: E402

VOCAB = 512
EC = ExecConfig(backend="xla", loss_chunk=0)


def small(dtype="float32", **kw):
    return get_config("deepseek-v2-lite").with_overrides(**dict(dict(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, kv_lora_rank=32,
        qk_rope_head_dim=16, qk_nope_head_dim=16, v_head_dim=16,
        n_experts=16, experts_per_token=4, n_shared_experts=2, moe_d_ff=32,
        d_ff=96, dense_d_ff=96, vocab_size=VOCAB, expert_shards=4,
        dtype=dtype, param_dtype=dtype), **kw))


def reference_for(mc):
    cfg = json.loads((manifest.ROOT
                      / "bench/configs/deepseek-v2-lite.json").read_text())
    ref = correct.reference(cfg)
    return ref, dict(cfg, **{k: getattr(mc, a)
                             for k, a in ref.REGISTRY_KEYS.items()})


def tokens(B, S, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, (B, S)).astype(
        np.int32)


def test_param_counts_match_the_cut_and_the_published_model():
    """The served cut holds 535 M parameters (one MoE layer 100.4 M, the
    dense layer 81.0 M, the vocabulary slices 52.4 M); uncut, the same
    widths give the published 15.7 B."""
    cfg = get_config("deepseek-v2-lite")
    assert abs(cfg.layer_params(1) - 100.4e6) < 0.05e6
    assert abs(cfg.layer_params(0) - 81.0e6) < 0.05e6
    assert abs(cfg.param_count() - 535.0e6) < 0.5e6
    full = cfg.with_overrides(n_layers=27, vocab_size=102_400,
                              expert_shards=1)
    assert abs(full.param_count() - 15.7e9) / 15.7e9 < 0.01


# Two float32 implementations of one forward pass differ only in the order
# of their sums: about 1e-6 of the logits' largest magnitude here.  1e-4 of
# it leaves room for that and none for a lost term; the float8 control
# departs by about 0.3 of it.
REF_TOL = 1e-4


def test_logits_match_the_float32_reference():
    mc = small()
    ref, ref_cfg = reference_for(mc)
    model = build_model(mc, EC)
    params = weights.make(model.init, 7)
    t = tokens(2, 40)
    prog = np.asarray(model.logits(params, t))[:, -1]
    want = ref.last_logits(params, ref_cfg, t)
    scale = np.abs(want).max()
    assert np.abs(prog - want).max() <= REF_TOL * scale
    ctl = ref.last_logits(params, ref_cfg, t, mode="fp8")
    assert np.abs(ctl - want).max() > 100 * REF_TOL * scale


def test_prefill_then_decode_through_the_latent_cache_matches_the_forward():
    """Prefill and 4 decode steps through the cache of the latent (32) and
    the shared rope key (16) give the full forward pass's logits."""
    mc = small()
    model = build_model(mc, EC)
    params = weights.make(model.init, 3)
    t = jnp.asarray(tokens(2, 12, seed=1))
    cache = model.init_cache(2, 16)
    assert cache["k"].shape[-1] == mc.kv_lora_rank
    assert cache["v"].shape[-1] == mc.qk_rope_head_dim
    logits, cache, n = model.prefill(params, t, cache)
    seq = t
    for step in range(4):
        full = model.logits(params, seq)[:, -1]
        np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                                   atol=1e-4, rtol=1e-4, err_msg=str(step))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        seq = jnp.concatenate([seq, tok[:, None]], axis=1)
        idx = jnp.full((2,), seq.shape[1] - 1, jnp.int32)
        logits, cache = model.decode_step(params, tok, cache, idx)
    full = model.logits(params, seq)[:, -1]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                               atol=1e-4, rtol=1e-4)


def _moe_layer(seed=5):
    """The uncut layer's weights (16 experts) and a normed input."""
    full = small(expert_shards=1)
    model = build_model(full, EC)
    lp = jax.tree_util.tree_map(lambda x: x[0],
                                weights.make(model.init, seed)["layers"])
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 24, full.d_model))
    return full, lp["moe"], x


def test_expert_shares_add_up_to_the_uncut_layer():
    """Over the 4 disjoint held sets, the program's outputs summed, with
    the shared experts counted once, equal the uncut reference layer.  A
    chip holds the first quarter of its router's experts, so chip i's
    router lists experts 4i.. first: the same softmax and top-k, reordered."""
    full, m, x = _moe_layer()
    ref, _ = reference_for(full)
    want = ref.moe(x.reshape(-1, full.d_model), m, held=16, top_k=4,
                   norm_topk=False, scaling=1.0, mode="f32")
    shards = 4
    cfg = full.with_overrides(expert_shards=shards)
    total = -(shards - 1) * shared_expert_apply(m, x)
    for i in range(shards):
        part = dict(m, w_router=jnp.roll(m["w_router"], -4 * i, axis=1),
                    **{k: m[k][4 * i:4 * i + 4]
                       for k in ("w_gate", "w_up", "w_down")})
        y, _, rows = moe_apply(part, cfg, EC, x, train=False)
        assert rows.shape == (4,)
        total = total + y
    np.testing.assert_allclose(np.asarray(total).reshape(want.shape),
                               np.asarray(want), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("norm", [False, True])
def test_norm_topk_prob_decides_renormalisation(norm):
    full, m, x = _moe_layer()
    cfg = full.with_overrides(norm_topk_prob=norm)
    x2d = x.reshape(-1, cfg.d_model)
    gates, idx, _ = router_topk(m, cfg, x2d)
    probs = jax.nn.softmax(x2d @ m["w_router"], axis=-1)
    top = np.sort(np.asarray(probs), -1)[:, ::-1][:, :4]
    sums = np.asarray(gates).sum(-1)
    if norm:
        np.testing.assert_allclose(sums, 1.0, atol=1e-6)
    else:
        np.testing.assert_allclose(np.asarray(gates), top, rtol=1e-6)
        assert sums.max() < 0.99


def test_served_forward_drops_no_token_where_capacity_would():
    """Every token routed to one expert: GShard dispatch at capacity 1.25
    drops most of them in training, while a pass that does not train (the
    served forward, under the harness's ExecConfig with its einsum
    ``moe_impl``) computes every one, as the reference does."""
    full, m, x = _moe_layer()
    cfg = small(capacity_factor=1.25)
    u = jnp.ones(cfg.d_model) / cfg.d_model ** 0.5
    x = x + 10.0 * u
    m = dict(m, w_router=m["w_router"].at[:, 0].add(50.0 * u))
    part = dict(m, **{k: m[k][:4] for k in ("w_gate", "w_up", "w_down")})
    ref, _ = reference_for(cfg)
    want = ref.moe(x.reshape(-1, cfg.d_model), m, held=4, top_k=4,
                   norm_topk=False, scaling=1.0, mode="f32")
    ec = ExecConfig(backend="auto", loss_chunk=0)
    assert ec.moe_impl == "einsum"
    served, _, rows = moe_apply(part, cfg, ec, x, train=False)
    trained, _, _ = moe_apply(part, cfg, ec, x, train=True)
    assert int(rows[0]) == x.shape[0] * x.shape[1]       # all on expert 0
    np.testing.assert_allclose(np.asarray(served).reshape(want.shape),
                               np.asarray(want), atol=1e-5, rtol=1e-5)
    assert np.abs(np.asarray(trained).reshape(want.shape)
                  - np.asarray(want)).max() > 1e-2


def _reference_rows(ref, ref_cfg, params, t):
    """Per MoE layer, the reference router's top-k slots on the held
    experts, from the reference's own hidden states."""
    inv_freq, _, _ = ref.rotary(ref_cfg)
    kw = ref.layer_kw(ref_cfg)
    h = jnp.asarray(params["embed"])[jnp.asarray(t)].astype(jnp.float32)
    h = ref._layer(h, params["first_layers"][0], inv_freq, dense=True, **kw)
    out = []
    for i in range(ref_cfg["num_hidden_layers"] - 1):
        lp = ref.layer_slice(params["layers"], i)
        attn = jax.lax.map(
            lambda s: ref._attend(s, lp["attn"], inv_freq, heads=kw["heads"],
                                  nope=kw["nope"], rope=kw["rope"],
                                  dv=kw["dv"], rank=kw["rank"], cs=kw["cs"],
                                  scale=kw["scale"], mode="f32"),
            ref.rms_norm(h, lp["ln1"]["scale"], kw["eps"]))
        x = ref.rms_norm(h + attn, lp["ln2"]["scale"], kw["eps"])
        scores = jax.nn.softmax(
            x.reshape(-1, x.shape[-1]) @ lp["moe"]["w_router"], -1)
        idx = np.asarray(jax.lax.top_k(scores, kw["top_k"])[1]).ravel()
        out.append(np.bincount(idx[idx < kw["held"]], minlength=kw["held"]))
        h = ref._layer(h, lp, inv_freq, dense=False, **kw)
    return np.stack(out)


def test_routing_rows_beside_the_logits_match_the_reference_router():
    mc = small()
    ref, ref_cfg = reference_for(mc)
    model = build_model(mc, EC)
    params = weights.make(model.init, 11)
    t = tokens(2, 40, seed=2)
    logits, rows = model.routed_logits(params, t)
    assert rows.dtype == jnp.int32 and rows.shape == (2, 4)
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(model.logits(params, t)))
    np.testing.assert_array_equal(np.asarray(rows),
                                  _reference_rows(ref, ref_cfg, params, t))


def test_served_call_reports_its_routing_on_span_and_counters():
    """Through ``FaasmRuntime`` → ``make_infer_function``: each call's
    ``serve.forward`` span carries the rows its forward routed to each held
    expert, and the runtime's counters add them up."""
    from repro import telemetry
    from repro.core import FaasmRuntime
    from repro.launch.serve import make_infer_function
    from repro.state.ddo import VectorAsync
    mc = small("bfloat16", vocab_size=2048)
    model = build_model(mc, EC)
    params = weights.make(model.init, 13)
    flat, treedef = jax.tree_util.tree_flatten(params)
    leaves = [np.asarray(x) for x in flat]
    prompts = [np.random.default_rng(i).integers(0, 2048, 16).astype(
        np.int32) for i in range(3)]
    t = telemetry.enable()
    rt = FaasmRuntime(n_hosts=1, capacity=2)
    try:
        VectorAsync.create(rt.global_tier, "serve/stats",
                           np.zeros(2048, np.float32))
        rt.upload(make_infer_function(model, treedef, leaves, prompt_len=16,
                                      state_wire="int8"))
        cids = rt.invoke_many("infer", [p.tobytes() for p in prompts])
        assert rt.wait_all(cids, timeout=120) == [0] * 3
        got = t.spans()
        routed = rt.metrics.get("faasm_serve_moe_routed_rows_total").value
        busiest = rt.metrics.get("faasm_serve_moe_busiest_rows_total").value
    finally:
        rt.shutdown()
        telemetry.disable()
    want_total = want_busiest = 0
    forward = jax.jit(model.routed_logits)
    for cid, p in zip(cids, prompts):
        _, rows = forward(params, p[None])
        rows = np.asarray(rows)
        fwd = [s for s in got if s.call == cid and s.name == "serve.forward"]
        assert len(fwd) == 1
        assert fwd[0].tags == {"moe_rows": rows.tolist(),
                               "moe_rows_max": int(rows.max())}
        want_total += int(rows.sum())
        want_busiest += int(rows.max())
    assert (routed, busiest) == (want_total, want_busiest)
