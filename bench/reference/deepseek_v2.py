"""Plain float32 reference of DeepSeek-V2 (DeepSeek-V2-Lite checkpoints).

Follows the published ``modeling_deepseek.py``: token embedding; per layer
RMSNorm, latent attention (MLA), RMSNorm and an MLP, each added to the
residual; final RMSNorm; logits against the untied head.

* Attention without q-LoRA: ``q = x W_q`` (heads of nope + rope channels);
  ``[c_kv, k_pe] = x W_kv_a``, ``c_kv`` RMS-normed (eps 1e-6, the norm's
  default) and expanded by ``W_kv_b`` to each head's ``k_nope`` and ``v``;
  rotary embeddings on the rope channels only, after the published
  regrouping of interleaved pairs ``view(.., d/2, 2).transpose``, with
  ``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies and cos/sin scale;
  ``k_pe`` one vector shared by every head; causal softmax in float32 at
  ``(nope + rope)^-0.5 * mscale(factor, mscale_all_dim)^2``.
* Layers before ``first_k_dense_replace`` have a SiLU-gated MLP of
  ``intermediate_size``; the others ``MoEGate`` (softmax over the router's
  experts, greedy top-k, renormalised only with ``norm_topk_prob``, else
  scaled by ``routed_scaling_factor``) and the shared experts as one SiLU
  MLP of ``moe_intermediate_size * n_shared_experts``.

Departures, each the program's too:

* The expert share: the router keeps its ``router_experts`` outputs, but
  only experts ``0 .. n_routed_experts - 1`` (the ones this chip holds) are
  computed, each applied to every token and weighted by its gate, which is
  0 where the token did not route to it.  What the other experts would add
  is left out.
* The vocabulary is a slice: the embedding and the head hold
  ``vocab_size`` rows.
* No multi-token prediction head (V2-Lite has none).

Imports nothing of the program: it reads weights by name from a dict and
sizes from the configuration file's published keys.  Runs one layer at a
time, attention one sequence at a time, so that it fits beside nothing
else on the device.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import einsum, layer_slice, rms_norm

# published key -> the program's registry attribute of the same quantity
REGISTRY_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "dense_d_ff",
    "moe_intermediate_size": "moe_d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "num_hidden_layers": "n_layers",
    "first_k_dense_replace": "first_k_dense",
    "n_routed_experts": "experts_held",
    "router_experts": "n_experts",
    "num_experts_per_tok": "experts_per_token",
    "n_shared_experts": "n_shared_experts",
    "norm_topk_prob": "norm_topk_prob",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "rope_scaling": "rope_scaling",
    "max_position_embeddings": "max_seq_len",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias",
    "hidden_act": "mlp_act",
}

# published keys this reference computes only at these values
COVERED = {"q_lora_rank": None, "scoring_func": "softmax",
           "topk_method": "greedy", "n_group": 1, "topk_group": 1,
           "moe_layer_freq": 1, "hidden_act": "silu",
           "attention_bias": False, "tie_word_embeddings": False}


def _yarn_get_mscale(scale: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def rotary(cfg: dict):
    """(inv_freq (rope/2,) float32, cos/sin scale, softmax scale)."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    exps = np.arange(0, dim, 2, dtype=np.float32) / dim
    freq_extra = 1.0 / base ** exps
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs is None:
        return freq_extra, 1.0, scale
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling {rs['type']!r} is not covered")
    factor = rs["factor"]
    freq_inter = 1.0 / (factor * base ** exps)

    def corr_dim(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - mask) + freq_extra * mask
    cs = (_yarn_get_mscale(factor, rs["mscale"])
          / _yarn_get_mscale(factor, rs["mscale_all_dim"]))
    if rs["mscale_all_dim"]:
        scale *= _yarn_get_mscale(factor, rs["mscale_all_dim"]) ** 2
    return inv_freq.astype(np.float32), float(cs), float(scale)


def _rope(x, inv_freq, cs):
    """x: (S, H, D) at positions 0..S-1, pairs interleaved as published."""
    S, H, D = x.shape
    freqs = jnp.outer(jnp.arange(S, dtype=jnp.float32), inv_freq)
    emb = jnp.concatenate([freqs, freqs], -1)
    cos, sin = jnp.cos(emb)[:, None] * cs, jnp.sin(emb)[:, None] * cs
    x = x.reshape(S, H, D // 2, 2).transpose(0, 1, 3, 2).reshape(S, H, D)
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + rot * sin


def _attend(x, a, inv_freq, *, heads, nope, rope, dv, rank, cs, scale, mode):
    """One sequence's attention output, x: (S, d) normed."""
    S = x.shape[0]
    q = einsum(mode, "sd,de->se", x, a["w_q"]).reshape(S, heads, nope + rope)
    ckv = einsum(mode, "sd,de->se", x, a["w_kv_a"])
    c = rms_norm(ckv[:, :rank], a["kv_norm"]["scale"], 1e-6)
    kv = einsum(mode, "sr,re->se", c, a["w_kv_b"]).reshape(S, heads,
                                                           nope + dv)
    q_pe = _rope(q[..., nope:], inv_freq, cs)
    k_pe = _rope(ckv[:, None, rank:], inv_freq, cs)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (S, heads, rope))], -1)
    s = einsum(mode, "qhd,khd->hqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = einsum(mode, "hqk,khd->qhd", p, kv[..., nope:]).reshape(S, heads * dv)
    return einsum(mode, "se,ed->sd", o, a["w_o"])


def _mlp(mode, x, m):
    g = einsum(mode, "td,df->tf", x, m["w_gate"])
    u = einsum(mode, "td,df->tf", x, m["w_up"])
    return einsum(mode, "tf,fd->td", jax.nn.silu(g) * u, m["w_down"])


def moe(x, m, *, held, top_k, norm_topk, scaling, mode):
    """x: (T, d) normed -> the held experts' part plus the shared experts."""
    logits = einsum(mode, "td,de->te", x, m["w_router"])
    scores = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(scores, top_k)
    if norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    else:
        w = w * scaling
    gate = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None],
                                     idx].set(w)
    y = _mlp(mode, x, m["shared"])
    for e in range(held):
        ex = {k: m[k][e] for k in ("w_gate", "w_up", "w_down")}
        y = y + gate[:, e:e + 1] * _mlp(mode, x, ex)
    return y


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "dv", "rank", "cs", "scale", "eps", "dense",
    "held", "top_k", "norm_topk", "scaling", "mode"))
def _layer(h, lp, inv_freq, *, heads, nope, rope, dv, rank, cs, scale, eps,
           dense, held, top_k, norm_topk, scaling, mode):
    B, S, d = h.shape
    attend = functools.partial(_attend, a=lp["attn"], inv_freq=inv_freq,
                               heads=heads, nope=nope, rope=rope, dv=dv,
                               rank=rank, cs=cs, scale=scale, mode=mode)
    h = h + jax.lax.map(attend, rms_norm(h, lp["ln1"]["scale"], eps))
    x = rms_norm(h, lp["ln2"]["scale"], eps).reshape(B * S, d)
    if dense:
        y = _mlp(mode, x, lp["mlp"])
    else:
        y = moe(x, lp["moe"], held=held, top_k=top_k, norm_topk=norm_topk,
                scaling=scaling, mode=mode)
    return h + y.reshape(B, S, d)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(h_last, norm_w, unembed, *, eps, mode):
    x = rms_norm(h_last, norm_w, eps)
    return einsum(mode, "bd,dv->bv", x, unembed)


def layer_kw(cfg: dict, mode: str = "f32") -> dict:
    for key, want in COVERED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key}={cfg[key]!r} is not covered")
    _, cs, scale = rotary(cfg)
    return dict(heads=cfg["num_attention_heads"],
                nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
                dv=cfg["v_head_dim"], rank=cfg["kv_lora_rank"], cs=cs,
                scale=scale, eps=float(cfg["rms_norm_eps"]),
                held=cfg["n_routed_experts"],
                top_k=cfg["num_experts_per_tok"],
                norm_topk=bool(cfg["norm_topk_prob"]),
                scaling=float(cfg["routed_scaling_factor"]), mode=mode)


def last_logits(params: dict, cfg: dict, tokens, mode: str = "f32"):
    """Logits (B, V) float32 at the last position of ``tokens`` (B, S)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    h = params["embed"][tokens].astype(jnp.float32)
    inv_freq, _, _ = rotary(cfg)
    kw = layer_kw(cfg, mode)
    n_dense = cfg["first_k_dense_replace"]
    for i in range(cfg["num_hidden_layers"]):
        lp = (params["first_layers"][i] if i < n_dense
              else layer_slice(params["layers"], i - n_dense))
        h = _layer(h, lp, inv_freq, dense=i < n_dense, **kw)
    out = _head(h[:, -1], params["final_norm"]["scale"], params["w_unembed"],
                eps=kw["eps"], mode=mode)
    return np.asarray(out)
