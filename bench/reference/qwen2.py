"""Plain float32 reference of the Qwen2 architecture (Qwen1.5 checkpoints).

Follows the published ``Qwen2ForCausalLM``: token embedding; per layer
RMSNorm, attention with q/k/v biases, rotary embeddings in the rotate-half
convention and causal softmax, output projection without bias, RMSNorm and a
SiLU-gated MLP, each added to the residual; final RMSNorm; logits against
the tied embedding.  Imports nothing of the program: it reads weights by
name from a dict and sizes from the configuration file's published keys.
Runs one layer at a time so that only one layer's weights are in float32
at once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import einsum, layer_slice, rms_norm

# published key -> the program's registry attribute of the same quantity
REGISTRY_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


def _rope(x, theta: float):
    """x: (B, S, H, D); rotate-half rotary embedding at positions 0..S-1."""
    S, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "theta", "mode"))
def _layer(h, lp, *, heads, kv_heads, eps, theta, mode):
    B, S, d = h.shape
    D = d // heads
    f32 = lambda a: a.astype(jnp.float32)
    x = rms_norm(h, lp["ln1"]["scale"], eps)
    a = lp["attn"]
    q = einsum(mode, "bsd,de->bse", x, a["wq"]) + f32(a["bq"])
    k = einsum(mode, "bsd,de->bse", x, a["wk"]) + f32(a["bk"])
    v = einsum(mode, "bsd,de->bse", x, a["wv"]) + f32(a["bv"])
    q = _rope(q.reshape(B, S, heads, D), theta)
    k = _rope(k.reshape(B, S, kv_heads, D), theta)
    v = v.reshape(B, S, kv_heads, D)
    rep = heads // kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = einsum(mode, "bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = einsum(mode, "bhqk,bkhd->bqhd", p, v).reshape(B, S, heads * D)
    h = h + einsum(mode, "bse,ed->bsd", o, a["wo"])
    x = rms_norm(h, lp["ln2"]["scale"], eps)
    m = lp["mlp"]
    g = einsum(mode, "bsd,df->bsf", x, m["w_gate"])
    u = einsum(mode, "bsd,df->bsf", x, m["w_up"])
    return h + einsum(mode, "bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(h_last, norm_w, embed, *, eps, mode):
    x = rms_norm(h_last, norm_w, eps)
    return einsum(mode, "bd,vd->bv", x, embed)


def last_logits(params: dict, cfg: dict, tokens, mode: str = "f32"):
    """Logits (B, V) float32 at the last position of ``tokens`` (B, S)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    h = params["embed"][tokens].astype(jnp.float32)
    kw = dict(heads=cfg["num_attention_heads"],
              kv_heads=cfg["num_key_value_heads"],
              eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
              mode=mode)
    for i in range(cfg["num_hidden_layers"]):
        h = _layer(h, layer_slice(params["layers"], i), **kw)
    out = _head(h[:, -1], params["final_norm"]["scale"], params["embed"],
                eps=kw["eps"], mode=mode)
    return np.asarray(out)
