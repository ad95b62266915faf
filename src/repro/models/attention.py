"""Attention layers: init, full-sequence apply, prefill and decode modes.

GQA, and DeepSeek-V2's latent attention (MLA) where ``cfg.kv_lora_rank`` is
set.  Dispatches to the flash-attention / decode-attention kernel packages.
GQA KV caches are (B, S_max, K, D) per layer; MLA caches the latent, (B,
S_max, kv_lora_rank) and the shared rope key (B, S_max, qk_rope_head_dim).
Decode writes the new token's entry at per-sequence positions via scatter
(sequences in a serving batch have different lengths — the Faasm serving
runtime batches unrelated requests).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.execution import ExecConfig
from repro.models.layers import (dt, rms_head_norm, rope_apply,
                                 rope_apply_interleaved, trunc_normal,
                                 yarn_inv_freq, yarn_mscale)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.decode_attention import decode_attention


def attn_init(key, cfg: ModelConfig):
    if cfg.kv_lora_rank:
        return mla_init(key, cfg)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    pdt = dt(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    std = d ** -0.5
    p = {
        "wq": trunc_normal(ks[0], (d, qd), std, pdt),
        "wk": trunc_normal(ks[1], (d, kvd), std, pdt),
        "wv": trunc_normal(ks[2], (d, kvd), std, pdt),
        "wo": trunc_normal(ks[3], (qd, d), qd ** -0.5, pdt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((qd,), pdt)
        p["bk"] = jnp.zeros((kvd,), pdt)
        p["bv"] = jnp.zeros((kvd,), pdt)
    if cfg.o_bias:
        p["bo"] = jnp.zeros((d,), pdt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), pdt)
        p["k_norm"] = jnp.ones((cfg.head_dim,), pdt)
    return p


def _project_qkv(p, cfg: ModelConfig, x, positions):
    """x: (B, S, d) -> q (B,S,H,D), k/v (B,S,K,D) with rope + qk-norm applied."""
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_head_norm(p["k_norm"], k, cfg.norm_eps)
    if cfg.use_rope and positions is not None:
        q = rope_apply(q, positions, cfg.rope_theta)
        k = rope_apply(k, positions, cfg.rope_theta)
    return q, k, v


def _out_proj(p, y, B, S, cfg):
    out = y.reshape(B, S, cfg.q_dim) @ p["wo"]
    if cfg.o_bias:
        out = out + p["bo"]
    return out


def attn_apply_full(p, cfg: ModelConfig, ec: ExecConfig, x, *,
                    positions=None, causal=True) -> jnp.ndarray:
    """Full-sequence attention (training / encoder).  x: (B, S, d)."""
    B, S, _ = x.shape
    if positions is None and cfg.use_rope:
        positions = jnp.arange(S)
    if cfg.kv_lora_rank:
        return mla_apply_prefill(p, cfg, ec, x, None, None,
                                 positions=positions)[0]
    q, k, v = _project_qkv(p, cfg, x, positions)
    if not ec.flash_for_prefill:
        y = attention_ref(q, k, v, causal=causal)
    elif causal and ec.attn_buckets > 1 and S % ec.attn_buckets == 0:
        # causal q-bucketing: queries in bucket i only ever see keys in
        # [0, (i+1)·S/nb) — skip the strictly-upper KV blocks entirely.
        # Work factor (nb+1)/(2·nb) of full-rectangle attention.
        nb = ec.attn_buckets
        bs = S // nb
        parts = []
        for i in range(nb):
            parts.append(flash_attention(
                q[:, i * bs:(i + 1) * bs], k[:, :(i + 1) * bs],
                v[:, :(i + 1) * bs], causal=True, q_offset=i * bs,
                backend=ec.backend, block_k=min(ec.attn_block_k, (i + 1) * bs)))
        y = jnp.concatenate(parts, axis=1)
    else:
        y = flash_attention(q, k, v, causal=causal, backend=ec.backend,
                            block_k=ec.attn_block_k)
    return _out_proj(p, y, B, S, cfg)


def attn_apply_prefill(p, cfg: ModelConfig, ec: ExecConfig, x, cache_k, cache_v,
                       *, positions=None) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Prefill: causal attention + write K/V into the cache prefix.

    cache_k/v: (B, S_max, K, D) zero-initialised.  Returns (out, k_cache, v_cache).
    """
    B, S, _ = x.shape
    if positions is None and cfg.use_rope:
        positions = jnp.arange(S)
    if cfg.kv_lora_rank:
        return mla_apply_prefill(p, cfg, ec, x, cache_k, cache_v,
                                 positions=positions)
    q, k, v = _project_qkv(p, cfg, x, positions)
    y = flash_attention(q, k, v, causal=True, backend=ec.backend,
                        block_k=ec.attn_block_k)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                           (0, 0, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                           (0, 0, 0, 0))
    return _out_proj(p, y, B, S, cfg), cache_k, cache_v


def attn_apply_decode(p, cfg: ModelConfig, ec: ExecConfig, x, cache_k, cache_v,
                      index) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step.  x: (B, 1, d); index: (B,) position of the new token.

    Returns (out (B,1,d), new cache_k, new cache_v)."""
    if cfg.kv_lora_rank:
        return mla_apply_decode(p, cfg, ec, x, cache_k, cache_v, index)
    B = x.shape[0]
    positions = index[:, None] if cfg.use_rope else None      # (B, 1)
    q, k, v = _project_qkv(p, cfg, x, positions)
    batch_ix = jnp.arange(B)
    cache_k = cache_k.at[batch_ix, index].set(k[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[batch_ix, index].set(v[:, 0].astype(cache_v.dtype))
    lengths = index + 1
    y = decode_attention(q[:, 0], cache_k.astype(q.dtype),
                         cache_v.astype(q.dtype), lengths,
                         backend=ec.backend)
    return _out_proj(p, y[:, None], B, 1, cfg), cache_k, cache_v


# ---------------------------------------------------------------------------
# Latent attention (DeepSeek-V2 MLA, no q-LoRA)
#
#   q = x·W_q: H heads of (nope + rope) channels
#   [c_kv, k_pe] = x·W_kv_a; c_kv RMS-normed (the cached latent)
#   [k_nope, v] = c_kv·W_kv_b: H heads of (nope + v) channels
#   rope (YaRN, interleaved pairs) on q's rope channels and on k_pe, one
#   vector shared by every head; o = softmax(q·k·scale)·v·W_o
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig):
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    pdt = dt(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    return {
        "w_q": trunc_normal(ks[0], (d, H * (nope + rope)), d ** -0.5, pdt),
        "w_kv_a": trunc_normal(ks[1], (d, r + rope), d ** -0.5, pdt),
        "kv_norm": {"scale": jnp.ones((r,), pdt)},
        "w_kv_b": trunc_normal(ks[2], (r, H * (nope + dv)), r ** -0.5, pdt),
        "w_o": trunc_normal(ks[3], (H * dv, d), (H * dv) ** -0.5, pdt),
    }


def mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale: (nope + rope)^-0.5, times YaRN's mscale squared."""
    s = cfg.head_dim ** -0.5
    if cfg.yarn is not None and cfg.yarn.mscale_all_dim:
        s *= yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim) ** 2
    return s


def _mla_rope(cfg: ModelConfig, x, positions):
    inv = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.yarn)
    return rope_apply_interleaved(x, positions, inv).astype(x.dtype)


def _mla_latent(p, cfg: ModelConfig, x, positions):
    """x (B, S, d) -> the cached latent (B, S, r), normed, and the rotated
    shared rope key (B, S, rope)."""
    r = cfg.kv_lora_rank
    kv = x @ p["w_kv_a"]
    c = rms_head_norm(p["kv_norm"]["scale"], kv[..., :r], cfg.norm_eps)
    k_pe = _mla_rope(cfg, kv[..., None, r:], positions)[:, :, 0]
    return c, k_pe


def _mla_q(p, cfg: ModelConfig, x, positions):
    """(q_nope (B, S, H, nope), q_pe (B, S, H, rope), rotated)."""
    B, S, _ = x.shape
    nope = cfg.qk_nope_head_dim
    q = (x @ p["w_q"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    return q[..., :nope], _mla_rope(cfg, q[..., nope:], positions)


def mla_apply_prefill(p, cfg: ModelConfig, ec: ExecConfig, x, cache_c,
                      cache_pe, *, positions):
    """Causal MLA over x (B, S, d) through the flash kernel (q and k of
    nope + rope channels, v of v_head_dim); with caches given, the latent
    and rope key are written into their prefix.  Returns (out, cache_c,
    cache_pe)."""
    B, S, _ = x.shape
    H, nope, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    with jax.named_scope("mla"):
        c, k_pe = _mla_latent(p, cfg, x, positions)
        q_nope, q_pe = _mla_q(p, cfg, x, positions)
        kv = (c @ p["w_kv_b"]).reshape(B, S, H, nope + dv)
        q = jnp.concatenate([q_nope, q_pe], -1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_pe[:, :, None], (B, S, H, cfg.qk_rope_head_dim))], -1)
        y = flash_attention(q, k, kv[..., nope:], causal=True,
                            scale=mla_scale(cfg), backend=ec.backend,
                            block_k=ec.attn_block_k)
        out = y.reshape(B, S, H * dv) @ p["w_o"]
    if cache_c is not None:
        cache_c = jax.lax.dynamic_update_slice(
            cache_c, c.astype(cache_c.dtype), (0, 0, 0))
        cache_pe = jax.lax.dynamic_update_slice(
            cache_pe, k_pe.astype(cache_pe.dtype), (0, 0, 0))
    return out, cache_c, cache_pe


def mla_apply_decode(p, cfg: ModelConfig, ec: ExecConfig, x, cache_c,
                     cache_pe, index):
    """One decode step against the latent cache, with W_kv_b absorbed: the
    query is taken into the latent space and the weighted latent back out
    through v's half of W_kv_b, so no head's k or v is formed."""
    B = x.shape[0]
    H, r = cfg.n_heads, cfg.kv_lora_rank
    nope, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    positions = index[:, None]
    f32 = jnp.float32
    with jax.named_scope("mla"):
        c, k_pe = _mla_latent(p, cfg, x, positions)
        batch_ix = jnp.arange(B)
        cache_c = cache_c.at[batch_ix, index].set(c[:, 0].astype(cache_c.dtype))
        cache_pe = cache_pe.at[batch_ix, index].set(
            k_pe[:, 0].astype(cache_pe.dtype))
        q_nope, q_pe = _mla_q(p, cfg, x, positions)
        w = p["w_kv_b"].reshape(r, H, nope + dv).astype(f32)
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0].astype(f32),
                           w[..., :nope])
        s = (jnp.einsum("bhr,btr->bht", q_lat, cache_c.astype(f32))
             + jnp.einsum("bhe,bte->bht", q_pe[:, 0].astype(f32),
                          cache_pe.astype(f32))) * mla_scale(cfg)
        live = jnp.arange(cache_c.shape[1])[None, :] <= index[:, None]
        s = jnp.where(live[:, None, :], s, -jnp.inf)
        o_lat = jnp.einsum("bht,btr->bhr", jax.nn.softmax(s, axis=-1),
                           cache_c.astype(f32))
        y = jnp.einsum("bhr,rhv->bhv", o_lat, w[..., nope:])
        out = y.reshape(B, 1, H * dv).astype(x.dtype) @ p["w_o"]
    return out, cache_c, cache_pe


# ---------------------------------------------------------------------------
# Cross-attention (encoder/decoder)
# ---------------------------------------------------------------------------

def cross_attn_init(key, cfg: ModelConfig):
    return attn_init(key, cfg)


def cross_attn_precompute(p, cfg: ModelConfig, enc_out):
    """Compute K/V over encoder output once per request.  enc_out: (B, F, d)."""
    B, F, _ = enc_out.shape
    k = enc_out @ p["wk"]
    v = enc_out @ p["wv"]
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    k = k.reshape(B, F, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, F, cfg.n_kv_heads, cfg.head_dim)
    return k, v


def cross_attn_apply(p, cfg: ModelConfig, ec: ExecConfig, x, ck, cv):
    """Decoder cross-attention (no masking).  x: (B, S, d); ck/cv: (B, F, K, D)."""
    B, S, _ = x.shape
    q = x @ p["wq"]
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    y = flash_attention(q, ck.astype(q.dtype), cv.astype(q.dtype), causal=False,
                        backend=ec.backend, block_k=ec.attn_block_k)
    return _out_proj(p, y, B, S, cfg)
