"""Shared neural-net layers: norms, RoPE, MLPs, embeddings, chunked loss."""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, YarnScaling


def dt(cfg_dtype: str):
    return jnp.dtype(cfg_dtype)


def trunc_normal(key, shape, std: float, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(cfg: ModelConfig, dim: Optional[int] = None):
    d = dim or cfg.d_model
    p = {"scale": jnp.ones((d,), dt(cfg.param_dtype))}
    if cfg.norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), dt(cfg.param_dtype))
    return p


def norm_apply(p, cfg: ModelConfig, x):
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        mean = xf.mean(-1, keepdims=True)
        var = ((xf - mean) ** 2).mean(-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_head_norm(scale, x, eps: float):
    """Per-head RMSNorm over the last (head_dim) axis — Qwen3 qk_norm."""
    xf = x.astype(jnp.float32)
    ms = (xf * xf).mean(-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (GPT-NeoX rotate-half convention)
# ---------------------------------------------------------------------------

def rope_apply(x, positions, theta: float):
    """x: (B, S, H, D); positions: (S,) or (B, S) absolute positions."""
    B, S, H, D = x.shape
    half = D // 2
    inv_freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    pos = positions.astype(jnp.float32)
    if pos.ndim == 1:
        pos = pos[None, :]                                   # (1, S)
    ang = pos[..., None] * inv_freq                           # (B?, S, half)
    cos = jnp.cos(ang)[:, :, None, :]                         # (B?, S, 1, half)
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# YaRN rope on interleaved channel pairs (DeepSeek-V2)
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, yarn: Optional[YarnScaling]):
    """(dim // 2,) inverse frequencies: plain rope's, or with ``yarn`` those
    of DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` — each interpolated
    by ``factor`` below the band that ``beta_fast``..``beta_slow`` turns
    within the original window mark as high-frequency, blended linearly
    across it."""
    extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if yarn is None:
        return extra

    def corr(rot):
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rot * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / yarn.factor * ramp + extra * (1.0 - ramp)


def rope_apply_interleaved(x, positions, inv_freq):
    """DeepSeek-V2's rope: x (B, S, H, D) holds its pairs interleaved
    (x0, x1), (x2, x3), ...; they are regrouped as [x0, x2, ..., x1, x3,
    ...] and rotated in the rotate-half convention, so the result is in
    that regrouped order.  positions: (S,) or (B, S)."""
    B, S, H, D = x.shape
    x = x.astype(jnp.float32).reshape(B, S, H, D // 2, 2).swapaxes(-1, -2)
    x1, x2 = x[..., 0, :], x[..., 1, :]
    pos = positions.astype(jnp.float32)
    if pos.ndim == 1:
        pos = pos[None, :]
    ang = pos[..., None] * inv_freq                           # (B?, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def seq_shard_constraint(h, wide: bool = False):
    """Activation-sharding constraint for the residual stream inside layer
    scans.  Without it GSPMD is free to pick a replicated sharding for the
    scan carry (observed: the whole batch landing on every chip).

    ``wide=False`` (attention archs): batch over (pod, data), sequence over
    model (Megatron-SP) — cuts per-layer saved-residual memory by the model
    axis.  ``wide=True`` (SSM/hybrid): batch over every axis that divides
    (pure DP).  No-op outside a mesh context or when dims don't divide."""
    try:
        from jax._src import mesh as _mesh_lib
        mesh = _mesh_lib.thread_resources.env.physical_mesh
        if mesh is None or mesh.empty:
            mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or not getattr(mesh, "shape_tuple", ()):
            return h
        ax = dict(mesh.shape_tuple)
        if h.ndim != 3:
            return h
        b_axes = []
        rem = h.shape[0]
        batch_pool = ("pod", "data", "model") if wide else ("pod", "data")
        for a in batch_pool:
            if a in ax and rem % ax[a] == 0:
                rem //= ax[a]
                b_axes.append(a)
        seq_ax = None
        if (not wide and "model" in ax and "model" not in b_axes
                and h.shape[1] % ax["model"] == 0):
            seq_ax = "model"
        from jax.sharding import PartitionSpec
        spec = PartitionSpec(tuple(b_axes) if b_axes else None, seq_ax, None)
        return jax.lax.with_sharding_constraint(h, spec)
    except Exception:
        return h


def sinusoidal_positions(n: int, d: int):
    """Whisper-style fixed sinusoidal embeddings (n, d)."""
    half = d // 2
    inv = jnp.exp(-jnp.log(10_000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = jnp.arange(n)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=1)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    pdt = dt(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    std_in, std_out = d ** -0.5, f ** -0.5
    if cfg.mlp_act == "silu":
        p = {"w_gate": trunc_normal(ks[0], (d, f), std_in, pdt),
             "w_up": trunc_normal(ks[1], (d, f), std_in, pdt),
             "w_down": trunc_normal(ks[2], (f, d), std_out, pdt)}
    else:
        p = {"w_up": trunc_normal(ks[0], (d, f), std_in, pdt),
             "w_down": trunc_normal(ks[1], (f, d), std_out, pdt)}
    if cfg.mlp_bias:
        p["b_up"] = jnp.zeros((f,), pdt)
        p["b_down"] = jnp.zeros((d,), pdt)
    return p


def mlp_apply(p, cfg: ModelConfig, x):
    if cfg.mlp_act == "silu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = x @ p["w_up"]
        if "b_up" in p:
            h = h + p["b_up"]
        h = jax.nn.gelu(h)
    y = h @ p["w_down"]
    if "b_down" in p:
        y = y + p["b_down"]
    return y


# ---------------------------------------------------------------------------
# Embedding / unembedding with chunked fused loss
# ---------------------------------------------------------------------------

def embed_init(key, cfg: ModelConfig):
    pdt = dt(cfg.param_dtype)
    p = {"embed": trunc_normal(key, (cfg.vocab_size, cfg.d_model), 0.02, pdt)}
    if not cfg.tie_embeddings:
        p["w_unembed"] = trunc_normal(jax.random.fold_in(key, 1),
                                    (cfg.d_model, cfg.vocab_size),
                                    cfg.d_model ** -0.5, pdt)
    return p


def embed_apply(p, cfg: ModelConfig, tokens):
    return p["embed"][tokens].astype(dt(cfg.dtype))


def unembed_matrix(p, cfg: ModelConfig):
    return p["embed"].T if cfg.tie_embeddings else p["w_unembed"]


def logits_apply(p, cfg: ModelConfig, h, f32: bool = True):
    w = unembed_matrix(p, cfg)
    logits = h @ w.astype(h.dtype)
    return logits.astype(jnp.float32) if f32 else logits


def softmax_xent(logits, targets, mask):
    """Mean masked cross-entropy.  logits: (..., V) f32; targets int; mask {0,1}."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    return nll.sum(), mask.sum()


def chunked_loss(p, cfg: ModelConfig, h, targets, mask, chunk: int):
    """Fused unembed + cross-entropy over sequence chunks.

    Avoids materialising the full (B, S, V) logit tensor — the chunk of logits
    lives only inside one scan step (then is recomputed in the backward pass
    under remat).  h: (B, S, d); targets/mask: (B, S).
    """
    B, S, d = h.shape
    if chunk <= 0 or S <= chunk or S % chunk != 0:
        logits = logits_apply(p, cfg, h)
        nll, denom = softmax_xent(logits, targets, mask)
        return nll / jnp.maximum(denom, 1.0)
    n = S // chunk
    hs = jnp.moveaxis(h.reshape(B, n, chunk, d), 1, 0)
    ts = jnp.moveaxis(targets.reshape(B, n, chunk), 1, 0)
    ms = jnp.moveaxis(mask.reshape(B, n, chunk), 1, 0)

    def body(carry, xs):
        h_c, t_c, m_c = xs
        logits = logits_apply(p, cfg, h_c)
        nll, denom = softmax_xent(logits, t_c, m_c)
        return (carry[0] + nll, carry[1] + denom), None

    body = jax.checkpoint(body)
    (nll, denom), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                   (hs, ts, ms))
    return nll / jnp.maximum(denom, 1.0)
