"""Decoder-only transformer: dense, MoE and VLM families.

Layers are stored *stacked* (leading L axis on every leaf) and executed with
``lax.scan`` so even the 61-layer / 1T-param kimi-k2 config lowers to compact
HLO.  MoE archs with ``first_k_dense`` leading dense layers keep those layers
unrolled (param structure differs) and scan the homogeneous MoE remainder.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.execution import ExecConfig
from repro.models import layers as L
from repro.models.attention import (attn_apply_decode, attn_apply_full,
                                    attn_apply_prefill, attn_init)
from repro.models.moe import moe_apply, moe_init


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def dense_block_init(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    ks = jax.random.split(key, 2)
    return {"ln1": L.norm_init(cfg), "attn": attn_init(ks[0], cfg),
            "ln2": L.norm_init(cfg), "mlp": L.mlp_init(ks[1], cfg, d_ff)}


def moe_block_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    return {"ln1": L.norm_init(cfg), "attn": attn_init(ks[0], cfg),
            "ln2": L.norm_init(cfg), "moe": moe_init(ks[1], cfg)}


def _ffn(lp, cfg, ec, h, train):
    """Second half of a block: returns (delta, aux, rows), ``rows`` the
    token slots routed to each held expert (None for a dense MLP)."""
    x = L.norm_apply(lp["ln2"], cfg, h)
    if "moe" in lp:
        return moe_apply(lp["moe"], cfg, ec, x, train=train)
    return L.mlp_apply(lp["mlp"], cfg, x), jnp.zeros((), jnp.float32), None


def block_full(lp, cfg: ModelConfig, ec: ExecConfig, h, positions=None,
               train: bool = True):
    h = h + attn_apply_full(lp["attn"], cfg, ec,
                            L.norm_apply(lp["ln1"], cfg, h), positions=positions)
    delta, aux, rows = _ffn(lp, cfg, ec, h, train)
    return h + delta, aux, rows


def block_prefill(lp, cfg, ec, h, ck, cv, positions=None):
    a, ck, cv = attn_apply_prefill(lp["attn"], cfg, ec,
                                   L.norm_apply(lp["ln1"], cfg, h), ck, cv,
                                   positions=positions)
    h = h + a
    delta, _, _ = _ffn(lp, cfg, ec, h, train=False)
    return h + delta, ck, cv


def block_decode(lp, cfg, ec, h, ck, cv, index):
    a, ck, cv = attn_apply_decode(lp["attn"], cfg, ec,
                                  L.norm_apply(lp["ln1"], cfg, h), ck, cv, index)
    h = h + a
    delta, _, _ = _ffn(lp, cfg, ec, h, train=False)
    return h + delta, ck, cv


def _maybe_remat(fn, ec: ExecConfig):
    if ec.remat == "none":
        return fn
    if ec.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    params = L.embed_init(ks[0], cfg)
    n_first = cfg.first_k_dense if cfg.n_experts else 0
    first = []
    for i in range(n_first):
        first.append(dense_block_init(jax.random.fold_in(ks[1], i), cfg,
                                      d_ff=cfg.dense_d_ff or cfg.d_ff))
    if first:
        params["first_layers"] = first
    n_scan = cfg.n_layers - n_first
    layer_init = (functools.partial(moe_block_init, cfg=cfg) if cfg.n_experts
                  else functools.partial(dense_block_init, cfg=cfg))
    params["layers"] = jax.vmap(lambda k: layer_init(k))(
        jax.random.split(ks[2], n_scan))
    params["final_norm"] = L.norm_init(cfg)
    return params


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg: ModelConfig, tokens, image_embeds=None):
    h = L.embed_apply(params, cfg, tokens)
    if cfg.family == "vlm":
        assert image_embeds is not None, "vlm needs stubbed patch embeddings"
        h = jnp.concatenate([image_embeds.astype(h.dtype), h], axis=1)
    return h


def forward_hidden(params, cfg: ModelConfig, ec: ExecConfig, tokens,
                   image_embeds=None, train: bool = True):
    """Returns (h (B, S_total, d) post-final-norm, aux_loss, rows): rows
    (n_moe_layers, experts_held) int32, the token slots routed to each held
    expert in each scanned MoE layer, or None without routed experts."""
    h = _embed_inputs(params, cfg, tokens, image_embeds)
    S = h.shape[1]
    positions = jnp.arange(S) if cfg.use_rope else None
    aux = jnp.zeros((), jnp.float32)
    for lp in params.get("first_layers", []):
        h2, a, _ = block_full(lp, cfg, ec, h, positions, train)
        h, aux = h2, aux + a

    def body(carry, lp):
        h, aux = carry
        if train and ec.shard_activations:
            h = L.seq_shard_constraint(h)
        h2, a, rows = block_full(lp, cfg, ec, h, positions, train)
        return (h2, aux + a), rows

    if train:
        body = _maybe_remat(body, ec)
    if ec.scan_layers:
        (h, aux), rows = jax.lax.scan(body, (h, aux), params["layers"])
    else:
        n = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
        per_layer = []
        for i in range(n):
            lp = jax.tree.map(lambda x: x[i], params["layers"])
            (h, aux), r = body((h, aux), lp)
            per_layer.append(r)
        rows = None if per_layer[0] is None else jnp.stack(per_layer)
    return L.norm_apply(params["final_norm"], cfg, h), aux, rows


def forward_train(params, cfg: ModelConfig, ec: ExecConfig, batch):
    """batch: tokens/targets/mask (+image_embeds).  Returns (loss, metrics)."""
    h, aux, _ = forward_hidden(params, cfg, ec, batch["tokens"],
                               batch.get("image_embeds"), train=True)
    if cfg.family == "vlm":
        h = h[:, cfg.n_image_tokens:]            # loss only over text positions
    loss = L.chunked_loss(params, cfg, h, batch["targets"], batch["mask"],
                          ec.loss_chunk)
    total = loss + aux
    return total, {"loss": loss, "aux_loss": aux}


def forward_logits_routed(params, cfg: ModelConfig, ec: ExecConfig, tokens,
                          image_embeds=None):
    """(logits, rows): the logits and, beside them, the token slots routed
    to each held expert in each MoE layer (``forward_hidden``)."""
    h, _, rows = forward_hidden(params, cfg, ec, tokens, image_embeds,
                                train=False)
    return L.logits_apply(params, cfg, h, f32=ec.logits_f32), rows


def forward_logits(params, cfg: ModelConfig, ec: ExecConfig, tokens,
                   image_embeds=None):
    return forward_logits_routed(params, cfg, ec, tokens, image_embeds)[0]


# ---------------------------------------------------------------------------
# KV-cache serving
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Per layer ``k`` and ``v`` (B, max_len, K, D); under latent attention
    ``k`` holds the latent (B, max_len, kv_lora_rank) and ``v`` the shared
    rope key (B, max_len, qk_rope_head_dim)."""
    n_first = cfg.first_k_dense if cfg.n_experts else 0
    n_scan = cfg.n_layers - n_first
    if cfg.kv_lora_rank:
        widths = (cfg.kv_lora_rank,), (cfg.qk_rope_head_dim,)
    else:
        widths = ((cfg.n_kv_heads, cfg.head_dim),) * 2
    kv = lambda n, w: jnp.zeros((n, batch, max_len) + w, L.dt(cfg.dtype))
    cache = {"k": kv(n_scan, widths[0]), "v": kv(n_scan, widths[1])}
    if n_first:
        cache["first_k"] = kv(n_first, widths[0])
        cache["first_v"] = kv(n_first, widths[1])
    return cache


def prefill(params, cfg: ModelConfig, ec: ExecConfig, tokens, cache,
            image_embeds=None):
    """Left-aligned prefill.  Returns (last-token logits, cache, seq_len)."""
    cache = dict(cache)
    h = _embed_inputs(params, cfg, tokens, image_embeds)
    S = h.shape[1]
    positions = jnp.arange(S) if cfg.use_rope else None
    for i, lp in enumerate(params.get("first_layers", [])):
        h, ck, cv = block_prefill(lp, cfg, ec, h, cache["first_k"][i],
                                  cache["first_v"][i], positions)
        cache["first_k"] = cache["first_k"].at[i].set(ck)
        cache["first_v"] = cache["first_v"].at[i].set(cv)

    def body(h, xs):
        lp, ck, cv = xs
        if ec.shard_activations:
            h = L.seq_shard_constraint(h)
        h, ck, cv = block_prefill(lp, cfg, ec, h, ck, cv, positions)
        return h, (ck, cv)

    h, (ck, cv) = jax.lax.scan(body, h, (params["layers"], cache["k"], cache["v"]))
    cache = dict(cache, k=ck, v=cv)
    h = L.norm_apply(params["final_norm"], cfg, h)
    logits = L.logits_apply(params, cfg, h[:, -1:], f32=ec.logits_f32)[:, 0]
    return logits, cache, S


def decode_step(params, cfg: ModelConfig, ec: ExecConfig, token, cache, index):
    """One serve step.  token: (B,) int32; index: (B,) position of this token.

    Returns (logits (B, V), new cache)."""
    cache = dict(cache)
    h = L.embed_apply(params, cfg, token[:, None])
    for i, lp in enumerate(params.get("first_layers", [])):
        h, ck, cv = block_decode(lp, cfg, ec, h, cache["first_k"][i],
                                 cache["first_v"][i], index)
        cache["first_k"] = cache["first_k"].at[i].set(ck)
        cache["first_v"] = cache["first_v"].at[i].set(cv)

    def body(h, xs):
        lp, ck, cv = xs
        if ec.shard_activations:
            h = L.seq_shard_constraint(h)
        h, ck, cv = block_decode(lp, cfg, ec, h, ck, cv, index)
        return h, (ck, cv)

    h, (ck, cv) = jax.lax.scan(body, h, (params["layers"], cache["k"], cache["v"]))
    cache = dict(cache, k=ck, v=cv)
    h = L.norm_apply(params["final_norm"], cfg, h)
    logits = L.logits_apply(params, cfg, h, f32=ec.logits_f32)[:, 0]
    return logits, cache
