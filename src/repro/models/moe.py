"""Mixture-of-experts layer (DeepSeek-style fine-grained: shared + routed top-k).

The layer holds the first ``cfg.experts_held`` of the router's
``cfg.n_experts`` experts: one chip's share when ``expert_shards`` chips
share the layer (expert parallelism), all of them otherwise.  It routes
every token over all ``n_experts`` and computes the part of the result
that its own experts give; the shared experts are always computed.  On one
chip the layer runs without the exchange between shards.

Two dispatch implementations:

* ``einsum`` — GShard-style grouped capacity dispatch with one-hot einsums.
  GSPMD-native (experts shard over the ``model`` mesh axis; the partitioner
  inserts the all-to-alls).  Dispatch-einsum FLOPs overhead ≈ group·cf/(3·d_ff)
  — kept small via ``moe_group_size``; visible in the roofline's
  MODEL_FLOPS/HLO_FLOPs ratio and attacked in §Perf.  Tokens past an
  expert's capacity are dropped, so only training takes it
  (``ExecConfig.moe_impl``).
* ``sorted`` — dropless sort-by-expert + grouped matmul (``kernels/moe_gmm``,
  ragged_dot on XLA).  No capacity padding, no dispatch FLOPs.  Every pass
  that does not train takes it: a served token is never dropped.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.execution import ExecConfig
from repro.models.layers import dt, trunc_normal
from repro.kernels.moe_gmm import gmm


def moe_init(key, cfg: ModelConfig):
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.experts_held
    pdt = dt(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    std_in, std_out = d ** -0.5, f ** -0.5
    p = {
        "w_router": trunc_normal(ks[0], (d, cfg.n_experts), std_in,
                                 jnp.float32),
        "w_gate": trunc_normal(ks[1], (E, d, f), std_in, pdt),
        "w_up": trunc_normal(ks[2], (E, d, f), std_in, pdt),
        "w_down": trunc_normal(ks[3], (E, f, d), std_out, pdt),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        kss = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": trunc_normal(kss[0], (d, fs), std_in, pdt),
            "w_up": trunc_normal(kss[1], (d, fs), std_in, pdt),
            "w_down": trunc_normal(kss[2], (fs, d), fs ** -0.5, pdt),
        }
    return p


def router_topk(p, cfg: ModelConfig, x2d) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Softmax over all ``n_experts``, greedy top-k.  x2d: (T, d).
    Returns (gates (T,k) f32, idx (T,k) i32 global expert ids, aux); the
    gates sum to 1 only with ``cfg.norm_topk_prob``."""
    logits = (x2d.astype(jnp.float32) @ p["w_router"]).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss.
    E = cfg.n_experts
    me = probs.mean(axis=0)                                                # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(
        1.0 / idx.size)
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)
    return gates, idx.astype(jnp.int32), aux


def _expert_ffn_dense(p, x_ecd):
    """x: (..., E, C, d) -> gated FFN with per-expert weights."""
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", x_ecd, p["w_gate"])) * \
        jnp.einsum("gecd,edf->gecf", x_ecd, p["w_up"])
    return jnp.einsum("gecf,efd->gecd", h, p["w_down"])


def shared_expert_apply(p, x):
    s = p["shared"]
    h = jax.nn.silu(x @ s["w_gate"]) * (x @ s["w_up"])
    return h @ s["w_down"]


def moe_apply(p, cfg: ModelConfig, ec: ExecConfig, x, *,
              train: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss, rows).

    ``rows`` (experts_held,) int32: the token slots routed to each held
    expert.  A pass that does not train takes the dropless sorted path."""
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    E = cfg.experts_held
    with jax.named_scope("moe.route"):
        gates, idx, aux = router_topk(p, cfg, x2d)
        # slots routed to experts held elsewhere count under E, past ours
        local = jnp.minimum(idx, E)
        rows = jnp.bincount(local.reshape(-1), length=E + 1)[:E]
        rows = rows.astype(jnp.int32)
    with jax.named_scope("moe.experts"):
        if train and ec.moe_impl == "einsum":
            y2d = _moe_einsum(p, cfg, ec, x2d, gates, local)
        else:
            y2d = _moe_sorted(p, cfg, x2d, gates, local, rows)
        if cfg.n_shared_experts:
            y2d = y2d + shared_expert_apply(p, x2d)
    return y2d.reshape(B, S, d), aux, rows


def _moe_einsum(p, cfg: ModelConfig, ec: ExecConfig, x2d, gates, local):
    """GShard grouped capacity dispatch (one-hot einsums).  ``local``: the
    held expert of each slot, ``experts_held`` where none."""
    T, d = x2d.shape
    E, k = cfg.experts_held, cfg.experts_per_token
    Sg = min(ec.moe_group_size, T)
    T_pad = ((T + Sg - 1) // Sg) * Sg
    if T_pad != T:
        x2d = jnp.pad(x2d, ((0, T_pad - T), (0, 0)))
        gates = jnp.pad(gates, ((0, T_pad - T), (0, 0)))
        local = jnp.pad(local, ((0, T_pad - T), (0, 0)), constant_values=E)
    Gg = T_pad // Sg
    C = max(1, int(k * Sg * cfg.capacity_factor / cfg.n_experts))

    # a slot routed to no held expert is an all-zero row: never dispatched
    oh = jax.nn.one_hot(local.reshape(Gg, Sg, k), E, dtype=jnp.float32)
    # slot-major priority: all slot-0 choices first, then slot-1, ...
    ohf = oh.transpose(0, 2, 1, 3).reshape(Gg, k * Sg, E)
    cum = jnp.cumsum(ohf, axis=1) - ohf                      # exclusive
    pos = jnp.sum(cum * ohf, axis=-1)                         # (Gg, k*Sg)
    keep = (pos < C).astype(jnp.float32)
    pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32)        # (Gg, k*Sg, C)
    disp_f = ohf[..., None] * pos_oh[:, :, None, :] * keep[..., None, None]
    # fold the k slots back onto tokens: (Gg, k, Sg, E, C) -> sum over k
    disp = disp_f.reshape(Gg, k, Sg, E, C).sum(axis=1)        # 0/1 (Gg,Sg,E,C)
    gates_f = gates.reshape(Gg, Sg, k).transpose(0, 2, 1).reshape(Gg, k * Sg)
    comb_f = disp_f * gates_f[..., None, None]
    comb = comb_f.reshape(Gg, k, Sg, E, C).sum(axis=1)        # (Gg,Sg,E,C)

    xg = x2d.reshape(Gg, Sg, d)
    cdt = xg.dtype
    expert_in = jnp.einsum("gsec,gsd->gecd", disp.astype(cdt), xg)
    expert_out = _expert_ffn_dense(p, expert_in)
    y = jnp.einsum("gsec,gecd->gsd", comb.astype(cdt), expert_out)
    return y.reshape(T_pad, d)[:T]


def _moe_sorted(p, cfg: ModelConfig, x2d, gates, local, rows):
    """Dropless sorted dispatch + grouped matmul (single-shard layout).
    Slots routed to no held expert sort last, past every group: the grouped
    matmul leaves them zero and their gate is zeroed."""
    T, d = x2d.shape
    E, k = cfg.experts_held, cfg.experts_per_token
    flat_e = local.reshape(-1)                                # (T*k,)
    order = jnp.argsort(flat_e)
    tok = order // k                                          # source token per row
    xs = x2d[tok]                                             # (T*k, d)

    h = jax.nn.silu(gmm(xs, p["w_gate"], rows)) * gmm(xs, p["w_up"], rows)
    out = gmm(h.astype(xs.dtype), p["w_down"], rows)          # (T*k, d)

    w = jnp.where(flat_e[order] < E, gates.reshape(-1)[order], 0.0)
    y = jnp.zeros((T, d), out.dtype).at[tok].add(out * w.astype(out.dtype)[:, None])
    return y
