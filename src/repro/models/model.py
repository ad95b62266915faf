"""Unified model facade: family dispatch, loss, serving and input specs.

``build_model(cfg, ec)`` returns a :class:`Model` whose methods are pure
functions of (params, inputs) — suitable for jit/pjit, ``jax.eval_shape`` and
the multi-pod dry-run (``input_specs`` produces ShapeDtypeStruct stand-ins
for every model input, with no device allocation).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.execution import ExecConfig, DEFAULT_EXEC
from repro.models import encdec, ssm_stack, transformer
from repro.models import layers as L

_FAMILY_MODULES = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "ssm": ssm_stack,
    "hybrid": ssm_stack,
    "encdec": encdec,
}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    ec: ExecConfig

    @property
    def _mod(self):
        return _FAMILY_MODULES[self.cfg.family]

    # -- construction ----------------------------------------------------------
    def init(self, rng):
        return self._mod.init_params(rng, self.cfg)

    def init_shapes(self, rng=None):
        """Parameter ShapeDtypeStructs without allocating (for the dry-run)."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(self.init, rng)

    # -- training ----------------------------------------------------------------
    def loss(self, params, batch):
        """(loss, metrics) for a train batch."""
        return self._mod.forward_train(params, self.cfg, self.ec, batch)

    def logits(self, params, tokens, extra=None):
        if self.cfg.family == "encdec":
            return self._mod.forward_logits(params, self.cfg, self.ec, tokens,
                                            extra)
        if self.cfg.family == "vlm":
            return self._mod.forward_logits(params, self.cfg, self.ec, tokens,
                                            extra)
        return self._mod.forward_logits(params, self.cfg, self.ec, tokens)

    def routed_logits(self, params, tokens):
        """(logits, rows): ``rows`` (n_moe_layers, experts_held) int32, the
        token slots routed to each held expert in each MoE layer."""
        return self._mod.forward_logits_routed(params, self.cfg, self.ec,
                                               tokens)

    # -- serving -----------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int):
        return self._mod.init_cache(self.cfg, batch, max_len)

    def prefill(self, params, tokens, cache, extra=None):
        """Returns (last-token logits, cache, prefix_len)."""
        if self.cfg.family == "encdec":
            return self._mod.prefill(params, self.cfg, self.ec, tokens, cache,
                                     frames=extra)
        if self.cfg.family == "vlm":
            return self._mod.prefill(params, self.cfg, self.ec, tokens, cache,
                                     image_embeds=extra)
        return self._mod.prefill(params, self.cfg, self.ec, tokens, cache)

    def decode_step(self, params, token, cache, index):
        """One serve step: (logits (B,V), new cache)."""
        return self._mod.decode_step(params, self.cfg, self.ec, token, cache,
                                     index)

    # -- dry-run input specs --------------------------------------------------------
    def cache_specs(self, batch: int, max_len: int):
        return jax.eval_shape(lambda: self.init_cache(batch, max_len))

    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every input of the step this shape
        lowers (train_step for "train", prefill/serve for the others)."""
        cfg = self.cfg
        GB, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        f = jnp.dtype(cfg.dtype)
        sds = jax.ShapeDtypeStruct

        def text_len():
            if cfg.family == "vlm":
                return S - cfg.n_image_tokens
            return S

        if shape.kind == "train":
            St = text_len()
            specs = {"tokens": sds((GB, St), i32),
                     "targets": sds((GB, St), i32),
                     "mask": sds((GB, St), jnp.float32)}
            if cfg.family == "vlm":
                specs["image_embeds"] = sds((GB, cfg.n_image_tokens, cfg.d_model), f)
            if cfg.family == "encdec":
                specs["frames"] = sds((GB, cfg.n_frames, cfg.d_model), f)
            return specs

        if shape.kind == "prefill":
            St = text_len()
            specs = {"tokens": sds((GB, St), i32)}
            if cfg.family == "vlm":
                specs["image_embeds"] = sds((GB, cfg.n_image_tokens, cfg.d_model), f)
            if cfg.family == "encdec":
                specs["frames"] = sds((GB, cfg.n_frames, cfg.d_model), f)
            specs["cache"] = self.cache_specs(GB, S)
            return specs

        # decode: one new token against a cache of seq_len
        return {"token": sds((GB,), i32),
                "index": sds((GB,), i32),
                "cache": self.cache_specs(GB, S)}


def build_model(cfg: ModelConfig, ec: Optional[ExecConfig] = None) -> Model:
    return Model(cfg=cfg, ec=ec or DEFAULT_EXEC)
