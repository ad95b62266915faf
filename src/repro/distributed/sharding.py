"""Per-architecture sharding rules: DP / TP (Megatron) / EP / SP / FSDP.

``ShardingRules`` maps every parameter, optimizer-state, batch and cache leaf
to a ``PartitionSpec`` on the production mesh:

  * **TP** over the ``model`` axis: QKV / MLP-up column-parallel, O / MLP-down
    row-parallel, vocab-parallel embeddings, experts expert-parallel.
  * **FSDP/ZeRO** over the ``data`` axis: the *other* matrix dimension of each
    weight is sharded over data and all-gathered per layer by GSPMD; optimizer
    state inherits the same spec (fully sharded).
  * **DP** over ``("pod", "data")``: batch dims.  The pod axis is pure data
    parallelism — weights are pod-replicated, gradients all-reduce across pods
    (the compressed global-tier push attacks exactly these bytes).
  * **SP for caches**: KV caches shard heads over ``model`` when the head
    count divides it, otherwise the cache *sequence* dim shards over ``model``
    (sequence-parallel decode attention); the 500k-token batch-1 cell shards
    sequence over every axis.
  * SSM archs (no head dim divisible by model): batch shards over
    ``(data, model)`` jointly where divisible — all axes do data parallelism,
    weights FSDP over ``data``.

Every assignment is divisibility-guarded: a dim that does not divide the axis
size stays unsharded rather than failing to lower.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig


def _dim(leaf, i):
    return leaf.shape[i]


def _axis_entry(axes):
    """Collapse an axis collection into a canonical PartitionSpec entry:
    ``[] -> None``, ``['model'] -> 'model'`` (scalar, not a 1-tuple),
    ``['pod', 'data'] -> ('pod', 'data')``."""
    axes = tuple(axes)
    if not axes:
        return None
    if len(axes) == 1:
        return axes[0]
    return axes


def _has_axis(entry, name: str) -> bool:
    """Membership test on a spec entry that may be None, a scalar or a tuple."""
    if entry is None:
        return False
    if isinstance(entry, str):
        return entry == name
    return name in entry


@dataclasses.dataclass
class ShardingRules:
    mesh: Mesh
    cfg: ModelConfig
    fsdp: bool = True

    def __post_init__(self):
        names = self.mesh.axis_names
        self.model_ax = "model" if "model" in names else None
        self.data_axs = tuple(a for a in names if a != "model")
        self.model_size = self.mesh.shape.get("model", 1)
        self.data_size = int(np.prod([self.mesh.shape[a] for a in self.data_axs])) \
            if self.data_axs else 1
        # trillion-scale params: extend FSDP across the pod axis too (ZeRO-3
        # over DCI) — weights must not be pod-replicated.
        fsdp_pod = (self.cfg.param_count() > 4e11 and "pod" in names)
        if not self.fsdp or "data" not in names:
            self.fsdp_ax = None
            self.fsdp_size = 1
        elif fsdp_pod:
            self.fsdp_ax = ("pod", "data")
            self.fsdp_size = self.mesh.shape["pod"] * self.mesh.shape["data"]
        else:
            self.fsdp_ax = "data"
            self.fsdp_size = self.mesh.shape.get("data", 1)

    # -- helpers ------------------------------------------------------------------

    def _maybe(self, ax: Optional[str], size: int, dim: int):
        """Assign axis only if the dim divides its size."""
        if ax is None or dim % max(size, 1) != 0 or size == 1:
            return None
        return ax

    def _model(self, dim: int):
        return self._maybe(self.model_ax, self.model_size, dim)

    def _fsdp(self, dim: int):
        return self._maybe(self.fsdp_ax, self.fsdp_size, dim)

    def _batch_axes(self, b: int, wide: bool = False):
        """Axes for a batch dim; ``wide`` also folds in the model axis (SSM DP)."""
        axs = []
        rem = b
        for a in self.data_axs + ((("model",) if wide and self.model_ax else ())):
            sz = self.mesh.shape[a]
            if rem % sz == 0:
                axs.append(a)
                rem //= sz
        return _axis_entry(axs)

    # -- parameter rules ----------------------------------------------------------

    def _param_rule(self, path: str, leaf) -> P:
        nd = leaf.ndim
        cfg = self.cfg
        name = path.split("'")[-2] if "'" in path else path

        def tail(*axes):
            """Spec for the trailing len(axes) dims; leading dims unsharded."""
            axes = list(axes)
            lead = nd - len(axes)
            if lead < 0:
                axes = axes[-nd:] if nd else []
                lead = 0
            return P(*([None] * lead + axes))

        ssm_weight = ".mamba" in path or "'mamba'" in path

        if name == "embed":
            return tail(self._model(_dim(leaf, 0)), self._fsdp(_dim(leaf, 1)))
        if name == "w_unembed":
            return tail(self._fsdp(_dim(leaf, 0)), self._model(_dim(leaf, 1)))

        if "moe" in path and name in ("w_gate", "w_up") and nd >= 3:
            return tail(self._model(_dim(leaf, nd - 3)),       # experts
                        self._fsdp(_dim(leaf, nd - 2)), None)
        if "moe" in path and name == "w_down" and nd >= 3:
            return tail(self._model(_dim(leaf, nd - 3)), None,
                        self._fsdp(_dim(leaf, nd - 1)))
        if name == "w_router":
            return tail(self._fsdp(_dim(leaf, nd - 2)), None)

        if ssm_weight:
            # SSM weights: FSDP only (head counts rarely divide the model axis)
            if name == "w_in":
                return tail(self._fsdp(_dim(leaf, nd - 2)), None)
            if name == "w_out":
                return tail(None, self._fsdp(_dim(leaf, nd - 1)))
            if name == "conv_w":
                return tail(None, None)
            return tail(*([None] * min(nd, 1)))

        if name in ("wq", "wk", "wv"):
            return tail(self._fsdp(_dim(leaf, nd - 2)), self._model(_dim(leaf, nd - 1)))
        if name == "wo":
            return tail(self._model(_dim(leaf, nd - 2)), self._fsdp(_dim(leaf, nd - 1)))
        if name in ("bq", "bk", "bv", "b_up"):
            return tail(self._model(_dim(leaf, nd - 1)))
        if name in ("w_gate", "w_up"):                         # dense / shared MLP
            return tail(self._fsdp(_dim(leaf, nd - 2)), self._model(_dim(leaf, nd - 1)))
        if name == "w_down":
            return tail(self._model(_dim(leaf, nd - 2)), self._fsdp(_dim(leaf, nd - 1)))

        # norms, small vectors, biases on d_model: replicated
        return P(*([None] * nd))

    def params_specs(self, params_shapes) -> Any:
        def rule(path, leaf):
            return self._param_rule(jax.tree_util.keystr(path), leaf)
        return jax.tree_util.tree_map_with_path(rule, params_shapes)

    def params_shardings(self, params_shapes) -> Any:
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.params_specs(params_shapes))

    # -- optimizer state: inherit the param spec where shapes match -----------------

    def opt_specs(self, opt_shapes, params_shapes) -> Any:
        pspecs = self.params_specs(params_shapes)
        pshapes = {tuple(l.shape) for l in jax.tree.leaves(params_shapes)}
        by_shape: Dict[tuple, P] = {}
        for l, s in zip(jax.tree.leaves(params_shapes),
                        jax.tree.leaves(pspecs)):
            by_shape.setdefault(tuple(l.shape), s)

        def rule(leaf):
            return by_shape.get(tuple(leaf.shape), P(*([None] * leaf.ndim)))
        return jax.tree.map(rule, opt_shapes)

    # -- batch / activation rules ------------------------------------------------------

    def _wide_batch(self) -> bool:
        """SSM/hybrid archs do pure DP across every axis (incl. model)."""
        return self.cfg.family in ("ssm", "hybrid")

    def batch_specs(self, input_specs: Dict[str, Any], shape: ShapeConfig) -> Any:
        wide = self._wide_batch()

        def spec_for_input(leaf):
            b_axes = self._batch_axes(leaf.shape[0], wide=wide)
            return P(*([b_axes] + [None] * (leaf.ndim - 1)))

        out = {}
        for k, v in input_specs.items():
            if k == "cache":
                out[k] = self.cache_specs(v)
            else:
                out[k] = jax.tree.map(spec_for_input, v)
        return out

    def cache_specs(self, cache_shapes) -> Any:
        """Cache leaves: (L, B, S, K, D) attn / (L, B, W, C) conv / (L, B, H, P, N) ssm."""
        wide = self._wide_batch()

        def rule(path, leaf):
            name = jax.tree_util.keystr(path)
            nd = leaf.ndim
            batch_dim = 1                      # all caches are (L, B, ...)
            b_axes = self._batch_axes(leaf.shape[batch_dim], wide=wide)
            spec = [None] * nd
            spec[batch_dim] = b_axes
            if ("'k'" in name or "'v'" in name or "'ck'" in name
                    or "'cv'" in name or "first_" in name) and nd == 5:
                L, B, S, K, D = leaf.shape
                model_used = _has_axis(b_axes, "model")
                if self._model(K) is not None and not model_used:
                    spec[3] = self._model(K)
                    model_used = True
                # sequence-parallel cache: any axis not already used shards S
                # (few KV heads -> model; batch-1 long-context -> data too).
                seq_axes = []
                rem = S
                if b_axes is None:
                    for a in self.data_axs:
                        if rem % self.mesh.shape[a] == 0:
                            seq_axes.append(a)
                            rem //= self.mesh.shape[a]
                if (self.model_ax and not model_used
                        and rem % self.model_size == 0):
                    seq_axes.append(self.model_ax)
                spec[2] = _axis_entry(seq_axes)
            elif "'ssm'" in name and nd == 5:
                L, B, H, Pd, N = leaf.shape
                if not _has_axis(b_axes, "model"):
                    if self._model(N) is not None and \
                            not _has_axis(b_axes, self.model_ax or ""):
                        spec[4] = self._model(N)
            elif "'conv'" in name and nd == 4:
                L, B, W, C = leaf.shape
                if not _has_axis(b_axes, "model"):
                    if self._model(C) is not None and \
                            not _has_axis(b_axes, self.model_ax or ""):
                        spec[3] = self._model(C)
            return P(*spec)

        return jax.tree_util.tree_map_with_path(rule, cache_shapes)

    # -- logits / outputs --------------------------------------------------------------

    def logits_spec(self, batch: int) -> P:
        b_axes = self._batch_axes(batch, wide=self._wide_batch())
        return P(b_axes, self._model(self.cfg.vocab_size))

    def scalar_spec(self) -> P:
        return P()
