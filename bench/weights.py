"""Random weights from the seed, made by the benchmark and not the program.

The program's ``init`` gives only the layout: the tree of names, shapes and
dtypes (``jax.eval_shape``, nothing computed).  Values come from one jitted
call here, keyed by the seed and each leaf's path, in the dtype the
program serves.  The float32 references get the same values by calling
:func:`make` again with the same seed, so they take nothing the program
made.

Each leaf is drawn by a rule on its name, close to how published models
are initialised and nonzero wherever a term could otherwise drop out
unseen (biases, norm scales, the skip ``D``).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int):
    """A PRNG key from any whole-number seed (large ones included)."""
    word = np.random.SeedSequence(seed % (1 << 64)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def _normal(key, shape, std):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std


def _leaf(key, name: str, shape):
    if name in ("scale", "norm_scale", "D"):
        return 1.0 + _normal(key, shape, 0.1)
    if name == "embed":
        return _normal(key, shape, 0.02)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        # dt log-uniform in [1e-3, 1e-1], stored as softplus^-1(dt)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "conv_w":
        return _normal(key, shape, shape[-2] ** -0.5)
    if name in ("conv_b", "bias") or (name.startswith("b") and len(name) == 2):
        return _normal(key, shape, 0.3)
    if name.startswith("w_") or (name.startswith("w") and len(name) == 2):
        return _normal(key, shape, shape[-2] ** -0.5)
    raise KeyError(f"no initialisation rule for parameter {name!r}")


def _path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def make(init_fn, seed: int):
    """Parameters laid out as ``init_fn``'s output, drawn from ``seed`` on
    the device in one jitted call."""
    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [(_path_name(p), s.shape, s.dtype) for p, s in leaves]

    @jax.jit
    def draw(key):
        return [_leaf(jax.random.fold_in(key, zlib.crc32(path.encode())
                                         & 0x7FFFFFFF),
                      path.rsplit("/", 1)[-1], shape).astype(dtype)
                for path, shape, dtype in specs]

    return jax.tree_util.tree_unflatten(treedef, draw(jax_key(seed)))
