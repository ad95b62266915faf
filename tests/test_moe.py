"""MoE layer tests: router invariants, dispatch-implementation equivalence."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import smoke_config
from repro.models.execution import ExecConfig
from repro.models.moe import moe_apply, moe_init, router_topk


def _setup(capacity_factor=8.0):
    cfg = smoke_config("deepseek-moe-16b").with_overrides(
        capacity_factor=capacity_factor)
    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)
    return cfg, p, x


def test_router_topk_invariants():
    cfg, p, x = _setup()
    gates, idx, aux = router_topk(p, cfg, x.reshape(-1, cfg.d_model))
    T = 32
    assert gates.shape == (T, cfg.experts_per_token)
    np.testing.assert_allclose(gates.sum(-1), 1.0, atol=1e-5)   # renormalised
    assert int(idx.min()) >= 0 and int(idx.max()) < cfg.n_experts
    # top-k indices are distinct per token
    for row in np.asarray(idx):
        assert len(set(row.tolist())) == cfg.experts_per_token
    assert float(aux) >= 0.0


def test_einsum_vs_sorted_dispatch_equivalent():
    """With capacity high enough to avoid drops, the GShard einsum dispatch
    and the dropless sorted-gmm dispatch are the same function."""
    cfg, p, x = _setup(capacity_factor=8.0)
    ec_e = ExecConfig(backend="xla", moe_impl="einsum", moe_group_size=32)
    ec_s = ExecConfig(backend="xla", moe_impl="sorted")
    y_e, aux_e, _ = moe_apply(p, cfg, ec_e, x)
    y_s, aux_s, _ = moe_apply(p, cfg, ec_s, x)
    np.testing.assert_allclose(np.asarray(y_e), np.asarray(y_s),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(float(aux_e), float(aux_s), rtol=1e-5)


def test_einsum_low_capacity_drops_tokens():
    """With a tiny capacity factor some tokens are dropped (zero output),
    never corrupted."""
    cfg, p, x = _setup(capacity_factor=8.0)
    ec_lo = ExecConfig(backend="xla", moe_impl="einsum", moe_group_size=32)
    y_hi, _, _ = moe_apply(p, cfg, ec_lo, x)
    cfg_lo = cfg.with_overrides(capacity_factor=0.25)
    y_lo, _, _ = moe_apply(p, cfg_lo, ec_lo, x)
    # dropped tokens shrink toward the shared-expert-only output
    assert float(jnp.abs(y_lo).mean()) <= float(jnp.abs(y_hi).mean()) + 1e-6


def test_moe_grads_flow_to_all_parts():
    cfg, p, x = _setup()
    ec = ExecConfig(backend="xla", moe_impl="einsum", moe_group_size=32)

    def loss(p):
        y, aux, _ = moe_apply(p, cfg, ec, x)
        return (y ** 2).mean() + aux

    g = jax.grad(loss)(p)
    for path, leaf in jax.tree_util.tree_flatten_with_path(g)[0]:
        name = jax.tree_util.keystr(path)
        assert bool(jnp.isfinite(leaf).all()), name
    assert float(jnp.abs(g["w_router"]).sum()) > 0
    assert float(jnp.abs(g["w_gate"]).sum()) > 0
    assert float(jnp.abs(g["shared"]["w_up"]).sum()) > 0
