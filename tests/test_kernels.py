"""Per-kernel allclose sweeps: pallas-interpret + xla paths vs the ref.py oracles."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import flash_attention, attention_ref
from repro.kernels.decode_attention import decode_attention, decode_attention_ref
from repro.kernels.ssd_scan import ssd, ssd_step, ssd_ref
from repro.kernels.moe_gmm import gmm, gmm_ref
from repro.kernels.state_push import (apply_delta, quantize_delta,
                                      quantize_delta_ref)

RNG = np.random.default_rng(42)


def _randn(*shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # B, Sq, Sk, H, K, D, causal, q_offset, Dv (v's channels)
    (2, 16, 16, 4, 2, 16, True, 0, 16),
    (1, 8, 24, 4, 4, 8, True, 16, 8),
    (2, 17, 33, 6, 2, 16, False, 0, 16),
    (1, 1, 40, 8, 2, 32, True, 39, 32),
    (2, 16, 16, 4, 1, 16, True, 0, 16),      # MQA
    (1, 20, 20, 4, 4, 24, True, 0, 16),      # latent attention: Dv < D, padded
    (2, 13, 29, 4, 4, 24, True, 16, 8),
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_flash_attention_matches_ref(case, backend):
    B, Sq, Sk, H, K, D, causal, off, Dv = case
    q, k, v = _randn(B, Sq, H, D), _randn(B, Sk, K, D), _randn(B, Sk, K, Dv)
    ref = attention_ref(q, k, v, causal=causal, q_offset=off)
    assert ref.shape == (B, Sq, H, Dv)
    got = flash_attention(q, k, v, causal=causal, q_offset=off,
                          backend=backend, block_q=8, block_k=8)
    np.testing.assert_allclose(ref, got, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = _randn(2, 12, 4, 16, dtype=dtype)
    k = _randn(2, 12, 2, 16, dtype=dtype)
    v = _randn(2, 12, 2, 16, dtype=dtype)
    ref = attention_ref(q, k, v)
    got = flash_attention(q, k, v, backend="pallas_interpret", block_q=8,
                          block_k=8)
    assert got.dtype == dtype
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.float32(ref), np.float32(got),
                               atol=tol, rtol=tol)


def test_flash_attention_grads_match_ref_autodiff():
    q, k, v = _randn(2, 16, 4, 16), _randn(2, 16, 2, 16), _randn(2, 16, 2, 16)
    f_ref = lambda q, k, v: (attention_ref(q, k, v) ** 2).sum()
    f_fa = lambda q, k, v: (flash_attention(q, k, v, backend="xla",
                                            block_k=8) ** 2).sum()
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_fa = jax.grad(f_fa, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fa):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


def test_flash_attention_grads_with_narrower_values():
    """The xla path's flash backward with v narrower than q and k (latent
    attention trains through it)."""
    q, k, v = _randn(1, 16, 4, 24), _randn(1, 16, 4, 24), _randn(1, 16, 4, 16)
    f_ref = lambda q, k, v: (attention_ref(q, k, v) ** 2).sum()
    f_fa = lambda q, k, v: (flash_attention(q, k, v, backend="xla",
                                            block_k=8) ** 2).sum()
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_fa = jax.grad(f_fa, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fa):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

DECODE_CASES = [(2, 64, 8, 2, 16), (3, 40, 4, 4, 32), (1, 128, 16, 2, 64)]


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_decode_attention_matches_ref(case, backend):
    B, S, H, K, D = case
    q = _randn(B, H, D)
    k, v = _randn(B, S, K, D), _randn(B, S, K, D)
    lengths = jnp.asarray(RNG.integers(1, S + 1, size=(B,)), jnp.int32)
    ref = decode_attention_ref(q, k, v, lengths)
    got = decode_attention(q, k, v, lengths, backend=backend, block_k=16)
    np.testing.assert_allclose(ref, got, atol=2e-5, rtol=2e-5)


def test_decode_attention_ignores_garbage_past_length():
    B, S, H, K, D = 2, 32, 4, 2, 16
    q = _randn(B, H, D)
    k, v = _randn(B, S, K, D), _randn(B, S, K, D)
    lengths = jnp.asarray([10, 20], jnp.int32)
    base = decode_attention(q, k, v, lengths, backend="xla")
    k2 = k.at[0, 15:].set(1e9)                      # garbage beyond length
    v2 = v.at[0, 15:].set(-1e9)
    got = decode_attention(q, k2, v2, lengths, backend="xla")
    np.testing.assert_allclose(base, got, atol=1e-6)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

SSD_CASES = [(2, 32, 4, 16, 2, 16, 8), (1, 24, 6, 8, 3, 8, 8),
             (2, 16, 4, 16, 1, 32, 16)]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_ssd_matches_ref(case, backend):
    Bt, S, H, P, G, N, chunk = case
    x = _randn(Bt, S, H, P)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(Bt, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    B = _randn(Bt, S, G, N)
    C = _randn(Bt, S, G, N)
    D = _randn(H)
    init = _randn(Bt, H, P, N)
    y_ref, f_ref = ssd_ref(x, dt, A, B, C, D, initial_state=init)
    y, f = ssd(x, dt, A, B, C, D, chunk=chunk, initial_state=init,
               backend=backend)
    np.testing.assert_allclose(y_ref, y, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(f_ref, f, atol=1e-4, rtol=1e-4)


def test_ssd_large_decay_no_nan():
    """Regression: masked upper-tri segsum overflow must not produce NaNs."""
    Bt, S, H, P, G, N = 1, 32, 2, 8, 1, 8
    x = _randn(Bt, S, H, P)
    dt = jnp.asarray(RNG.uniform(0.5, 3.0, size=(Bt, S, H)), jnp.float32)
    A = jnp.asarray([-12.0, -16.0], jnp.float32)
    B = _randn(Bt, S, G, N)
    C = _randn(Bt, S, G, N)
    D = _randn(H)
    y, f = ssd(x, dt, A, B, C, D, chunk=8, backend="xla")
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(f).all())


def test_ssd_step_matches_scan():
    Bt, S, H, P, G, N = 2, 6, 4, 8, 2, 8
    x = _randn(Bt, S, H, P)
    dt = jnp.asarray(RNG.uniform(0.01, 0.3, size=(Bt, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    B = _randn(Bt, S, G, N)
    C = _randn(Bt, S, G, N)
    D = _randn(H)
    y_ref, _ = ssd_ref(x, dt, A, B, C, D)
    state = jnp.zeros((Bt, H, P, N), jnp.float32)
    for t in range(S):
        y_t, state = ssd_step(state, x[:, t], dt[:, t], A, B[:, t], C[:, t], D)
        np.testing.assert_allclose(y_ref[:, t], y_t, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [(64, 32, 48, 4, 8), (100, 16, 16, 5, 16),
                                  (40, 8, 24, 3, 8)])
@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_gmm_matches_ref(case, backend):
    T, d, f, E, bm = case
    x = _randn(T, d)
    w = _randn(E, d, f)
    cuts = np.sort(RNG.integers(0, T + 1, size=E - 1))
    gs = jnp.asarray(np.diff(np.concatenate([[0], cuts, [T]])), jnp.int32)
    ref = gmm_ref(x, w, gs)
    got = gmm(x, w, gs, backend=backend, block_m=bm, block_n=8)
    np.testing.assert_allclose(ref, got, atol=1e-4, rtol=1e-4)


def test_gmm_empty_groups():
    T, d, f, E = 32, 8, 8, 4
    x = _randn(T, d)
    w = _randn(E, d, f)
    gs = jnp.asarray([0, T, 0, 0], jnp.int32)       # all tokens -> expert 1
    ref = gmm_ref(x, w, gs)
    got = gmm(x, w, gs, backend="pallas_interpret", block_m=8, block_n=8)
    np.testing.assert_allclose(ref, got, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# state push
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(100,), (13, 7), (5, 5, 5), (1,)])
@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_state_push_roundtrip(shape, backend):
    local = _randn(*shape)
    base = _randn(*shape)
    gv = _randn(*shape)
    q, s, n = quantize_delta(local, base, backend=backend)
    newg = apply_delta(gv, q, s, backend=backend)
    exact = gv + (local - base)
    bound = float(np.abs(np.asarray(local - base)).max()) / 127 * 1.01 + 1e-8
    np.testing.assert_allclose(newg, exact, atol=bound)      # int8 error bound


def test_quantize_zero_delta_is_exact():
    x = _randn(64)
    q, s, _ = quantize_delta(x, x, backend="xla")
    assert int(jnp.abs(q).max()) == 0
