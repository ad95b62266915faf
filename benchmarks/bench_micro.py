"""Paper Fig. 9 analogue: isolation/kernel overhead microbenchmarks.

The paper measures Wasm-vs-native overhead; our SFI analogue is the kernel
dispatch layer, so we measure each Pallas kernel's xla path against its
pure-jnp oracle at fixed shapes (overhead ≈ 1.0x means free isolation), plus
host-interface call overhead."""
import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.kernels.flash_attention import flash_attention, attention_ref
from repro.kernels.decode_attention import decode_attention, decode_attention_ref
from repro.kernels.ssd_scan import ssd, ssd_ref
from repro.kernels.moe_gmm import gmm, gmm_ref
from repro.kernels.state_push import apply_delta, quantize_delta

RNG = np.random.default_rng(0)


def _r(*s):
    return jnp.asarray(RNG.normal(size=s), jnp.float32)


def main() -> None:
    # flash attention
    q, k, v = _r(2, 256, 8, 64), _r(2, 256, 2, 64), _r(2, 256, 2, 64)
    f_ref = jax.jit(lambda: attention_ref(q, k, v))
    f_fa = jax.jit(lambda: flash_attention(q, k, v, backend="xla", block_k=128))
    t_ref = time_fn(lambda: f_ref().block_until_ready())
    t_fa = time_fn(lambda: f_fa().block_until_ready())
    emit("fig9_micro/flash_attention", t_fa, f"{t_fa / t_ref:.2f}x vs oracle")

    # decode attention
    q2, k2, v2 = _r(8, 16, 64), _r(8, 2048, 2, 64), _r(8, 2048, 2, 64)
    lens = jnp.full((8,), 2048, jnp.int32)
    d_ref = jax.jit(lambda: decode_attention_ref(q2, k2, v2, lens))
    d_fa = jax.jit(lambda: decode_attention(q2, k2, v2, lens, backend="xla"))
    t_ref = time_fn(lambda: d_ref().block_until_ready())
    t_fa = time_fn(lambda: d_fa().block_until_ready())
    emit("fig9_micro/decode_attention", t_fa, f"{t_fa / t_ref:.2f}x vs oracle")

    # SSD scan
    x = _r(2, 256, 8, 32)
    dt = jnp.abs(_r(2, 256, 8)) * 0.1 + 0.01
    A = -jnp.abs(_r(8)) - 0.5
    B = _r(2, 256, 1, 32)
    C = _r(2, 256, 1, 32)
    D = _r(8)
    s_ref = jax.jit(lambda: ssd_ref(x, dt, A, B, C, D)[0])
    s_ch = jax.jit(lambda: ssd(x, dt, A, B, C, D, chunk=64, backend="xla")[0])
    t_ref = time_fn(lambda: s_ref().block_until_ready())
    t_ch = time_fn(lambda: s_ch().block_until_ready())
    emit("fig9_micro/ssd_chunked", t_ch,
         f"{t_ch / t_ref:.2f}x vs sequential oracle")

    # grouped matmul
    xg = _r(512, 64)
    wg = _r(8, 64, 64)
    gs = jnp.full((8,), 64, jnp.int32)
    g_ref = jax.jit(lambda: gmm_ref(xg, wg, gs))
    g_rd = jax.jit(lambda: gmm(xg, wg, gs, backend="xla"))
    t_ref = time_fn(lambda: g_ref().block_until_ready())
    t_rd = time_fn(lambda: g_rd().block_until_ready())
    emit("fig9_micro/moe_gmm", t_rd, f"{t_rd / t_ref:.2f}x vs dense-masked oracle")

    # quantised push wire: encode (quantize_delta) + decode-apply (apply_delta)
    a, b, c = _r(1 << 16), _r(1 << 16), _r(1 << 16)
    t_q = time_fn(lambda: jax.block_until_ready(
        quantize_delta(a, b, backend="xla")[0]))
    emit("fig9_micro/state_push_quantize", t_q,
         "int8 wire encode, 64k f32 (4x fewer push bytes)")
    qw, sw, _ = quantize_delta(a, b, backend="xla")
    t_ap = time_fn(lambda: jax.block_until_ready(
        apply_delta(c, qw, sw, backend="xla")))
    emit("fig9_micro/state_push_apply_q", t_ap, "int8 wire decode+apply")

    # host interface call overhead (Table 2 surface)
    from repro.core import FaasmRuntime, FunctionDef
    rt = FaasmRuntime(n_hosts=1)
    try:
        rt.upload(FunctionDef("noop", lambda api: 0))
        rt.wait(rt.invoke("noop"), timeout=10)          # warm

        def one():
            rt.wait(rt.invoke("noop"), timeout=10)
        emit("fig9_micro/host_interface_call", time_fn(one, n=10),
             "warm no-op invocation")
    finally:
        rt.shutdown()


if __name__ == "__main__":
    main()
