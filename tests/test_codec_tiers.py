"""The int8 wire codec — the host-native fused codec, the device encode
paths on ``xla`` and Pallas, the ``WireFrame`` it produces — and the
heuristic ``WirePolicy`` that chooses between it and the exact wire.

Parity notes baked into the bounds below:

* The host codec divides ``absmax/QMAX`` plainly; XLA jit compiles the same
  division to reciprocal-multiply, which can shift a handful of row scales
  by one ULP — so cross-path assertions are tolerance-based, never bitwise.

The ``pallas_interpret`` parametrisations are auto-marked slow by conftest;
the xla rows run in the ``scripts/tier1.sh`` fast gate.
"""
import numpy as np
import pytest

from repro.kernels.state_push import QMAX, hostcodec
from repro.kernels.state_push import ops
from repro.state.wire import WirePolicy, get_codec
from repro.telemetry.spans import COMPILE_EVENT

BACKENDS = ("xla", "pallas_interpret")
ODD_SIZES = (1, 5, 130, 1000, 4097)
# every odd size, plus serve/stats: one f32 per qwen1.5 token id
SIZES = ODD_SIZES + (151936,)


def _rng(seed=0):
    return np.random.default_rng(seed)


def _pair(n, seed=0, scale=1.0):
    rng = _rng(seed)
    eff = (rng.normal(size=n) * scale).astype(np.float32)
    base = (rng.normal(size=n) * scale).astype(np.float32)
    return eff, base


# -- host codec: conservation, pad no-op, odd sizes, chunk invariance ---------


@pytest.mark.parametrize("n", SIZES)
def test_hostcodec_residual_conserves_delta(n):
    """deq + residual == delta exactly — error feedback loses nothing."""
    eff, base = _pair(n, seed=n)
    q, s, numel, resid = hostcodec.encode_quant(eff, base)
    assert numel == n and resid.shape == (n,)
    deq = hostcodec.decode_rows(q, s, n)
    np.testing.assert_allclose(deq + resid, eff - base, atol=1e-6)
    assert np.abs(q.astype(np.int32)).max() <= QMAX


@pytest.mark.parametrize("n", SIZES)
def test_hostcodec_pad_region_is_zero(n):
    eff, base = _pair(n, seed=3)
    q, s, numel, _ = hostcodec.encode_quant(eff, base)
    rows = -(-n // 128)
    assert q.shape == (rows, 128) and s.shape == (rows, 1) and numel == n
    assert np.all(q.reshape(-1)[n:] == 0)


@pytest.mark.parametrize("chunk_rows", [1, 3, 7, 1024])
def test_hostcodec_chunked_matches_unchunked_bitwise(chunk_rows):
    """Chunks split on row boundaries and scales are per-row, so any chunk
    size yields bit-identical wire buffers."""
    n = 9 * 128 + 17
    eff, base = _pair(n, seed=9)
    q1, s1, _, r1 = hostcodec.encode_quant(eff, base, chunk_rows=chunk_rows)
    q2, s2, _, r2 = hostcodec.encode_quant(eff, base)
    assert np.array_equal(q1, q2)
    assert np.array_equal(s1, s2)
    assert np.array_equal(r1, r2)


def test_hostcodec_none_base_is_zero_base():
    eff, _ = _pair(1000, seed=4)
    q1, s1, _, r1 = hostcodec.encode_quant(eff, None)
    q2, s2, _, r2 = hostcodec.encode_quant(eff, np.zeros_like(eff))
    assert np.array_equal(q1, q2) and np.array_equal(s1, s2)
    assert np.array_equal(r1, r2)


def test_hostcodec_exact_matches_subtract():
    eff, base = _pair(4097, seed=5)
    out = hostcodec.encode_exact(eff, base, chunk_rows=2)
    np.testing.assert_array_equal(out, eff - base)


# -- the int8 WireFrame -------------------------------------------------------


@pytest.mark.parametrize("n", SIZES)
def test_int8_frame_bytes_and_decode_at_odd_sizes(n):
    """An int8 frame moves one byte per padded lane plus one f32 scale per
    row, and decodes to within one step of each row's scale."""
    eff, base = _pair(n, seed=n + 1)
    frame, resid = get_codec("int8").encode(eff, base, backend="xla")
    rows = -(-n // 128)
    assert frame.wire == "int8" and frame.numel == n
    assert frame.payload.shape == (rows, 128)
    assert frame.payload.dtype == np.int8
    assert frame.nbytes == rows * 128 + 4 * rows
    step = np.repeat(frame.scales[:, 0], 128)[:n]
    err = np.abs(frame.decode() - (eff - base))
    assert np.all(err <= step * (1 + 1e-6))
    q, s = frame.codes()
    assert q is frame.payload and s is frame.scales


# -- xla / pallas_interpret parity matrix -------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n", SIZES)
def test_quant_parity_host_vs_device(backend, n):
    """The device encode and the host fast path agree to quantisation
    precision (scales may differ by one ULP — see module docstring)."""
    import jax.numpy as jnp
    eff, base = _pair(n, seed=127 + n)
    qh, sh, _, _ = hostcodec.encode_quant(eff, base)
    qd, sd, numel, _ = ops.encode_quant(jnp.asarray(eff), jnp.asarray(base),
                                        backend=backend)
    assert numel == n
    deq_h = hostcodec.decode_rows(qh, sh, n)
    deq_d = hostcodec.decode_rows(np.asarray(qd), np.asarray(sd), n)
    step = np.abs(eff - base).max() / QMAX
    assert np.abs(deq_h - deq_d).max() <= step + 1e-6


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n", SIZES)
def test_residual_conservation_device_paths(backend, n):
    """Fused device encode's residual also conserves: deq + resid == delta
    to f32 rounding, and the pad lanes carry zero codes."""
    import jax.numpy as jnp
    eff, base = _pair(n, seed=11)
    q, s, numel, resid = ops.encode_quant(jnp.asarray(eff),
                                          jnp.asarray(base), backend=backend)
    assert numel == n and q.shape == (-(-n // 128), 128)
    assert np.all(np.asarray(q).reshape(-1)[n:] == 0)
    deq = hostcodec.decode_rows(np.asarray(q), np.asarray(s), n)
    np.testing.assert_allclose(deq + np.asarray(resid), eff - base, atol=1e-5)


def test_device_chunked_encode_matches_single_shot():
    """Values past DEVICE_CHUNK_ROWS rows take the pipelined chunk path;
    row-aligned chunks with per-row scales must reproduce the single-shot
    executable bitwise."""
    import jax.numpy as jnp
    n = (ops.DEVICE_CHUNK_ROWS + 100) * 128 + 7
    eff, base = _pair(n, seed=12, scale=0.1)
    je, jb = jnp.asarray(eff), jnp.asarray(base)
    q, s, numel, resid = ops.encode_quant(je, jb)
    assert numel == n
    qs, ss, rs = ops._encode_fused(je, jb, True)
    assert np.array_equal(q, np.asarray(qs))
    assert np.array_equal(s, np.asarray(ss))
    np.testing.assert_array_equal(resid,
                                  np.asarray(rs).reshape(-1)[:n])


@pytest.mark.parametrize("n", SIZES)
def test_pallas_encode_matches_eager_kernel_bitwise(n):
    """The jitted Pallas encode returns the codes, scales and residual that
    the quantise kernel gives when called eagerly on the padded rows."""
    import jax.numpy as jnp
    from repro.kernels.common import round_up
    from repro.kernels.state_push.kernel import quantize_delta_pallas
    eff, base = _pair(n, seed=n + 127, scale=0.1)
    je, jb = jnp.asarray(eff), jnp.asarray(base)
    lr, _ = ops._to_rows(je)
    br, _ = ops._to_rows(jb)
    rows = lr.shape[0]
    blk = min(256, round_up(rows, 8))
    pad = ((0, round_up(rows, blk) - rows), (0, 0))
    q, s = quantize_delta_pallas(jnp.pad(lr, pad), jnp.pad(br, pad),
                                 block_rows=blk, interpret=True)
    qe, se = np.asarray(q)[:rows], np.asarray(s)[:rows]
    re = (np.asarray(lr - br) - qe.astype(np.float32) * se).reshape(-1)[:n]
    qj, sj, numel, rj = ops.encode_quant(je, jb, backend="pallas_interpret")
    assert numel == n and qj.dtype == np.int8
    assert np.array_equal(qj, qe)
    assert np.array_equal(sj.view(np.uint32), se.view(np.uint32))
    assert np.array_equal(rj.view(np.uint32), re.view(np.uint32))


def _compiles():
    """Start collecting the functions JAX compiles; returns the list and
    the listener to unregister."""
    import jax
    compiles = []

    def listen(event, duration, **kw):
        if event == COMPILE_EVENT:
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    return compiles, listen


_PI = "pallas_interpret"
_PALLAS_OPS = {
    "encode_quant": lambda e, b: ops.encode_quant(e, b, backend=_PI),
    "quantize_delta": lambda e, b: ops.quantize_delta(e, b, backend=_PI),
    "apply_delta": lambda e, b: ops.apply_delta(
        b, *hostcodec.encode_quant(np.asarray(e), np.asarray(b))[:2],
        backend=_PI),
}


@pytest.mark.parametrize("op", sorted(_PALLAS_OPS))
def test_pallas_interpret_dispatch_compiles_once_per_shape(op):
    """A Pallas dispatch compiles on the first call of a shape and never
    again for that shape; a new shape compiles anew."""
    import jax
    import jax.numpy as jnp

    def call(n):
        eff, base = _pair(n, seed=n)
        jax.block_until_ready(_PALLAS_OPS[op](jnp.asarray(eff),
                                              jnp.asarray(base)))

    compiles, listen = _compiles()
    try:
        call(1531)
        compiles.clear()
        call(1531)
        assert compiles == []
        call(2711)
        assert compiles
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


@pytest.mark.parametrize("backend", BACKENDS)
def test_host_fast_path_skips_jax_dispatch(backend):
    """numpy operands return numpy wire buffers computed by the host codec
    on every backend — bitwise equal to calling hostcodec directly, with no
    JAX compile (a shape no other test encodes, so a device encode would
    compile)."""
    import jax
    eff, base = _pair(2389, seed=13)
    compiles, listen = _compiles()
    try:
        q, s, n, resid = ops.encode_quant(eff, base, backend=backend)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    qh, sh, _, rh = hostcodec.encode_quant(eff, base)
    assert type(q) is np.ndarray and type(s) is np.ndarray
    assert type(resid) is np.ndarray and n == 2389
    assert np.array_equal(q, qh) and np.array_equal(s, sh)
    assert np.array_equal(resid, rh)
    assert compiles == []


@pytest.mark.parametrize("backend,kernel", [("xla", "_encode_fused"),
                                            (_PI, "_encode_pallas")])
def test_device_operand_keeps_device_encode(backend, kernel, monkeypatch):
    """A device operand still reaches the backend's device kernel, and
    ``host_resident`` says so: the backend chooses only that kernel."""
    import jax.numpy as jnp
    eff, base = _pair(1000, seed=14)
    calls = []
    real = getattr(ops, kernel)

    def spy(*a, **kw):
        calls.append(kernel)
        return real(*a, **kw)

    monkeypatch.setattr(ops, kernel, spy)
    je = jnp.asarray(eff)
    assert not ops.host_resident(je, base)
    assert not ops.host_resident(eff, jnp.asarray(base))
    assert ops.host_resident(eff, base) and ops.host_resident(eff, None)
    q, s, n, resid = ops.encode_quant(je, jnp.asarray(base), backend=backend)
    assert calls == [kernel] and n == 1000
    deq = hostcodec.decode_rows(q, s, n)
    np.testing.assert_allclose(deq + resid, eff - base, atol=1e-5)


# -- WirePolicy ---------------------------------------------------------------


def test_policy_legacy_regime_untouched_when_disarmed():
    """The policy's one regime: int8 until ``damping`` consecutive
    over-cap residuals vote it off, one flip."""
    pol = WirePolicy(damping=2)
    nb = 1 << 20
    assert pol.select(nb, np.float32) == "int8"
    for _ in range(2):
        pol.observe(delta_absmax=1.0, density=1.0, residual_ratio=0.9)
    assert pol.select(nb, np.float32) == "exact"
    assert pol.flips == 1


_TRACE_RATIOS = (0.0, 0.05, 0.25, 0.2500001, 0.9)
_TRACE_DENSITIES = (1e-3, 1.0 / 256, 0.01, 0.5, 1.0, 1.0)


def _policy_trace(seed, **kw):
    """Drive one WirePolicy through a seeded mix of selects and
    observations; the wire after each step ('i' int8, 'e' exact) and the
    flip count."""
    rng = np.random.default_rng(seed)
    pol = WirePolicy(**kw)
    out = []
    for _ in range(160):
        if rng.random() < 0.4:
            nbytes = int(rng.choice([0, 1, 4095, 4096, 1 << 20]))
            dt = np.float32 if rng.random() < 0.9 else np.int32
            out.append(pol.select(nbytes, dt, probe=bool(rng.random() < 0.8)))
        else:
            ratio = (None if rng.random() < 0.3
                     else float(rng.choice(_TRACE_RATIOS)))
            absmax = 0.0 if rng.random() < 0.1 else 1.0
            pol.observe(delta_absmax=absmax,
                        density=float(rng.choice(_TRACE_DENSITIES)),
                        residual_ratio=ratio)
            out.append(pol.wire)
    return "".join(w[0] for w in out), pol.flips


# Recorded from the WirePolicy that also carried an opt-in measured-cost
# regime, left disarmed: (constructor arguments, flips, wire after each of
# the 160 steps of _policy_trace seeded with the entry's index).
_POLICY_GOLDEN = [
    (dict(), 7,
     "iiiieiiiiiiiiiiiieeeeiiiiiiiiiiiieiiiiiieieiiiiiiiiiiiiiiiiiiiee"
     "eeeeeeeeeeeeeeeeiiiiiieeiieieeeeeeeeeeeeeeeeeeeieeiiieiiiieiiiii"
     "eiieiiiiiiieiiieiiiiiiieeeeeeeee"),
    (dict(damping=1), 29,
     "eeeiieeeeeeeeeeeeeeiiiiiieiiieiiieeeiieieiieiiiiiieiiieeeeeeeeei"
     "iieiieeeeeeeeeeeiiiieeieeeeiiiieeiieeeeiiiiieeeeeiiiieeeeieiiiee"
     "iieeeeeieieeeeiiiieeeeieeeiiieee"),
    (dict(damping=2), 8,
     "eieiieieeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeiieieeeeeeeeeeeiiiei"
     "iieeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeiiieeiiiieieeeeeeeeee"
     "eeeeeeeeeeeeiieiieiieeeieiiiiiee"),
    (dict(damping=5), 1,
     "eeiiiiiiiiiiiiiieiiieiiieieiieeeiiieieiieiiiieeiiiiiieiieiiiiiii"
     "iiiiieiiiiiiiieeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeee"
     "eeeeeeeeeeeeeeeeeeeieeeeeeeeeeee"),
    (dict(probe_after=1), 3,
     "iiiiiiieiiieieiiieeiiiieiieiieiiiiiieiiiiiieiiiieiiiiiiiieiiiiie"
     "iiiiieeeeeeeeeeeeeeeeeeeeeeeeeeeeieeeeeeeiiiiieiiiiiiiiiiiiieeee"
     "eeeeeeeeeeeeeeeeeeeeeeeeeeieeeie"),
    (dict(probe_after=4), 5,
     "ieeeiiiiiiieeeeeeeeeeeeeeeeeeeeeeeeeeeeiieiiiieiieeiieieiieiieie"
     "eeiieiiiieieiieeiiieeeeeeiieeeeeeeeeeeeeeeieieieeeiieiieiiieieii"
     "iiiiiiiiiieiieeeieeiieieeeeeeeee"),
    (dict(damping=2, probe_after=3, residual_cap=0.1, min_density=0.01), 5,
     "ieeeeeeeeeeeeeeeeeeeeeeeeieeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeeee"
     "eeeeeeeeeeeieeeeeeeeeeeeeieiiiiieeeeeeeeeeeeeeeeeeeeeeeeeeeieiee"
     "eeeeeeeeeeeeeeeeeeeeeeeieeeeeeee"),
]


@pytest.mark.parametrize("seed", range(len(_POLICY_GOLDEN)))
def test_policy_reproduces_recorded_choices(seed):
    """Choice for choice, every select and observe of a seeded sequence
    answers as recorded."""
    kw, flips, trace = _POLICY_GOLDEN[seed]
    assert _policy_trace(seed, **kw) == (trace, flips)
