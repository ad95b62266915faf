"""Narrow wire tiers (int4/fp8), the host-native fused codec, chunked
encode, and measured-cost wire selection.

Parity notes baked into the bounds below:

* The host codec divides ``absmax/qmax`` plainly; XLA jit compiles the same
  division to reciprocal-multiply, which can shift a handful of row scales
  by one ULP — so cross-path assertions are tolerance-based, never bitwise.
* fp8 (e4m3fn) has a 12.5% relative step, so cross-backend code ties at
  half-step boundaries can land a *full* step apart; fp8 bounds are in
  step units.

The ``pallas_interpret`` parametrisations are auto-marked slow by conftest;
the xla rows run in the ``scripts/tier1.sh`` fast gate.
"""
import numpy as np
import pytest

from repro.kernels.state_push import hostcodec
from repro.kernels.state_push import ops
from repro.state import wire as wire_mod
from repro.state.kv import GlobalTier
from repro.state.local import LocalTier
from repro.state.wire import (WireCostModel, WirePolicy, available_wires,
                              get_codec)
from repro.telemetry.spans import COMPILE_EVENT

BACKENDS = ("xla", "pallas_interpret")
ODD_SIZES = (1, 5, 130, 1000, 4097)

needs_fp8 = pytest.mark.skipif(not hostcodec.fp8_available(),
                               reason="ml_dtypes not installed")


def _rng(seed=0):
    return np.random.default_rng(seed)


def _pair(n, seed=0, scale=1.0):
    rng = _rng(seed)
    eff = (rng.normal(size=n) * scale).astype(np.float32)
    base = (rng.normal(size=n) * scale).astype(np.float32)
    return eff, base


# -- host codec: conservation, pad no-op, odd sizes, chunk invariance ---------


@pytest.mark.parametrize("qmax", [127, 7])
@pytest.mark.parametrize("n", ODD_SIZES)
def test_hostcodec_residual_conserves_delta(qmax, n):
    """deq + residual == delta exactly — error feedback loses nothing."""
    eff, base = _pair(n, seed=n)
    q, s, numel, resid = hostcodec.encode_quant(eff, base, qmax=qmax)
    assert numel == n and resid.shape == (n,)
    deq = hostcodec.decode_rows(q, s, n)
    np.testing.assert_allclose(deq + resid, eff - base, atol=1e-6)
    assert np.abs(q.astype(np.int32)).max() <= qmax


@pytest.mark.parametrize("qmax", [127, 7])
def test_hostcodec_pad_region_is_zero(qmax):
    n = 130                                   # 2 rows, 126 pad lanes
    eff, base = _pair(n, seed=3)
    q, s, numel, _ = hostcodec.encode_quant(eff, base, qmax=qmax)
    assert q.shape == (2, 128) and numel == n
    assert np.all(q.reshape(-1)[n:] == 0)


@pytest.mark.parametrize("chunk_rows", [1, 3, 7, 1024])
def test_hostcodec_chunked_matches_unchunked_bitwise(chunk_rows):
    """Chunks split on row boundaries and scales are per-row, so any chunk
    size yields bit-identical wire buffers."""
    n = 9 * 128 + 17
    eff, base = _pair(n, seed=9)
    q1, s1, _, r1 = hostcodec.encode_quant(eff, base, qmax=127, chunk_rows=chunk_rows)
    q2, s2, _, r2 = hostcodec.encode_quant(eff, base, qmax=127)
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


# -- int4 nibble packing ------------------------------------------------------


def test_int4_pack_roundtrips_full_code_range():
    q = np.tile(np.arange(-7, 8, dtype=np.int8), (3, 128))[:, :128]
    packed = hostcodec.pack_int4(q)
    assert packed.shape == (3, 64) and packed.dtype == np.uint8
    assert np.array_equal(hostcodec.unpack_int4(packed), q)


def test_int4_frame_halves_payload():
    eff, base = _pair(256 << 8, seed=6)
    f8 = get_codec("int8").encode(eff, base, backend="xla")[0]
    f4 = get_codec("int4").encode(eff, base, backend="xla")[0]
    assert f4.payload.nbytes * 2 == f8.payload.nbytes


# -- fp8 tier -----------------------------------------------------------------


@needs_fp8
@pytest.mark.parametrize("n", ODD_SIZES)
def test_hostcodec_fp8_conserves_and_never_nans(n):
    # huge dynamic range: without the pre-cast clip these overflow to NaN
    eff, base = _pair(n, seed=n, scale=1e4)
    q, s, numel, resid = hostcodec.encode_fp8(eff, base)
    deq = hostcodec.decode_rows(q, s, numel)
    assert not np.isnan(deq).any()
    np.testing.assert_allclose(deq + resid, eff - base, atol=1e-6)
    # e4m3 relative step is 2^-3: per-element error ≤ |delta|/8 + eps
    delta = eff - base
    assert np.abs(deq - delta).max() <= np.abs(delta).max() / 8.0 + 1e-6


@needs_fp8
def test_fp8_codec_registered_only_when_available():
    assert "fp8" in available_wires()
    assert get_codec("fp8").name == "fp8"


# -- xla / pallas_interpret parity matrix -------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("qmax", [127, 7])
@pytest.mark.parametrize("n", [130, 1000, 151936])
def test_quant_parity_host_vs_device(backend, qmax, n):
    """The device encode and the host fast path agree to quantisation
    precision (scales may differ by one ULP — see module docstring)."""
    import jax.numpy as jnp
    eff, base = _pair(n, seed=qmax + n)
    qh, sh, _, _ = hostcodec.encode_quant(eff, base, qmax=qmax)
    qd, sd, numel, _ = ops.encode_quant(jnp.asarray(eff), jnp.asarray(base),
                                        qmax=qmax, backend=backend)
    assert numel == n
    deq_h = hostcodec.decode_rows(qh, sh, n)
    deq_d = hostcodec.decode_rows(np.asarray(qd), np.asarray(sd), n)
    step = np.abs(eff - base).max() / qmax
    assert np.abs(deq_h - deq_d).max() <= step + 1e-6


@needs_fp8
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n", [130, 1000])
def test_fp8_parity_host_vs_device(backend, n):
    """fp8 ties at half-step boundaries can land a full e4m3 step apart
    across backends — the bound is in fp8-step units, deliberately loose."""
    import jax.numpy as jnp
    eff, base = _pair(n, seed=n)
    qh, sh, _, _ = hostcodec.encode_fp8(eff, base)
    qd, sd, numel, _ = ops.encode_fp8(jnp.asarray(eff), jnp.asarray(base),
                                      backend=backend)
    assert numel == n
    deq_h = hostcodec.decode_rows(qh, sh, n)
    deq_d = hostcodec.decode_rows(np.asarray(qd).astype(np.float32),
                                  np.asarray(sd), n)
    assert not np.isnan(deq_d).any()
    # one fp8 step of the largest magnitude in the row set
    bound = np.abs(eff - base).max() / 4.0 + 1e-6
    assert np.abs(deq_h - deq_d).max() <= bound


@pytest.mark.parametrize(
    "backend,n", [(b, 1000) for b in BACKENDS] + [(b, 151936) for b in BACKENDS],
    ids=list(BACKENDS) + [f"{b}-151936" for b in BACKENDS])
def test_residual_conservation_device_paths(backend, n):
    """Fused device encode's residual also conserves: deq + resid == delta
    to f32 rounding."""
    import jax.numpy as jnp
    eff, base = _pair(n, seed=11)
    q, s, n, resid = ops.encode_quant(jnp.asarray(eff), jnp.asarray(base),
                                      qmax=127, backend=backend)
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
    q, s, numel, resid = ops.encode_quant(je, jb, qmax=127)
    assert numel == n
    qs, ss, rs = ops._encode_fused(je, jb, 127, True)
    assert np.array_equal(q, np.asarray(qs))
    assert np.array_equal(s, np.asarray(ss))
    np.testing.assert_array_equal(resid,
                                  np.asarray(rs).reshape(-1)[:n])


@pytest.mark.parametrize("qmax", [127, 7])
@pytest.mark.parametrize("n", [1000, 151936])
def test_pallas_encode_matches_eager_kernel_bitwise(n, qmax):
    """The jitted Pallas encode returns the codes, scales and residual that
    the quantise kernel gives when called eagerly on the padded rows."""
    import jax.numpy as jnp
    from repro.kernels.common import round_up
    from repro.kernels.state_push.kernel import quantize_delta_pallas
    eff, base = _pair(n, seed=n + qmax, scale=0.1)
    je, jb = jnp.asarray(eff), jnp.asarray(base)
    lr, _ = ops._to_rows(je)
    br, _ = ops._to_rows(jb)
    rows = lr.shape[0]
    blk = min(256, round_up(rows, 8))
    pad = ((0, round_up(rows, blk) - rows), (0, 0))
    q, s = quantize_delta_pallas(jnp.pad(lr, pad), jnp.pad(br, pad),
                                 block_rows=blk, interpret=True,
                                 qmax=float(qmax))
    qe, se = np.asarray(q)[:rows], np.asarray(s)[:rows]
    re = (np.asarray(lr - br) - qe.astype(np.float32) * se).reshape(-1)[:n]
    qj, sj, numel, rj = ops.encode_quant(je, jb, qmax=qmax,
                                         backend="pallas_interpret")
    assert numel == n and qj.dtype == np.int8
    assert np.array_equal(qj, qe)
    assert np.array_equal(sj.view(np.uint32), se.view(np.uint32))
    assert np.array_equal(rj.view(np.uint32), re.view(np.uint32))


_PI = "pallas_interpret"
_PALLAS_OPS = {
    "encode_quant_127": lambda e, b: ops.encode_quant(e, b, qmax=127,
                                                      backend=_PI),
    "encode_quant_7": lambda e, b: ops.encode_quant(e, b, qmax=7,
                                                    backend=_PI),
    "encode_fp8": lambda e, b: ops.encode_fp8(e, b, backend=_PI),
    "quantize_delta": lambda e, b: ops.quantize_delta(e, b, backend=_PI),
    "apply_delta": lambda e, b: ops.apply_delta(
        b, *hostcodec.encode_quant(np.asarray(e), np.asarray(b))[:2],
        backend=_PI),
    "push": lambda e, b: ops.push(e, b, b, backend=_PI),
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

    compiles = []

    def listen(event, duration, **kw):
        if event == COMPILE_EVENT:
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        call(1531)
        compiles.clear()
        call(1531)
        assert compiles == []
        call(2711)
        assert compiles
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def test_host_fast_path_skips_jax_dispatch():
    """numpy operands on the xla backend return numpy wire buffers computed
    by the host codec — bitwise equal to calling hostcodec directly."""
    eff, base = _pair(130, seed=13)
    q, s, n, resid = ops.encode_quant(eff, base, qmax=127, backend="xla")
    qh, sh, _, rh = hostcodec.encode_quant(eff, base, qmax=127)
    assert type(q) is np.ndarray
    assert np.array_equal(q, qh) and np.array_equal(s, sh)
    assert np.array_equal(resid, rh)


# -- wire codecs end to end ---------------------------------------------------


@pytest.mark.parametrize("wire", ["int4", "fp8"])
def test_narrow_tier_push_converges_with_error_feedback(wire):
    """A narrow-tier push stream converges on the global value: per-push
    quantisation error is carried by the residual, not lost."""
    if wire == "fp8" and not hostcodec.fp8_available():
        pytest.skip("ml_dtypes not installed")
    n = 256 << 8                              # 256 KB
    gt = GlobalTier()
    gt.set("w", np.zeros(n, np.float32).tobytes(), host="up")
    lt = LocalTier("h0", gt)
    lt.set_wire_tiers(wire)
    lt.pull("w")
    lt.snapshot_base("w")
    LocalTier("q", gt).pull("w")              # wire interest: frame it
    rng = _rng(17)
    view = lt.replica("w").buf.view(np.float32)
    total = np.zeros(n, np.float32)
    for _ in range(6):
        u = (rng.normal(size=n) * 0.01).astype(np.float32)
        view[:] += u
        total += u
        lt.push_delta("w", wire=wire)
    got = np.frombuffer(gt.get("w", host="check"), np.float32)
    # after the final push one residual remains un-pushed: bounded by one
    # quantisation step of the last encode's per-row absmax (~N(0, 0.01)
    # updates plus carried residual → well under one update magnitude)
    assert np.abs(got - total).max() <= 0.01
    assert np.abs(got - total).mean() <= 2e-3


def test_int4_wire_frame_decodes_through_frame_api():
    eff, base = _pair(130, seed=19)
    frame, resid = get_codec("int4").encode(eff, base, backend="xla")
    assert frame.wire == "int4" and frame.payload.dtype == np.uint8
    deq = frame.decode()
    np.testing.assert_allclose(deq + resid, eff - base, atol=1e-6)
    q, s = frame.codes()
    assert q.dtype == np.int8 and np.abs(q.astype(np.int32)).max() <= 7


# -- WireCostModel ------------------------------------------------------------


def test_cost_model_bucket_clamps():
    assert WireCostModel.bucket(1) == WireCostModel.MIN_BUCKET
    assert WireCostModel.bucket(1 << 20) == 20
    assert WireCostModel.bucket(1 << 40) == WireCostModel.MAX_BUCKET


def test_cost_model_frame_bytes():
    vb = 128 * 4 * 8                          # 8 rows of f32
    assert WireCostModel.frame_bytes("exact", vb) == vb
    assert WireCostModel.frame_bytes("int8", vb) == 8 * 128 + 8 * 4
    assert WireCostModel.frame_bytes("int4", vb) == 8 * 64 + 8 * 4
    assert WireCostModel.frame_bytes("fp8", vb) == 8 * 128 + 8 * 4


def test_cost_model_predict_needs_evidence_then_learns():
    m = WireCostModel()
    assert m.predict("int8", 1 << 20) is None
    m.observe("int8", 1 << 20, 2_000_000, wall_ns=5_000_000)
    p = m.predict("int8", 1 << 20)
    assert p == pytest.approx(5_000_000)
    # EWMA moves toward new evidence without jumping
    m.observe("int8", 1 << 20, 4_000_000, wall_ns=8_000_000)
    p2 = m.predict("int8", 1 << 20)
    assert 5_000_000 < p2 < 8_000_000


def test_cost_model_rescales_from_nearest_bucket():
    m = WireCostModel()
    m.observe("exact", 1 << 20, 1_000_000, wall_ns=1_500_000)
    # 4 MB never observed: the 1 MB evidence rescales linearly
    p = m.predict("exact", 1 << 22)
    assert p == pytest.approx(6_000_000)


def test_cost_model_link_bandwidth_term():
    m = WireCostModel(link_bytes_per_s=1e6)   # 1 MB/s — glacial
    m.observe("exact", 1 << 20, 1_000, wall_ns=2_000)
    m.observe("int8", 1 << 20, 500_000, wall_ns=600_000)
    # exact ships 4x the bytes: on a slow link int8 must win
    assert m.predict("int8", 1 << 20) < m.predict("exact", 1 << 20)


def test_cost_model_seed_from_bench_schema():
    bench = {"value_kb": [64, 4096],
             "64kb": {"exact": {"encode_us_p50": 50.0, "push_us_p50": 100.0,
                                "bytes_per_push": 65536},
                      "int8": {"encode_us_p50": 150.0, "push_us_p50": 300.0,
                               "bytes_per_push": 17408},
                      "auto": {"push_us_p50": 99.0},
                      "crossover_mbps": {"int8": 100.0}},
             "4096kb": {"exact": {"encode_us_p50": 4000.0,
                                  "push_us_p50": 8000.0}}}
    m = WireCostModel()
    assert m.seed(bench) == 3                 # auto/crossover rows skipped
    assert m.predict("exact", 64 << 10) == pytest.approx(100.0 * 1e3)
    assert m.predict("int8", 64 << 10) == pytest.approx(300.0 * 1e3)
    snap = m.snapshot()
    assert 16 in snap["exact"] and 22 in snap["exact"]


# -- WirePolicy: measured-cost regime -----------------------------------------


def _armed(**kw):
    return wire_mod.enable_cost_model(**kw)


def test_policy_cost_mode_probes_unknown_then_argmins():
    m = _armed()
    pol = WirePolicy(tiers=("int8",))
    nb = 1 << 20
    # nothing observed: exact is first unknown → probe it
    assert pol.select(nb, np.float32) == "exact"
    m.observe("exact", nb, 1_000_000, wall_ns=2_000_000)
    # int8 still unknown → probed next
    assert pol.select(nb, np.float32) == "int8"
    m.observe("int8", nb, 500_000, wall_ns=900_000)
    assert pol.select(nb, np.float32) == "int8"     # measured cheapest
    m.observe("int8", nb, 9_000_000, wall_ns=20_000_000)
    assert pol.select(nb, np.float32) == "exact"    # evidence flipped it
    assert pol.flips >= 2


def test_policy_cost_mode_residual_ban_and_reprobe():
    m = _armed()
    pol = WirePolicy(tiers=("int8",), damping=3, probe_after=4)
    nb = 1 << 20
    m.observe("exact", nb, 1_000_000, wall_ns=2_000_000)
    m.observe("int8", nb, 100_000, wall_ns=200_000)
    assert pol.select(nb, np.float32) == "int8"
    # 3 consecutive over-cap residuals ban the tier despite its low cost
    for _ in range(3):
        pol.observe(delta_absmax=1.0, density=1.0,
                    residual_ratio=0.9, wire="int8")
    assert pol.select(nb, np.float32) == "exact"   # advances the ban clock
    # every probe_after-th select routes one re-qualification push onto the
    # banned tier (the assert above already advanced the clock once)
    wires = [pol.select(nb, np.float32) for _ in range(4)]
    assert wires.count("int8") == 1
    assert all(w == "exact" for w in wires if w != "int8")
    # the re-probe comes back clean → tier un-banned, wins again on cost
    pol.observe(delta_absmax=1.0, density=1.0,
                residual_ratio=0.01, wire="int8")
    assert pol.select(nb, np.float32) == "int8"


def test_policy_cost_mode_structural_fallbacks_hold():
    _armed()
    pol = WirePolicy(tiers=("int8", "int4"))
    assert pol.select(64, np.float32) == "exact"          # below min_bytes
    assert pol.select(1 << 20, np.int32) == "exact"       # non-float


def test_policy_legacy_regime_untouched_when_disarmed():
    pol = WirePolicy(tiers=("int8",), damping=2)
    nb = 1 << 20
    assert pol.select(nb, np.float32) == "int8"
    for _ in range(2):
        pol.observe(delta_absmax=1.0, density=1.0,
                    residual_ratio=0.9, wire="int8")
    assert pol.select(nb, np.float32) == "exact"
    assert pol.flips == 1


def test_auto_push_with_cost_model_takes_cheapest_wire():
    """End to end: an armed cost model seeded to favour int8 routes an
    ``auto`` push onto the int8 wire; spans aside, the global value still
    converges."""
    n = 256 << 8
    m = _armed()
    for w in available_wires():
        # seed: int8 measured far cheaper than anything else at this size
        ns = 100_000 if w == "int8" else 10_000_000
        m.observe(w, n * 4, ns, wall_ns=ns * 2)
    gt = GlobalTier()
    gt.set("w", np.zeros(n, np.float32).tobytes(), host="up")
    lt = LocalTier("h0", gt)
    lt.set_wire_tiers(*[w for w in available_wires() if w != "exact"])
    lt.pull("w")
    lt.snapshot_base("w")
    LocalTier("q", gt).pull("w")
    view = lt.replica("w").buf.view(np.float32)
    u = (_rng(23).normal(size=n) * 0.01).astype(np.float32)
    view[:] += u
    lt.push_delta("w", wire="auto")
    assert lt.wire_policy("w").wire == "int8"
    got = np.frombuffer(gt.get("w", host="check"), np.float32)
    assert np.abs(got - u).max() <= np.abs(u).max() / 254.0 + 1e-6
