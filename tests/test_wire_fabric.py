"""Symmetric wire fabric: quantised delta pulls (with stale-base fallback
and pull-side error feedback), peer broadcast (subscriber churn, base
coherence, device apply), adaptive wire selection (flip-flop damping), and
exact/int8 parity of the pull-direction kernel entry points on both the
``xla`` and ``pallas_interpret`` backends.

The ``pallas_interpret`` parametrisations are auto-marked slow by conftest;
the xla rows run in the ``scripts/tier1.sh`` fast gate."""
import numpy as np
import pytest

from repro.kernels.state_push import apply_pull, dequantize, encode_pull
from repro.state.kv import GlobalTier
from repro.state.local import INT8_WIRE_MIN_BYTES, LocalTier
from repro.state.wire import WireFrame, WirePolicy, get_codec

BACKENDS = ("xla", "pallas_interpret")


def _rng(seed=0):
    return np.random.default_rng(seed)


def _setup(n, *, seed=0, init=None, **gt_kwargs):
    """Global tier with an n-float key, a pusher (base armed) and a puller
    (warm full replica)."""
    gt = GlobalTier(**gt_kwargs)
    init = np.zeros(n, np.float32) if init is None else init
    gt.set("w", init.tobytes(), host="up")
    pusher = LocalTier("pusher", gt)
    pusher.pull("w")
    pusher.snapshot_base("w")
    puller = LocalTier("puller", gt)
    puller.pull("w")
    return gt, pusher, puller


def _global(gt, key="w"):
    return np.frombuffer(gt.get(key, host="check"), np.float32)


# -- delta pulls ---------------------------------------------------------------


def test_warm_int8_refresh_moves_under_30_percent():
    """Acceptance criterion: a warm-replica 4 MB f32 refresh via
    ``pull(wire="int8")`` moves ≤ 30% of the exact (full) pull bytes."""
    size = 4 << 20
    n = size // 4
    gt, pusher, puller = _setup(n)
    view = pusher.replica("w").buf.view(np.float32)
    view[:] += (_rng(1).normal(size=n) * 0.01).astype(np.float32)
    pusher.push_delta("w", wire="int8")
    gt.reset_metrics()
    moved = puller.pull("w", wire="int8")
    assert 0 < moved <= 0.30 * size
    assert gt.bytes_pulled["puller"] == moved
    got = puller.replica("w").buf.view(np.float32)
    want = _global(gt)
    # one delta pull: error bounded by one quantisation step of the delta
    assert np.abs(got - want).max() <= 0.01 * 6 / 254.0 + 1e-6
    # up to date now: the next pull moves nothing
    assert puller.pull("w", wire="int8") == 0


def test_exact_delta_pull_is_exact():
    n = INT8_WIRE_MIN_BYTES // 4 * 4
    gt, pusher, puller = _setup(n)
    view = pusher.replica("w").buf.view(np.float32)
    view[:] += (_rng(2).normal(size=n)).astype(np.float32)
    pusher.push_delta("w", wire="exact")
    moved = puller.pull("w", wire="exact")
    assert moved == n * 4                       # the f32 delta frame
    np.testing.assert_array_equal(
        puller.replica("w").buf.view(np.float32), _global(gt))


def test_repeated_int8_pulls_carry_residual():
    """Pull-side error feedback: across many quantised refreshes the
    replica tracks the global value within ~one step (no random walk)."""
    n = INT8_WIRE_MIN_BYTES // 4 * 8
    gt, pusher, puller = _setup(n)
    view = pusher.replica("w").buf.view(np.float32)
    rng = _rng(3)
    for _ in range(12):
        view[:] += (rng.normal(size=n) * 0.01).astype(np.float32)
        pusher.push_delta("w", wire="exact")    # global moves exactly
        puller.pull("w", wire="int8")           # replica refreshes quantised
    got = puller.replica("w").buf.view(np.float32)
    assert np.abs(got - _global(gt)).max() <= 2 * 0.01 * 6 / 254.0
    assert puller.replica("w").pull_residual is not None


@pytest.mark.parametrize("n", [1, 5, 130, 1000, 4097, 151936])
def test_pull_wire_int8_converges_with_pull_residual(n):
    """``GlobalTier.pull_wire`` int8 refreshes at odd sizes: the residual
    handed back holds exactly what the replica is behind by, and stays
    within half a step of each row's scale — debt carried, not lost."""
    gt, pusher, puller = _setup(n)
    view = pusher.replica("w").buf.view(np.float32)
    base_version = puller.replica("w").global_version
    replica = np.zeros(n, np.float32)
    residual = None
    rng = _rng(n)
    for _ in range(8):
        view[:] += (rng.normal(size=n) * 0.01).astype(np.float32)
        pusher.push_delta("w", wire="exact")    # global moves exactly
        frame, base_version, residual = gt.pull_wire(
            "w", base_version, wire="int8", residual=residual,
            exclude_origin=puller.origin_id, host="puller")
        assert frame.wire == "int8" and frame.numel == n
        replica += frame.decode()
    want = _global(gt)
    np.testing.assert_allclose(replica + residual, want, atol=1e-5)
    half_step = np.repeat(frame.scales[:, 0], 128)[:n] / 2
    assert np.all(np.abs(replica - want) <= half_step * (1 + 1e-5) + 1e-6)


def test_stale_base_falls_back_to_full_pull():
    """A base older than the retained window floor can't be served as a
    delta: the pull degrades to a full (exact) re-pull."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt, pusher, puller = _setup(n, delta_window=2)
    view = pusher.replica("w").buf.view(np.float32)
    for _ in range(5):                          # window keeps only the last 2
        view[:] += 1.0
        pusher.push_delta("w", wire="int8")
    gt.reset_metrics()
    moved = puller.pull("w", wire="int8")
    assert moved == n * 4                       # full-value bytes
    np.testing.assert_array_equal(
        puller.replica("w").buf.view(np.float32), _global(gt))
    assert puller.pull("w") == 0                # re-based: now current


def test_non_delta_write_invalidates_window():
    """set()/push() overwrite semantics can't be expressed as retained
    deltas: pulls from older bases full-pull, exactly."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt, pusher, puller = _setup(n)
    view = pusher.replica("w").buf.view(np.float32)
    view[:] += 2.0
    pusher.push_delta("w", wire="int8")
    gt.set("w", np.full(n, 7.0, np.float32).tobytes(), host="up")
    gt.reset_metrics()
    moved = puller.pull("w", wire="int8")
    assert moved == n * 4
    np.testing.assert_array_equal(puller.replica("w").buf.view(np.float32),
                                  np.full(n, 7.0, np.float32))


def test_pull_after_grow_falls_back():
    """append() grows the value and invalidates the window: the warm
    replica full-pulls the grown value instead of mis-applying a delta."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt, pusher, puller = _setup(n, init=np.full(n, 1.0, np.float32))
    gt.append("w", np.full(n, 5.0, np.float32).tobytes(), host="up")
    moved = puller.pull("w", wire="int8")
    assert moved == 2 * n * 4
    got = puller.replica("w").buf.view(np.float32)
    np.testing.assert_array_equal(got[n:], 5.0)


@pytest.mark.parametrize("wire", ["bogus", "int4", "fp8"])
def test_pull_rejects_bogus_wire(wire):
    n = INT8_WIRE_MIN_BYTES // 4
    gt, pusher, puller = _setup(n)
    pusher.replica("w").buf.view(np.float32)[:] += 1.0
    pusher.push_delta("w", wire="int8")
    with pytest.raises(ValueError):
        puller.pull("w", wire=wire)


@pytest.mark.parametrize("wire", ["bogus", "int4", "fp8"])
def test_push_rejects_unknown_wire(wire):
    """Only the exact and int8 wires exist; a push names one of them or
    ``auto``, and the value is untouched when it does not."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt, pusher, _ = _setup(n)
    pusher.replica("w").buf.view(np.float32)[:] += 1.0
    with pytest.raises(ValueError):
        pusher.push_delta("w", wire=wire)
    np.testing.assert_array_equal(_global(gt), 0.0)


# -- peer broadcast ------------------------------------------------------------


def test_subscribed_peer_converges_with_zero_pull_bytes():
    """Acceptance criterion: after one int8 push a subscribed peer replica
    holds the new global value and its next pull moves zero bytes."""
    n = (4 << 20) // 4
    gt, pusher, _ = _setup(n)
    peer = LocalTier("peer", gt)
    peer.subscribe("w")
    gt.reset_metrics()
    view = pusher.replica("w").buf.view(np.float32)
    view[:] += (_rng(5).normal(size=n) * 0.01).astype(np.float32)
    pusher.push_delta("w", wire="int8")
    gt.flush_broadcasts()                       # fan-out is async: drain it
    # the peer replica converged through the broadcast alone
    np.testing.assert_array_equal(peer.replica("w").buf.view(np.float32),
                                  _global(gt))
    assert gt.bytes_pulled.get("peer", 0) == 0
    assert peer.pull("w", wire="int8") == 0     # zero pull bytes
    assert gt.total_broadcast() > 0             # push-side fan-out accounted


def test_broadcast_updates_base_no_repush():
    """The broadcast delta lands in the peer's delta base too: its next
    push ships only its own writes, never the peer-received delta."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt, pusher, _ = _setup(n)
    peer = LocalTier("peer", gt)
    peer.subscribe("w")
    peer.snapshot_base("w")
    pview = pusher.replica("w").buf.view(np.float32)
    pview[:] += 2.0
    pusher.push_delta("w", wire="int8")         # broadcast lands at the peer
    gt.flush_broadcasts()
    peer.push_delta("w", wire="exact")          # peer pushes nothing new
    np.testing.assert_allclose(_global(gt), 2.0, atol=1e-5)


def test_broadcast_applies_to_fresh_device_replica():
    """A device-resident subscribed replica stays fresh: the frame is
    applied to the device value and base, so a later device-native push
    carries no phantom delta."""
    jnp = pytest.importorskip("jax.numpy")
    n = INT8_WIRE_MIN_BYTES // 4
    gt, pusher, _ = _setup(n)
    peer = LocalTier("peer", gt)
    peer.subscribe("w")
    peer.to_device("w", track_delta=True)
    pview = pusher.replica("w").buf.view(np.float32)
    pview[:] += 2.0
    pusher.push_delta("w", wire="int8")
    gt.flush_broadcasts()
    assert not peer.device_stale("w")
    np.testing.assert_allclose(np.asarray(peer.device_replica("w").value),
                               _global(gt), atol=1e-6)
    peer.push_delta("w", wire="int8")           # device-native, zero delta
    np.testing.assert_allclose(_global(gt), 2.0, atol=1e-5)
    assert jnp is not None


def test_subscriber_churn_host_leaves_mid_broadcast():
    """A subscriber whose host left (replica evicted / callback raising) is
    dropped mid-broadcast; the healthy peers still receive the frame."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt, pusher, _ = _setup(n)
    healthy = LocalTier("healthy", gt)
    healthy.subscribe("w")
    leaver = LocalTier("leaver", gt)
    leaver.subscribe("w")
    calls = {"dead": 0}

    def dead_cb(key, frame):
        calls["dead"] += 1
        raise RuntimeError("host went away")

    gt.subscribe("w", "dead-host", dead_cb)
    # the leaver's host fails between subscribe and push: drop() cancels
    # its subscription, simulating departure mid-stream
    leaver.drop()
    view = pusher.replica("w").buf.view(np.float32)
    view[:] += 1.0
    pusher.push_delta("w", wire="int8")
    gt.flush_broadcasts()
    np.testing.assert_array_equal(healthy.replica("w").buf.view(np.float32),
                                  _global(gt))
    assert calls["dead"] == 1                   # delivered once, then dropped
    view[:] += 1.0
    pusher.push_delta("w", wire="int8")
    gt.flush_broadcasts()
    assert calls["dead"] == 1                   # raising subscriber was culled
    np.testing.assert_array_equal(healthy.replica("w").buf.view(np.float32),
                                  _global(gt))


def test_out_of_order_frame_skipped_then_repaired_by_pull():
    """A frame that doesn't extend the replica's exact version is skipped
    (never misapplied); the next pull repairs through the delta window."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt, pusher, _ = _setup(n)
    peer = LocalTier("peer", gt)
    peer.subscribe("w")
    view = pusher.replica("w").buf.view(np.float32)
    view[:] += 1.0
    pusher.push_delta("w", wire="exact")
    gt.flush_broadcasts()
    # replay the same frame versions: prev no longer matches -> skipped
    stale = WireFrame(wire="exact", numel=n,
                      payload=np.full(n, 100.0, np.float32),
                      prev_version=0, version=1)
    peer._deliver("w", stale)
    assert float(peer.replica("w").buf.view(np.float32).max()) < 50.0
    view[:] += 1.0
    pusher.push_delta("w", wire="exact")        # peer applies (versions chain)
    gt.flush_broadcasts()
    assert peer.pull("w") == 0 or True          # and pull reconciles any gap
    np.testing.assert_allclose(peer.replica("w").buf.view(np.float32),
                               _global(gt), atol=1e-5)


def test_racing_pushers_never_replay_their_own_frame():
    """Regression: a pusher whose push raced a peer's (its frame landed on
    top of a version it never saw) must not re-apply its own delta when it
    later delta-pulls — own-origin frames are excluded from the window
    composition."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt = GlobalTier()
    init = np.full(n, 10.0, np.float32)
    gt.set("w", init.tobytes(), host="up")
    a, b = LocalTier("a", gt), LocalTier("b", gt)
    for lt in (a, b):
        lt.pull("w")
        lt.snapshot_base("w")
    a.replica("w").buf.view(np.float32)[:] += 1.0
    a.push_delta("w", wire="exact")
    # b's push lands second: its frame's prev_version is a's version, which
    # b has not seen — b's global_version goes stale
    b.replica("w").buf.view(np.float32)[:] += 2.0
    b.push_delta("w", wire="exact")
    np.testing.assert_allclose(_global(gt), 13.0, atol=1e-5)
    moved = b.pull("w")                         # catches up on a's frame ONLY
    assert moved > 0
    np.testing.assert_allclose(b.replica("w").buf.view(np.float32), 13.0,
                               atol=1e-5)      # NOT 15.0 (own +2 replayed)
    assert b.pull("w") == 0
    # and b's next push carries nothing new
    b.push_delta("w", wire="exact")
    np.testing.assert_allclose(_global(gt), 13.0, atol=1e-5)


def test_broadcast_applies_f64_frames_with_value_dtype():
    """Regression: a broadcast frame for a float64 key must be applied
    through f64 views — an f32 reinterpretation scrambles the bytes."""
    n = INT8_WIRE_MIN_BYTES // 8
    gt = GlobalTier()
    gt.set("w", np.full(n, 1.0, np.float64).tobytes(), host="up")
    pusher = LocalTier("p", gt)
    pusher.pull("w")
    pusher.snapshot_base("w")
    peer = LocalTier("peer", gt)
    peer.subscribe("w")
    pusher.replica("w").buf.view(np.float64)[:] += 2.0
    pusher.push_delta("w", dtype=np.float64, wire="int8")
    gt.flush_broadcasts()                       # fan-out is async: drain it
    got = peer.replica("w").buf.view(np.float64)
    want = np.frombuffer(gt.get("w", host="x"), np.float64)
    np.testing.assert_allclose(got, want, atol=1e-4)
    np.testing.assert_allclose(got, 3.0, atol=1e-4)


def test_full_pull_fallback_refreshes_base_no_repush():
    """Regression: the warm-refresh full-pull fallback re-stamps the delta
    base from the pulled buffer — otherwise the next push re-applies every
    peer write since the stale snapshot."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt, pusher, puller = _setup(n)
    puller.snapshot_base("w")                   # base at version v0
    gt.set("w", np.full(n, 7.0, np.float32).tobytes(), host="up")  # window gone
    moved = puller.pull("w")                    # fallback full pull
    assert moved == n * 4
    puller.push_delta("w", wire="exact")        # nothing local: no-op push
    np.testing.assert_allclose(_global(gt), 7.0, atol=1e-6)


def test_exact_wire_pushes_fresh_device_value():
    """Regression: the exact wire must push from a fresh DeviceReplica's
    arrays, like the int8 path — a policy flip to exact on a
    device-resident key must not silently drop device-side updates."""
    pytest.importorskip("jax")
    n = INT8_WIRE_MIN_BYTES // 4
    gt, pusher, _ = _setup(n)
    dv = pusher.to_device("w", track_delta=True)
    pusher.update_device("w", dv + 2.0)         # device-side compute
    pusher.replica("w").buf.view(np.float32)[:] = 1e9   # poison host copy
    pusher.push_delta("w", wire="exact")
    np.testing.assert_allclose(_global(gt), 2.0, atol=1e-6)
    pusher.push_delta("w", wire="exact")        # base rebound: no re-push
    np.testing.assert_allclose(_global(gt), 2.0, atol=1e-6)


def test_stale_refresh_keeps_unpushed_local_writes():
    """Regression: the full-pull fallback must not clobber a replica's
    un-pushed local writes — warm pulls on a dirty replica stay a no-op
    (legacy semantics) until the writes are pushed."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt, pusher, puller = _setup(n, delta_window=2)
    puller.snapshot_base("w")
    puller.replica("w").buf.view(np.float32)[0] += 5.0
    puller.mark_dirty("w", 0, 4)                # un-pushed local write
    view = pusher.replica("w").buf.view(np.float32)
    for _ in range(5):                          # window floor passes puller
        view[:] += 1.0
        pusher.push_delta("w", wire="int8")
    assert puller.pull("w") == 0                # no clobber: writes pending
    assert puller.replica("w").buf.view(np.float32)[0] == 5.0
    puller.push_delta("w", wire="exact")        # ship the local write
    assert puller.pull("w") == n * 4            # clean now: full refresh
    np.testing.assert_allclose(_global(gt)[0], 10.0, atol=1e-3)
    np.testing.assert_allclose(
        puller.replica("w").buf.view(np.float32), _global(gt), atol=1e-6)


def test_inplace_exact_push_keeps_warm_pull_free():
    """Regression: the zero-copy in-place exact path (sole consumer, or
    sub-threshold keys) must keep the pusher's base version current — its
    warm pulls stay 0-byte no-ops instead of full re-pulls per push."""
    n = 1024                                    # sub-threshold f32 key
    gt = GlobalTier()
    gt.set("w", np.zeros(n, np.float32).tobytes(), host="up")
    lt = LocalTier("h", gt)
    lt.pull("w")
    lt.snapshot_base("w")
    gt.reset_metrics()
    for _ in range(3):
        lt.replica("w").buf.view(np.float32)[:] += 1.0
        lt.push_delta("w", wire="exact")        # in-place legacy path
        assert lt.pull("w") == 0                # warm pull: no re-pull
    assert gt.bytes_pulled.get("h", 0) == 0
    np.testing.assert_allclose(_global(gt), 3.0, atol=1e-6)


def test_container_sibling_tiers_are_distinct_fabric_parties():
    """Regression: container tiers share a metrics host id (`runtime`
    re-points ``host_id`` at the physical host) but must remain distinct
    wire-fabric parties — a sibling's frames are NOT 'own frames' and a
    delta pull must deliver them."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt = GlobalTier()
    gt.set("w", np.zeros(n, np.float32).tobytes(), host="up")
    a = LocalTier("host0/c1", gt)
    b = LocalTier("host0/c2", gt)
    a.host_id = b.host_id = "host0"             # what container mode does
    for lt in (a, b):
        lt.pull("w")
        lt.snapshot_base("w")
    a.replica("w").buf.view(np.float32)[:] += 3.0
    a.push_delta("w", wire="int8")
    moved = b.pull("w", wire="int8")
    assert moved > 0                            # sibling's frame delivered
    np.testing.assert_allclose(b.replica("w").buf.view(np.float32), 3.0,
                               atol=1e-4)
    # and both siblings can hold broadcast subscriptions at once
    a.subscribe("w")
    b.subscribe("w")
    b.replica("w").buf.view(np.float32)[:] += 1.0
    b.push_delta("w", wire="exact")
    gt.flush_broadcasts()
    np.testing.assert_allclose(a.replica("w").buf.view(np.float32), 4.0,
                               atol=1e-4)


def test_write_only_keys_retain_no_frames():
    """Demand gating: with no other warm puller or subscriber, exact f32
    pushes stay on the zero-copy in-place path (no value-sized memcpy
    accounted) and nothing is retained; the first consumer full-pulls once
    and flips later pushes onto the frame path."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt = GlobalTier()
    gt.set("w", np.zeros(n, np.float32).tobytes(), host="up")
    pusher = LocalTier("pusher", gt)
    pusher.pull("w")
    pusher.snapshot_base("w")
    assert not gt.wire_interest("w", exclude="pusher")
    gt.reset_metrics()
    pusher.replica("w").buf.view(np.float32)[:] += 1.0
    pusher.push_delta("w", wire="exact")
    assert gt.total_copied() == 0               # in-place, no frame built
    late = LocalTier("late", gt)
    late.pull("w")                              # full pull declares interest
    assert gt.wire_interest("w", exclude="pusher")
    pusher.replica("w").buf.view(np.float32)[:] += 1.0
    pusher.push_delta("w", wire="exact")        # now recorded
    assert late.pull("w", wire="exact") == n * 4   # served as a delta
    np.testing.assert_array_equal(late.replica("w").buf.view(np.float32),
                                  _global(gt))


# -- adaptive wire selection ---------------------------------------------------


@pytest.mark.parametrize("nbytes", [0, 1, INT8_WIRE_MIN_BYTES - 1,
                                    INT8_WIRE_MIN_BYTES])
@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32,
                                   np.int64, np.bool_])
def test_policy_structural_fallbacks(dtype, nbytes):
    """Non-float values, and float values under INT8_WIRE_MIN_BYTES, ride
    the exact wire whatever the policy has learned."""
    p = WirePolicy()
    want = ("int8" if np.dtype(dtype).kind == "f"
            and nbytes >= INT8_WIRE_MIN_BYTES else "exact")
    assert p.select(nbytes, dtype) == want
    assert p.wire == "int8"                     # the learned choice is kept


@pytest.mark.parametrize("damping", [1, 2, 3, 5])
def test_policy_flips_after_damping_and_back(damping):
    p = WirePolicy(damping=damping)
    bad = dict(delta_absmax=1.0, density=0.9, residual_ratio=2.0)
    good = dict(delta_absmax=1.0, density=0.9, residual_ratio=0.001)
    for _ in range(2):
        for _ in range(damping - 1):
            p.observe(**bad)
        assert p.wire == "int8"                 # not yet: damping holds
        p.observe(**good)                       # confirming: streak resets
    for _ in range(damping):
        p.observe(**bad)
    assert p.wire == "exact"                    # damping consecutive -> flip
    for _ in range(damping - 1):
        p.observe(**good)
    assert p.wire == "exact"
    p.observe(**good)
    assert p.wire == "int8"                     # healthy again -> flip back
    assert p.flips == 2


def test_policy_flip_flop_damped():
    """Alternating good/bad observations never accumulate a streak: the
    wire stays put instead of thrashing."""
    p = WirePolicy(damping=2)
    bad = dict(delta_absmax=1.0, density=0.9, residual_ratio=2.0)
    good = dict(delta_absmax=1.0, density=0.9, residual_ratio=0.0)
    for _ in range(10):
        p.observe(**bad)
        p.observe(**good)
    assert p.wire == "int8"
    # zero-delta pushes teach nothing either
    p.observe(delta_absmax=0.0, density=0.0, residual_ratio=9.9)
    assert p.wire == "int8"


def test_policy_prefers_exact_for_sparse_deltas():
    p = WirePolicy(damping=1)
    p.observe(delta_absmax=1.0, density=1e-5, residual_ratio=0.0)
    assert p.wire == "exact"


def test_policy_exact_observations_never_vote_int8():
    """Regression: exact-wire pushes carry no quantisation evidence
    (residual_ratio=None) — they must not vote the policy back onto int8,
    or a key int8 genuinely mishandles would thrash exact↔int8 forever.
    Returning to int8 happens only through an explicit probe push."""
    p = WirePolicy(damping=1, probe_after=3)
    big, f32 = 1 << 20, np.float32
    p.observe(delta_absmax=1.0, density=0.9, residual_ratio=2.0)
    assert p.wire == "exact"
    for _ in range(2):                          # dense exact pushes: no vote
        p.observe(delta_absmax=1.0, density=0.9)
        assert p.wire == "exact" and p.select(big, f32) == "exact"
    p.observe(delta_absmax=1.0, density=0.9)    # 3rd: probe clock expires
    assert p.select(big, f32, probe=False) == "exact"   # pulls don't consume
    assert p.select(big, f32) == "int8"         # exactly one probe push
    assert p.select(big, f32) == "exact"        # then back until evidence
    p.observe(delta_absmax=1.0, density=0.9, residual_ratio=0.0)
    assert p.wire == "int8"                     # healthy probe re-qualifies


@pytest.mark.parametrize("probe_after", [1, 4, 8])
def test_policy_reprobes_int8_every_probe_after(probe_after):
    """Backed off to exact, the policy routes one push onto int8 after
    every ``probe_after`` dense exact pushes; a failed probe keeps exact
    without a flip, a clean one re-qualifies int8."""
    p = WirePolicy(damping=1, probe_after=probe_after)
    big, f32 = 1 << 20, np.float32
    bad = dict(delta_absmax=1.0, density=0.9, residual_ratio=2.0)
    p.observe(**bad)
    assert p.wire == "exact" and p.flips == 1
    for _ in range(3):
        for _ in range(probe_after):
            assert p.select(big, f32) == "exact"
            p.observe(delta_absmax=1.0, density=0.9)
        assert p.select(big, f32) == "int8"     # the probe push
        p.observe(**bad)                        # it fails: stay on exact
        assert p.wire == "exact" and p.flips == 1
    for _ in range(probe_after):
        p.observe(delta_absmax=1.0, density=0.9)
    assert p.select(big, f32) == "int8"
    p.observe(delta_absmax=1.0, density=0.9, residual_ratio=0.0)
    assert p.wire == "int8" and p.flips == 2


_CAP, _DENSE = 0.25, 1.0 / 256                  # WirePolicy's defaults


@pytest.mark.parametrize("ratio,density,want", [
    (_CAP, 1.0, "int8"),
    (float(np.nextafter(_CAP, 1.0)), 1.0, "exact"),
    (0.0, _DENSE, "int8"),
    (0.0, float(np.nextafter(_DENSE, 0.0)), "exact"),
    (None, _DENSE, "int8"),
    (None, float(np.nextafter(_DENSE, 0.0)), "exact"),
], ids=["at-cap", "over-cap", "at-min-density", "under-min-density",
        "exact-push-at-min-density", "exact-push-under-min-density"])
def test_policy_residual_cap_and_min_density_edges(ratio, density, want):
    """``residual_cap`` and ``min_density`` are strict bounds: a residual
    at the cap or a density at the floor keeps int8; one ULP past either
    votes exact, from an int8 push or an exact one."""
    p = WirePolicy(damping=1)
    assert (p.residual_cap, p.min_density) == (_CAP, _DENSE)
    p.observe(delta_absmax=1.0, density=density, residual_ratio=ratio)
    assert p.wire == want


@pytest.mark.parametrize("start", ["int8", "exact"])
def test_policy_noop_push_teaches_nothing(start):
    """A push whose delta is all zero neither votes, nor advances a
    damping streak, nor advances the re-probe clock."""
    p = WirePolicy(damping=2, probe_after=1)
    bad = dict(delta_absmax=1.0, density=0.9, residual_ratio=2.0)
    good = dict(delta_absmax=1.0, density=0.9, residual_ratio=0.0)
    if start == "exact":
        p.observe(**bad)
        p.observe(**bad)
    assert p.wire == start
    flips = p.flips
    for ratio in (2.0, 0.0, None):
        for density in (0.0, 0.9):
            p.observe(delta_absmax=0.0, density=density,
                      residual_ratio=ratio)
    assert p.wire == start and p.flips == flips
    assert p.select(1 << 20, np.float32) == start   # no probe came due
    p.observe(**(good if start == "exact" else bad))
    assert p.wire == start                      # one vote: the streak was 0


def test_policy_pull_select_does_not_spend_the_probe():
    """``probe=False`` reads the choice without consuming a due probe,
    however often it is asked."""
    p = WirePolicy(damping=1, probe_after=2)
    big, f32 = 1 << 20, np.float32
    p.observe(delta_absmax=1.0, density=0.9, residual_ratio=2.0)
    p.observe(delta_absmax=1.0, density=0.9)
    p.observe(delta_absmax=1.0, density=0.9)
    for _ in range(5):
        assert p.select(big, f32, probe=False) == "exact"
    assert p.select(big, f32) == "int8"
    assert p.select(big, f32) == "exact"


def test_auto_wire_end_to_end():
    """wire="auto" picks int8 for a large dense f32 key (wire bytes ~¼ of
    the value) and exact for a sub-threshold key."""
    big = (1 << 20) // 4
    gt, pusher, _ = _setup(big)
    view = pusher.replica("w").buf.view(np.float32)
    view[:] += (_rng(7).normal(size=big) * 0.1).astype(np.float32)
    moved = pusher.push_delta("w", wire="auto")
    assert moved <= 0.30 * big * 4
    tiny = 16
    gt.set("t", np.zeros(tiny, np.float32).tobytes(), host="up")
    lt = LocalTier("h", gt)
    lt.pull("t")
    lt.snapshot_base("t")
    lt.replica("t").buf.view(np.float32)[:] = 3.0
    assert lt.push_delta("t", wire="auto") == tiny * 4     # exact path
    np.testing.assert_array_equal(_global(gt, "t"), 3.0)


def test_policy_backoff_switches_pushes_to_exact():
    """End-to-end adaptivity: deltas so sparse the per-row scales carry no
    information flip the key's policy after `damping` pushes, and auto
    pushes move to the exact wire."""
    n = INT8_WIRE_MIN_BYTES // 4 * 4
    gt, pusher, _ = _setup(n)
    pol = pusher.wire_policy("w")
    view = pusher.replica("w").buf.view(np.float32)
    for _ in range(pol.damping):
        view[0] += 5.0                          # a single spot write
        assert pusher.push_delta("w", wire="auto") <= 0.3 * n * 4
    assert pol.wire == "exact"
    view[:] += 1.0
    assert pusher.push_delta("w", wire="auto") == n * 4    # exact frame now
    np.testing.assert_allclose(_global(gt)[1:], 1.0, atol=1e-4)


# -- pull-direction kernel entry points (ref + interpret parity) ---------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n", [64, 128, 1000])
def test_encode_pull_apply_pull_roundtrip(backend, n):
    rng = _rng(n)
    new = rng.normal(size=n).astype(np.float32)
    base = rng.normal(size=n).astype(np.float32)
    q, s, numel = encode_pull(new, base, backend=backend)
    assert numel == n
    deq = np.asarray(dequantize(q, s, numel))
    bound = np.abs(new - base).max() / 254.0 + 1e-6
    assert np.abs(deq - (new - base)).max() <= bound
    val = rng.normal(size=n).astype(np.float32)
    got = np.asarray(apply_pull(val, q, s, backend=backend))
    np.testing.assert_allclose(got, val + deq, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("wire", ("exact", "int8"))
def test_tier_delta_pull_parity(backend, wire):
    """A warm-replica refresh lands the same value whichever backend runs
    the codec, and the exact wire is bit-exact with the global value."""
    n = INT8_WIRE_MIN_BYTES // 4 * 2
    gt, pusher, puller = _setup(n, seed=13)
    view = pusher.replica("w").buf.view(np.float32)
    view[:] += (_rng(13).normal(size=n) * 0.05).astype(np.float32)
    pusher.push_delta("w", wire="exact", backend=backend)
    moved = puller.pull("w", wire=wire, backend=backend)
    assert moved > 0
    got = puller.replica("w").buf.view(np.float32)
    want = _global(gt)
    if wire == "exact":
        np.testing.assert_array_equal(got, want)
    else:
        assert np.abs(got - want).max() <= 0.05 * 6 / 254.0 + 1e-6


# -- frame plumbing ------------------------------------------------------------


def test_wire_frame_nbytes_and_decode():
    delta = np.arange(8, dtype=np.float32)
    exact = get_codec("exact").encode_delta(delta)
    assert exact.nbytes == 32
    np.testing.assert_array_equal(exact.decode(), delta)
    int8 = get_codec("int8").encode_delta(delta)
    assert int8.nbytes == int8.payload.nbytes + int8.scales.nbytes
    assert int8.numel == 8
    assert np.abs(int8.decode() - delta).max() <= delta.max() / 254.0 + 1e-6


def test_exact_frame_push_matches_legacy_inplace():
    """The exact f32 frame path lands bit-identical results to the old
    in-place add (same math, now recordable/broadcastable)."""
    n = 256
    rng = _rng(17)
    init = rng.normal(size=n).astype(np.float32)
    upd = rng.normal(size=n).astype(np.float32)

    # _setup's puller declares interest, so pusher1 takes the frame path
    gt1, pusher1, _ = _setup(n, init=init.copy())
    pusher1.replica("w").buf.view(np.float32)[:] += upd
    pusher1.push_delta("w", wire="exact")

    gt2 = GlobalTier()
    gt2.set("w", init.tobytes(), host="up")
    lt2 = LocalTier("h", gt2)
    lt2.pull("w")
    lt2.snapshot_base("w")
    local = lt2.replica("w").buf.view(np.float32)
    local[:] += upd
    base = lt2.replica("w").base.view(np.float32)
    gt2.add_inplace("w", local, base, host="h")
    got1, got2 = _global(gt1), _global(gt2)
    np.testing.assert_allclose(got1, init + upd, atol=1e-6)
    np.testing.assert_allclose(got1, got2, atol=1e-6)
