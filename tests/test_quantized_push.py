"""Quantised device-side delta push: wire round-trip bounds, error-feedback
convergence, HOGWILD composition, wire-byte accounting (the ≤30%-of-exact
acceptance bound), pad-region no-op, device-replica staleness, fallbacks.

The ``pallas_interpret`` parametrisations are auto-marked slow by conftest;
the xla-backend rows run in the ``scripts/tier1.sh`` fast gate."""
import threading

import numpy as np
import pytest

from repro.kernels.state_push import (apply_delta, dequantize, quantize_delta,
                                      wire_nbytes)
from repro.state.kv import GlobalTier
from repro.state.local import INT8_WIRE_MIN_BYTES, LocalTier
from repro.state.wire import get_codec

BACKENDS = ("xla", "pallas_interpret")


def _rng(seed=0):
    return np.random.default_rng(seed)


# -- wire format round trip ----------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n", [1, 100, 128, 1000])
def test_wire_roundtrip_error_bound(backend, n):
    """Quantise→dequantise error is bounded by half a quantisation step
    (per-row absmax / 127 / 2)."""
    rng = _rng(n)
    local = rng.normal(size=n).astype(np.float32)
    base = rng.normal(size=n).astype(np.float32)
    q, s, numel = quantize_delta(local, base, backend=backend)
    assert numel == n
    deq = np.asarray(dequantize(q, s, numel))
    delta = local - base
    bound = np.abs(delta).max() / 254.0 + 1e-6
    assert np.abs(deq - delta).max() <= bound


@pytest.mark.parametrize("backend", BACKENDS)
def test_pad_region_quantises_to_zero(backend):
    """Non-multiple-of-128 values pad to (rows, 128); the pad must carry
    zero delta so applying a padded push is a no-op beyond ``numel``."""
    n = 130                                   # 2 rows, 126 pad lanes
    rng = _rng(3)
    local = rng.normal(size=n).astype(np.float32)
    base = rng.normal(size=n).astype(np.float32)
    q, s, numel = quantize_delta(local, base, backend=backend)
    assert q.shape == (2, 128) and numel == n
    assert np.all(np.asarray(q).reshape(-1)[n:] == 0)
    # apply through the kernel: the value beyond numel is never touched
    gv = rng.normal(size=n).astype(np.float32)
    out = np.asarray(apply_delta(gv, q, s, backend=backend))
    bound = np.abs(local - base).max() / 254.0 + 1e-5
    assert np.abs(out - (gv + (local - base))).max() <= bound


@pytest.mark.parametrize("backend", BACKENDS)
def test_tier_push_matches_kernel_apply(backend):
    """LocalTier int8 push through GlobalTier.apply_wire lands the same
    value as applying an int8 frame of the delta with the fused kernel."""
    n = INT8_WIRE_MIN_BYTES // 4 * 2
    rng = _rng(7)
    init = rng.normal(size=n).astype(np.float32)
    gt = GlobalTier()
    gt.set("w", init.tobytes(), host="up")
    lt = LocalTier("h0", gt)
    lt.pull("w")
    lt.snapshot_base("w")
    upd = (rng.normal(size=n) * 0.1).astype(np.float32)
    lt.replica("w").buf.view(np.float32)[:] += upd
    lt.push_delta("w", wire="int8", backend=backend)
    got = np.frombuffer(gt.get("w", host="x"), np.float32)
    frame = get_codec("int8").encode_delta((init + upd) - init,
                                           backend=backend)
    want = np.asarray(apply_delta(init, frame.payload, frame.scales,
                                  backend=backend))
    np.testing.assert_allclose(got, want, atol=1e-5)


# -- error feedback ------------------------------------------------------------


def test_error_feedback_residual_bounded_and_converges():
    """≥10 consecutive int8 pushes track the exact path within tolerance and
    the per-replica residual stays bounded (no bias accumulation) — the
    acceptance-criterion property."""
    n = 1 << 18                               # 1 MB of f32
    rng = _rng(11)
    gt = GlobalTier()
    gt.set("w", np.zeros(n, np.float32).tobytes(), host="up")
    lt = LocalTier("h0", gt)
    lt.pull("w")
    lt.snapshot_base("w")
    view = lt.replica("w").buf.view(np.float32)
    expected = np.zeros(n, np.float32)
    scale = 0.01
    resid_caps = []
    for i in range(12):
        u = (rng.normal(size=n) * scale).astype(np.float32)
        view[:] += u
        expected += u
        lt.push_delta("w", wire="int8")
        r = lt.replica("w").residual
        resid_caps.append(float(np.abs(r).max()))
    final = np.frombuffer(gt.get("w", host="x"), np.float32)
    # with error feedback, total error ≤ one half-step of the *last* push,
    # not the sum of 12 half-steps
    one_step = scale * 6 / 254.0              # ~absmax of one N(0,0.01) push
    assert np.abs(final - expected).max() <= one_step * 2
    # residual bounded across all pushes: no growth trend
    assert max(resid_caps) <= one_step * 2
    assert resid_caps[-1] <= 2 * max(resid_caps[:3]) + 1e-6


def test_error_feedback_beats_no_feedback():
    """The same biased update stream quantised N times: with feedback the
    accumulated value stays near exact; zeroing the residual each push
    (no feedback) drifts measurably further."""
    n = 1 << 14
    pushes = 15
    u = np.full(n, 0.003, np.float32)         # constant update: worst case
    u[::7] = 0.1                              # large row absmax -> coarse step

    def run(feedback: bool) -> float:
        gt = GlobalTier()
        gt.set("w", np.zeros(n, np.float32).tobytes(), host="up")
        lt = LocalTier("h0", gt)
        lt.pull("w")
        lt.snapshot_base("w")
        view = lt.replica("w").buf.view(np.float32)
        for _ in range(pushes):
            view[:] += u
            lt.push_delta("w", wire="int8")
            if not feedback:
                lt.replica("w").residual[:] = 0
        final = np.frombuffer(gt.get("w", host="x"), np.float32)
        return float(np.abs(final - u * pushes).max())

    assert run(True) < run(False)


# -- HOGWILD composition -------------------------------------------------------


def test_concurrent_int8_pushes_compose():
    """Concurrent quantised pushes from different hosts accumulate instead
    of overwriting (each under the key's global write lock)."""
    n = INT8_WIRE_MIN_BYTES // 4
    n_hosts = 4
    gt = GlobalTier()
    gt.set("w", np.zeros(n, np.float32).tobytes(), host="up")
    tiers = [LocalTier(f"h{i}", gt) for i in range(n_hosts)]
    per = n // n_hosts
    for i, lt in enumerate(tiers):
        lt.pull("w")
        lt.snapshot_base("w")
        view = lt.replica("w").buf.view(np.float32)
        # ±c patterns quantise exactly (scale = c/127, q = ±127)
        view[i * per:(i + 1) * per] += np.float32(i + 1)
    errs = []

    def push(lt):
        try:
            lt.push_delta("w", wire="int8")
        except Exception as e:                # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=push, args=(lt,)) for lt in tiers]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    final = np.frombuffer(gt.get("w", host="x"), np.float32)
    want = np.zeros(n, np.float32)
    for i in range(n_hosts):
        want[i * per:(i + 1) * per] = i + 1
    np.testing.assert_allclose(final, want, atol=1e-4)


# -- wire-byte accounting (the ≤30% acceptance bound) --------------------------


def test_int8_push_of_4mb_key_moves_under_30_percent():
    """Acceptance criterion: int8 push_delta of a ≥4 MB f32 key moves ≤ 30%
    of the exact-path bytes, with the residual bounded across ≥10 pushes."""
    size = 4 << 20                            # 4 MB
    n = size // 4
    rng = _rng(23)

    def run(wire: str):
        gt = GlobalTier()
        gt.set("w", np.zeros(n, np.float32).tobytes(), host="up")
        lt = LocalTier("h0", gt)
        lt.pull("w")
        lt.snapshot_base("w")
        gt.reset_metrics()
        view = lt.replica("w").buf.view(np.float32)
        resid_caps = []
        for i in range(10):
            view[:] += (rng.normal(size=n) * 0.01).astype(np.float32)
            lt.push_delta("w", wire=wire)
            r = lt.replica("w").residual
            if r is not None:
                resid_caps.append(float(np.abs(r).max()))
        return gt.bytes_pushed["h0"], resid_caps

    exact_bytes, _ = run("exact")
    int8_bytes, resid_caps = run("int8")
    assert exact_bytes == 10 * size           # exact accounts value bytes
    assert int8_bytes <= 0.30 * exact_bytes   # wire accounting: ~26% + scales
    assert len(resid_caps) == 10
    assert max(resid_caps) <= 0.01 * 6 / 254.0 * 2   # bounded, no growth


def test_apply_quantized_accounts_wire_bytes():
    """An int8 frame applied through GlobalTier.apply_wire is accounted at
    its wire bytes, not the value's."""
    n = 1024
    gt = GlobalTier()
    gt.set("w", np.zeros(n, np.float32).tobytes(), host="up")
    gt.reset_metrics()
    delta = np.full(n, 0.5, np.float32)
    frame = get_codec("int8").encode_delta(delta)
    q, s = frame.payload, frame.scales
    moved = gt.apply_wire("w", frame, host="h0")
    wire = wire_nbytes(q, s)
    assert moved == wire == q.nbytes + s.nbytes
    assert gt.bytes_pushed["h0"] == wire      # not the 4 KB of value bytes
    assert gt.total_copied() == wire
    np.testing.assert_allclose(
        np.frombuffer(gt.get("w", host="x"), np.float32), 0.5, atol=0.5 / 127)


# -- fallbacks -----------------------------------------------------------------


def test_sub_threshold_and_non_float_fall_back_exact():
    gt = GlobalTier()
    tiny = np.arange(16, dtype=np.float32)
    gt.set("t", np.zeros(16, np.float32).tobytes(), host="up")
    lt = LocalTier("h0", gt)
    lt.pull("t")
    lt.snapshot_base("t")
    lt.replica("t").buf.view(np.float32)[:] = tiny
    moved = lt.push_delta("t", wire="int8")   # < INT8_WIRE_MIN_BYTES
    assert moved == 64                        # exact in-place path
    np.testing.assert_array_equal(
        np.frombuffer(gt.get("t", host="x"), np.float32), tiny)

    gt.set("i", np.zeros(INT8_WIRE_MIN_BYTES // 8, np.int64).tobytes(),
           host="up")
    lt.pull("i")
    lt.snapshot_base("i")
    lt.replica("i").buf.view(np.int64)[0] = 7
    lt.push_delta("i", dtype=np.int64, wire="int8")   # int dtype: exact
    assert np.frombuffer(gt.get("i", host="x"), np.int64)[0] == 7

    with pytest.raises(ValueError):
        lt.push_delta("t", wire="bogus")


# -- device-resident replica plane ---------------------------------------------


def test_device_replica_sync_and_staleness():
    import jax.numpy as jnp

    n = INT8_WIRE_MIN_BYTES // 4
    gt = GlobalTier()
    gt.set("w", np.arange(n, dtype=np.float32).tobytes(), host="up")
    lt = LocalTier("h0", gt)
    lt.pull("w")
    dv = lt.to_device("w")
    assert np.asarray(dv)[5] == 5.0
    assert not lt.device_stale("w")
    ver = lt.device_replica("w").synced_version
    assert lt.to_device("w") is dv            # synced: no re-upload

    # host write bumps the version -> device copy goes stale
    lt.replica("w").buf.view(np.float32)[0] = 99.0
    lt.mark_dirty("w", 0, 4)
    assert lt.device_stale("w")
    dv2 = lt.to_device("w")
    assert np.asarray(dv2)[0] == 99.0
    assert lt.device_replica("w").synced_version > ver

    # device-side compute, then explicit D2H sync
    lt.update_device("w", dv2 + 1.0)
    assert not lt.device_stale("w")           # device is ahead, not stale
    assert lt.device_replica("w").device_dirty
    moved = lt.from_device("w")
    assert moved == n * 4
    assert lt.replica("w").buf.view(np.float32)[0] == 100.0
    assert not lt.device_replica("w").device_dirty
    assert jnp is not None


def test_device_native_int8_push_skips_host_buffer():
    """A device-resident replica pushes straight from its device arrays: the
    host replica buffer is never consulted (we poison it to prove it)."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt = GlobalTier()
    gt.set("w", np.zeros(n, np.float32).tobytes(), host="up")
    lt = LocalTier("h0", gt)
    lt.pull("w")
    dv = lt.to_device("w", track_delta=True)
    lt.update_device("w", dv + 2.0)           # ±c quantises exactly
    lt.replica("w").buf.view(np.float32)[:] = 1e9   # poison the host copy
    gt.reset_metrics()
    moved = lt.push_delta("w", wire="int8")
    assert moved < n * 4                      # wire bytes, not value bytes
    final = np.frombuffer(gt.get("w", host="x"), np.float32)
    np.testing.assert_allclose(final, 2.0, atol=1e-5)
    # base refreshed on device: an immediate re-push carries ~zero delta
    lt.push_delta("w", wire="int8")
    final = np.frombuffer(gt.get("w", host="x"), np.float32)
    np.testing.assert_allclose(final, 2.0, atol=1e-5)


def test_stale_device_copy_is_not_pushed():
    """Host writes after the device sync invalidate the device arrays: the
    push must fall back to the (authoritative) host buffer."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt = GlobalTier()
    gt.set("w", np.zeros(n, np.float32).tobytes(), host="up")
    lt = LocalTier("h0", gt)
    lt.pull("w")
    lt.snapshot_base("w")
    lt.to_device("w", track_delta=True)
    view = lt.replica("w").buf.view(np.float32)
    view[:] = 3.0
    lt.mark_dirty("w", 0, n * 4)              # device now stale
    lt.push_delta("w", wire="int8")
    final = np.frombuffer(gt.get("w", host="x"), np.float32)
    np.testing.assert_allclose(final, 3.0, atol=1e-4)


def test_device_push_without_track_delta_uses_host_base():
    """Regression: a device copy synced without track_delta must diff
    against the host-side base snapshot, not zeros (zeros re-pushes the
    whole value and doubles the global)."""
    n = INT8_WIRE_MIN_BYTES // 4
    init = np.arange(n, dtype=np.float32)
    gt = GlobalTier()
    gt.set("w", init.tobytes(), host="up")
    lt = LocalTier("h0", gt)
    lt.pull("w")
    lt.snapshot_base("w")
    lt.to_device("w")                         # no track_delta
    lt.push_delta("w", wire="int8")           # no changes since snapshot
    final = np.frombuffer(gt.get("w", host="x"), np.float32)
    np.testing.assert_allclose(final, init, atol=np.abs(init).max() / 200)


def test_from_device_carries_base_no_double_push():
    """Regression: after a device-native push and a D2H sync, a host-path
    push must not re-apply the device-era delta (the device base comes back
    with the value)."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt = GlobalTier()
    gt.set("w", np.zeros(n, np.float32).tobytes(), host="up")
    lt = LocalTier("h0", gt)
    lt.pull("w")
    lt.snapshot_base("w")
    dv = lt.to_device("w", track_delta=True)
    lt.update_device("w", dv + 2.0)
    lt.push_delta("w", wire="int8")           # ships +2 from the device
    lt.from_device("w")                       # host buf = 2.0, base follows
    lt.push_delta("w")                        # exact host push: delta ≈ 0
    final = np.frombuffer(gt.get("w", host="x"), np.float32)
    np.testing.assert_allclose(final, 2.0, atol=1e-4)


def test_track_delta_does_not_drop_pending_device_writes():
    """Regression: to_device(track_delta=True) while device writes are
    pending must not re-arm the base to the unsynced value (that would
    erase the pending delta from every future push)."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt = GlobalTier()
    gt.set("w", np.zeros(n, np.float32).tobytes(), host="up")
    lt = LocalTier("h0", gt)
    lt.pull("w")
    dv = lt.to_device("w", track_delta=True)
    lt.update_device("w", dv + 2.0)               # pending, un-pushed
    again = lt.to_device("w", track_delta=True)   # loop-top re-sync: no-op
    assert np.asarray(again)[0] == 2.0            # device value preserved
    lt.push_delta("w", wire="int8")
    final = np.frombuffer(gt.get("w", host="x"), np.float32)
    np.testing.assert_allclose(final, 2.0, atol=1e-5)   # +2 NOT lost


def test_device_push_then_host_push_no_double_apply():
    """Regression: a device-fresh push whose value mirrors the host buffer
    must refresh the host base too — a later host-path push re-applied the
    same delta otherwise (global read 2.0 where 1.0 is correct)."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt = GlobalTier()
    gt.set("w", np.zeros(n, np.float32).tobytes(), host="up")
    lt = LocalTier("h0", gt)
    lt.pull("w")
    lt.snapshot_base("w")
    lt.replica("w").buf.view(np.float32)[:] = 1.0   # host write
    lt.mark_dirty("w", 0, n * 4)
    lt.to_device("w")                               # sync, no track_delta
    lt.push_delta("w", wire="int8")                 # device branch: pushes +1
    lt.mark_dirty("w", 0, 4)                        # device goes stale
    lt.push_delta("w", wire="int8")                 # host branch: delta ≈ 0
    final = np.frombuffer(gt.get("w", host="x"), np.float32)
    np.testing.assert_allclose(final, 1.0, atol=1e-4)


def test_grown_replica_base_zero_extended():
    """Regression: a base snapshotted before the replica grew is
    zero-extended for the new tail (never pushed => base 0 there), not
    replaced with an all-zeros base (which would re-push the whole value)."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt = GlobalTier()
    gt.set("w", np.full(n, 5.0, np.float32).tobytes(), host="up")
    lt = LocalTier("h0", gt)
    lt.pull("w")
    lt.snapshot_base("w")                           # base = 5.0 * n
    gt.append("w", np.full(n, 3.0, np.float32).tobytes(), host="up")
    lt.replica("w", size=2 * n * 4)                 # buf grows; base is stale
    lt.pull_chunk("w", 0)                           # old chunk present
    r = lt.replica("w")
    r.present_chunks.clear()
    r.full = False
    lt.pull("w")                                    # refresh whole value
    lt.push_delta("w", wire="int8")                 # delta vs old-base: tail!
    final = np.frombuffer(gt.get("w", host="x"), np.float32)
    # head: 5 - 5 = 0 delta; tail: base zero-extended -> pushes +3 once
    np.testing.assert_allclose(final[:n], 5.0, atol=1e-3)
    np.testing.assert_allclose(final[n:], 6.0, atol=1e-3)


def test_host_writes_survive_device_dirty_push():
    """Regression: a device-dirty int8 push must not clear the host dirty
    record — host writes made alongside pending device writes were not in
    the push and must still reach the global tier."""
    n = INT8_WIRE_MIN_BYTES // 4
    gt = GlobalTier()
    gt.set("w", np.zeros(n, np.float32).tobytes(), host="up")
    lt = LocalTier("h0", gt)
    lt.pull("w")
    dv = lt.to_device("w", track_delta=True)
    lt.update_device("w", dv + 2.0)               # pending device write
    lt.replica("w").buf.view(np.float32)[0] = 7.0  # concurrent host write
    lt.mark_dirty("w", 0, 4)
    lt.push_delta("w", wire="int8")               # device branch: ships +2
    # the push covered only the device delta: the host dirty record must
    # survive so those writes can still be pushed (push_dirty carries
    # overwrite semantics, so reconciling the divergence is the caller's
    # from_device + push; the record existing is what makes that possible)
    assert lt.replica("w").dirty_chunks
    final = np.frombuffer(gt.get("w", host="x"), np.float32)
    np.testing.assert_allclose(final, 2.0, atol=1e-5)
