#!/usr/bin/env python3
"""Fail fast when the entry points the tier-1 gate relies on stop resolving
on the installed JAX.  Run from ``scripts/tier1.sh``:

  * ``repro.kernels.common.tpu_compiler_params`` cannot build
    ``pltpu.CompilerParams`` with the kernels' dimension semantics -> exit 1
  * the ``kernels/state_push`` entry points (the wire codec dispatched from
    ``LocalTier.push_delta(wire="int8")``) fail to import or to quantise a
    trivial delta                                        -> exit 1
  * the ``repro.analysis`` entry points (faasmlint rules, sanitizer lock
    factories and hook installation) fail to resolve — a refactor silently
    orphaning the instrumentation                        -> exit 1
  * the ``repro.telemetry`` plane fails to install/uninstall its hooks or
    the disarmed compile-out (zero ring writes) breaks   -> exit 1
  * the ``repro.overload`` control plane (deadlines, retry budgets,
    breakers) fails to resolve, or its disarmed hooks stop compiling out
    to one pointer compare on a policy-less runtime      -> exit 1
  * the host-native wire codec (``state_push.hostcodec``) fails to
    quantise/conserve an int8 delta, or the wire layer stops resolving
    its two codecs (exact, int8)                         -> exit 1

Invoked standalone:  python scripts/check_jax_pin.py
"""
from __future__ import annotations

import os
import sys


def check_analysis_entry_points() -> int:
    """The isolation checker's entry points must resolve and its hooks must
    install/uninstall, so a refactor cannot orphan the instrumentation."""
    try:
        from repro.analysis import holds_stripe              # noqa: F401
        from repro.analysis.lint import RULES, lint_source
        from repro.analysis import sanitizer
        from repro import cancellation, faults
        from repro.state import kv, local, wire

        assert {"stripe-access", "lock-blocking", "wire-construct",
                "tier-copy", "fault-point", "metric-naming",
                "bounded-queue", "suppress-justify"} <= set(RULES), RULES
        # the fault layer must be disarmed at import and resolve its public
        # surface (the chaos gate in tier1.sh depends on it)
        assert faults.active() is None
        assert faults.point("wire-frame-drop") is False
        assert callable(faults.arm) and callable(faults.disarm)
        assert len(faults.FAULT_POINTS) == 11, faults.FAULT_POINTS
        assert {"queue-flood", "subscriber-stall",
                "deadline-clock-skew"} <= set(faults.FAULT_POINTS)
        # a seeded violation must still be caught
        probe = ("from repro.state.wire import WireFrame\n"
                 "f = WireFrame(wire='exact', numel=0, payload=None)\n")
        vs = lint_source(probe, "probe.py")
        assert any(v.rule == "wire-construct" for v in vs), vs
        # the sanitizer must install its hook state into the fabric modules
        st = sanitizer.enable()
        try:
            assert kv._SAN is st and local._SAN is st and wire._SAN is st
            assert cancellation._SAN_GUARD is not None
            assert isinstance(sanitizer.make_mutex("probe"),
                              sanitizer.SanLock)
        finally:
            sanitizer.disable()
        assert kv._SAN is None and cancellation._SAN_GUARD is None
    except Exception as e:
        print(f"check_jax_pin: FAIL — repro.analysis entry points do not "
              f"resolve: {e!r}\n"
              f"  scripts/faasmlint.py and the FAASM_SANITIZE hooks in "
              f"repro/state + repro/cancellation depend on these; fix "
              f"src/repro/analysis/ before trusting the tier-1 gate.")
        return 1
    return check_overload_entry_points()


def check_telemetry_entry_points() -> int:
    """The tracing plane must compile out when disarmed (one pointer
    compare per hook site, zero ring writes) and install/uninstall into
    every instrumented module — the bench_dispatch warm-p99 budget
    depends on the disarmed fast path staying free.  Runs after the
    jax-free wire check: arming imports jax for the profiler mirroring
    and the compile listener."""
    try:
        from repro import faults, telemetry
        from repro.analysis import sanitizer
        from repro.core import runtime
        from repro.launch import serve
        from repro.state import kv, local
        from repro.telemetry import metrics, spans

        # disarmed: every hook slot is None — hook sites cost one compare
        assert not telemetry.enabled()
        for mod in (runtime, kv, local, faults, serve):
            assert mod._TEL is None, mod
        # armed: one Tracer lands in every slot; disarm restores None
        t = telemetry.enable()
        try:
            for mod in (runtime, kv, local, faults, serve):
                assert mod._TEL is t, mod
            assert telemetry.tracer() is t
        finally:
            telemetry.disable()
        for mod in (runtime, kv, local, faults, serve):
            assert mod._TEL is None, mod
        # compile-out: building + exercising a fabric while disarmed must
        # leave a fresh tracer's write counter untouched
        probe = telemetry.spans.Tracer()
        assert probe.writes == 0 and probe.drain() == []
        # the sanitizer installs the drain guard into the spans module
        st = sanitizer.enable()
        try:
            assert spans._SAN_GUARD is not None
        finally:
            sanitizer.disable()
        assert spans._SAN_GUARD is None
        # the registry enforces the naming convention at registration
        try:
            metrics.Registry().counter("not_a_faasm_metric")
        except ValueError:
            pass
        else:
            raise AssertionError("bad metric name accepted")
        assert metrics.valid_name("faasm_tier_net_bytes")
    except Exception as e:
        print(f"check_jax_pin: FAIL — repro.telemetry entry points do not "
              f"resolve: {e!r}\n"
              f"  The span hooks in repro/core + repro/state and the "
              f"metrics registry depend on these; fix src/repro/telemetry/ "
              f"before trusting the tier-1 gate.")
        return 1
    return 0


def check_overload_entry_points() -> int:
    """The overload control plane must resolve its public surface and its
    disarmed hooks must compile out to one pointer compare each — the
    warm-path latency budget assumes a runtime built without an
    OverloadPolicy pays nothing for deadlines/shedding/breakers."""
    try:
        from repro import overload
        from repro.core.runtime import BatchTimeout, Call  # noqa: F401

        # return codes are part of the wire contract (serve.py re-exports
        # SHED_RC; scatter_gather keys retry decisions off DEADLINE_RC)
        assert overload.SHED_RC == -2 and overload.DEADLINE_RC == -3
        # deadline algebra: absolute expiry, positive-budget guard
        dl = overload.Deadline.after(60.0)
        assert not dl.expired() and 0.0 < dl.remaining() <= 60.0
        try:
            overload.Deadline.after(0.0)
        except ValueError:
            pass
        else:
            raise AssertionError("zero deadline budget accepted")
        # retry budget: token bucket spends whole tokens, refills by ratio
        rb = overload.RetryBudget(ratio=0.5, burst=2.0, initial=1.0)
        assert rb.try_spend() and not rb.try_spend()
        rb.on_success()
        assert 0.0 < rb.fill_ratio() <= 1.0
        # circuit breaker: failures trip it, allow() then refuses placement
        br = overload.CircuitBreaker(window=4, failure_ratio=0.5,
                                     min_volume=2, reset_timeout_s=60.0)
        assert br.allow() and br.state == br.CLOSED
        br.record(False)
        br.record(False)
        assert br.state == br.OPEN and not br.allow()
        # bounded primitives: queues refuse growth past their depth
        assert overload.bounded_queue(4).maxsize == 4
        cq = overload.CoalescingQueue(depth=2)
        assert cq.depth == 2
        # disarmed compile-out: a policy-less runtime leaves every overload
        # hook slot None and every fresh Call without a deadline, so the
        # hot-path checks are single pointer compares
        assert Call.__dataclass_fields__["deadline"].default is None
        from repro.core.runtime import FaasmRuntime
        rt = FaasmRuntime(n_hosts=1)
        try:
            assert rt.overload is None
            assert rt._retry_budget is None and rt._breakers is None
        finally:
            rt.shutdown()
        import inspect
        sig = inspect.signature(overload.OverloadPolicy)
        assert "max_queue_depth" in sig.parameters
    except Exception as e:
        print(f"check_jax_pin: FAIL — repro.overload entry points do not "
              f"resolve: {e!r}\n"
              f"  The admission/deadline/breaker hooks in repro/core/runtime "
              f"and the serve.py --max-queue-depth/--default-deadline-ms "
              f"flags depend on these; fix src/repro/overload.py before "
              f"trusting the tier-1 gate.")
        return 1
    return check_wire_entry_points()


def check_wire_entry_points() -> int:
    """The host-native wire codec must resolve *without* importing jax —
    ``LocalTier.push_delta`` takes the hostcodec fast path on every
    host-resident push, so a drift here is a data-plane outage, not a
    kernel nicety.  Runs before the jax probes on purpose: importing
    ``state_push.hostcodec`` must not pull in the device runtime."""
    try:
        import numpy as np
        from repro.kernels.state_push import QMAX, hostcodec
        assert "jax" not in sys.modules, \
            "hostcodec import pulled in jax — host fast path is no longer " \
            "dispatch-free"

        # fused int8 quantise: roundtrip + exact residual conservation
        rng = np.random.default_rng(7)
        eff = rng.standard_normal(130).astype(np.float32)
        base = rng.standard_normal(130).astype(np.float32)
        delta = eff - base
        q, s, n, resid = hostcodec.encode_quant(eff, base)
        assert n == 130 and q.shape == (2, 128) and s.shape == (2, 1)
        assert q.dtype == np.int8 and np.abs(q).max() <= QMAX
        deq = hostcodec.decode_rows(q, s, n)
        assert np.allclose(deq + resid, delta, atol=1e-6)

        # wire layer: exactly the two wires, each resolving its codec, and
        # the policy selects one of them for an f32 value
        from repro.state import wire
        assert wire.WIRES == ("exact", "int8"), wire.WIRES
        for w in wire.WIRES:
            assert wire.get_codec(w).name == w
        w0 = wire.WirePolicy().select(1 << 20, np.float32)
        assert w0 in wire.WIRES, w0
    except Exception as e:
        print(f"check_jax_pin: FAIL — wire codec entry points do not "
              f"resolve: {e!r}\n"
              f"  LocalTier.push_delta's host fast path and WirePolicy "
              f"depend on these; fix "
              f"src/repro/kernels/state_push/hostcodec.py and "
              f"src/repro/state/wire.py before trusting the tier-1 gate.")
        return 1
    return check_telemetry_entry_points()


def main() -> int:
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
    rc = check_analysis_entry_points()
    if rc:
        return rc

    import jax

    try:
        from repro.kernels.common import tpu_compiler_params
        params = tpu_compiler_params(("parallel", "arbitrary"))
        assert params.dimension_semantics == ("parallel", "arbitrary"), params
    except Exception as e:
        print(f"check_jax_pin: FAIL — tpu_compiler_params cannot build "
              f"pltpu.CompilerParams under jax {jax.__version__}: {e!r}\n"
              f"  Every Pallas kernel passes its grid semantics through it; "
              f"fix src/repro/kernels/common.py.")
        return 1

    # the quantised wire codec is dispatched from the state tier on every
    # int8 push_delta, delta pull and peer broadcast: make a JAX drift there
    # loud, not a slow failure at transfer time.  Runs after the
    # compiler-params probe above so a pallas API change hits its targeted
    # diagnostic first, not this generic one.
    try:
        from repro.kernels.state_push import (apply_pull, dequantize,
                                              encode_pull, quantize_delta)
        from repro.kernels.state_push.kernel import (       # noqa: F401
            apply_delta_pallas, quantize_delta_pallas)
        import numpy as np
        q, s, n = quantize_delta(np.ones(4, np.float32),
                                 np.zeros(4, np.float32), backend="xla")
        deq = np.asarray(dequantize(q, s, n))
        assert n == 4 and abs(float(deq[0]) - 1.0) < 1e-2, (n, deq)
        # pull/broadcast direction: encode a catch-up delta and apply it to
        # a replica value (GlobalTier.pull_wire / LocalTier broadcast apply)
        q, s, n = encode_pull(np.full(4, 2.0, np.float32),
                              np.zeros(4, np.float32), backend="xla")
        got = np.asarray(apply_pull(np.ones(4, np.float32), q, s,
                                    backend="xla"))
        assert abs(float(got[0]) - 3.0) < 1e-2, got
    except Exception as e:
        print(f"check_jax_pin: FAIL — state_push kernel entry points do not "
              f"resolve under jax {jax.__version__}: {e!r}\n"
              f"  The wire fabric (LocalTier.push_delta/pull(wire='int8'), "
              f"GlobalTier.pull_wire, peer broadcast) dispatches these; fix "
              f"src/repro/kernels/state_push/ before trusting the tier.")
        return 1

    print(f"check_jax_pin: OK — jax {jax.__version__}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
