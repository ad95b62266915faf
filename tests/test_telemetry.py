"""Telemetry plane: compile-out, span trees, metrics, Perfetto export.

Covers the observability contract in ``docs/observability.md``:
  * compile-out — disarmed hook sites leave zero ring-buffer writes
  * span-tree correctness — a speculation twin and a retry-after-crash
    appear as sibling spans of one logical call (same fence, distinct
    epochs), with fault-point hits as instant spans
  * histogram percentile accuracy against numpy on the log-bucketed bins
  * Chrome/Perfetto trace_event schema of the exporter
  * the traced chaos smoke (``-k smoke`` in scripts/tier1.sh): seed-0
    storm with tracing armed under the sanitizer exports a non-empty,
    well-formed trace
  * sanitizer integration — collector drain under a stripe/key lock is
    reported, ring writes under the same lock are not
  * the served ``infer`` body — ``serve.weights``/``serve.forward``/
    ``wire.encode`` nest under their call, a compile inside a call is a
    ``jax.compile`` span, and the spans mirrored into a profiler session
    land on the harness's clock join within 1 ms
"""
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro import faults, telemetry
from repro.core import FaasmRuntime, FunctionDef
from repro.state.ddo import VectorAsync
from repro.state.kv import GlobalTier
from repro.state.local import LocalTier
from repro.telemetry import clock, metrics, spans, trace

KEY = "w"


def _global(gt, key=KEY):
    return np.frombuffer(gt.get(key, host="check"), np.float32)


def _fabric(n_floats=256):
    gt = GlobalTier()
    gt.set(KEY, np.zeros(n_floats, np.float32).tobytes(), host="seed")
    t = LocalTier("push0", gt)
    t.pull(KEY)
    t.snapshot_base(KEY)
    return gt, t


def _spans_named(span_list, name):
    return [s for s in span_list if s.name == name]


def _compile_listeners():
    """Tracers' compile listeners in JAX's (private) listener registry."""
    from jax._src import monitoring
    return [cb for cb in monitoring._event_duration_secs_listeners
            if getattr(cb, "__func__", None) is spans.Tracer.compiled]


@pytest.fixture(scope="module")
def tiny_lm():
    """A small qwen-shaped model whose ``serve/stats`` vector is large
    enough to take the int8 wire."""
    import jax
    from repro.configs import smoke_config
    from repro.models import ExecConfig, build_model
    cfg = smoke_config("qwen1.5-0.5b").with_overrides(vocab_size=2048)
    model = build_model(cfg, ExecConfig(backend="xla", loss_chunk=0))
    flat, treedef = jax.tree_util.tree_flatten(
        model.init(jax.random.PRNGKey(0)))
    return model, treedef, [np.asarray(x) for x in flat], cfg.vocab_size


def _serve(lm, n=2, prompt_len=8):
    """``n`` served ``infer`` calls (int8 ``serve/stats`` push) on a fresh
    runtime; returns their call ids and the runtime's metrics scraped
    before shutdown."""
    from repro.launch.serve import make_infer_function
    model, treedef, leaves, vocab = lm
    rt = FaasmRuntime(n_hosts=1, capacity=2)
    try:
        VectorAsync.create(rt.global_tier, "serve/stats",
                           np.zeros(vocab, np.float32))
        rt.upload(make_infer_function(model, treedef, leaves,
                                      prompt_len=prompt_len,
                                      state_wire="int8"))
        prompt = np.arange(prompt_len, dtype=np.int32).tobytes()
        cids = rt.invoke_many("infer", [prompt] * n)
        assert rt.wait_all(cids, timeout=120) == [0] * n
        return cids, rt.metrics.snapshot()
    finally:
        rt.shutdown()


# -- compile-out --------------------------------------------------------------

def test_disarmed_hooks_compile_out(tiny_lm):
    """Disarmed, every hook slot is None, no compile listener is
    registered, and a full runtime + fabric workload — a served ``infer``
    call with its int8 push among it — performs zero ring-buffer writes."""
    from repro.core import runtime as runtime_mod
    from repro.launch import serve as serve_mod
    from repro.state import kv as kv_mod
    from repro.state import local as local_mod

    assert not telemetry.enabled()
    for mod in (runtime_mod, kv_mod, local_mod, faults, serve_mod):
        assert mod._TEL is None
    assert _compile_listeners() == []
    _serve(tiny_lm, n=1)

    gt, t = _fabric()
    t.replica(KEY).buf.view(np.float32)[0] += 1.0
    t.push_delta(KEY, wire="exact")
    gt.pull_wire(KEY, 0, host="other")

    rt = FaasmRuntime(n_hosts=1)
    try:
        rt.upload(FunctionDef("echo", lambda api: 0))
        assert rt.wait(rt.invoke("echo"), timeout=10) == 0
    finally:
        rt.shutdown()

    # arming *after* the workload finds a tracer that never saw a write
    tr = telemetry.enable()
    assert tr.writes == 0
    assert tr.spans() == []
    telemetry.disable()


def test_enable_disable_installs_hooks():
    from repro.core import runtime as runtime_mod
    from repro.launch import serve as serve_mod
    from repro.state import kv as kv_mod
    from repro.state import local as local_mod

    t = telemetry.enable()
    assert telemetry.enable() is t               # idempotent
    for mod in (runtime_mod, kv_mod, local_mod, faults, serve_mod):
        assert mod._TEL is t
    assert len(_compile_listeners()) == 1
    telemetry.disable()
    for mod in (runtime_mod, kv_mod, local_mod, faults, serve_mod):
        assert mod._TEL is None
    assert _compile_listeners() == []


def test_ring_drop_oldest():
    tr = spans.Tracer()
    for i in range(spans._RING_CAPACITY + 100):
        tr.record("x", "call", float(i), float(i) + 0.5, idx=i)
    got = tr.take()
    assert tr.dropped == 100
    assert len(got) == spans._RING_CAPACITY
    # oldest 100 were dropped; survivors come back in t0 order
    assert got[0].tags["idx"] == 100
    assert [s.t0 for s in got] == sorted(s.t0 for s in got)


# -- the single clock ---------------------------------------------------------

def test_call_timing_single_clock():
    """Call.t_* all come from telemetry.clock; queue_wait/exec_wall are
    derived and sum to the settled latency."""
    rt = FaasmRuntime(n_hosts=1)
    try:
        rt.upload(FunctionDef("nap", lambda api: time.sleep(0.02) or 0))
        cid = rt.invoke("nap")
        assert rt.wait(cid, timeout=10) == 0
        c = rt.call(cid)
        assert c.queue_wait >= 0.0
        assert c.exec_wall >= 0.02
        assert abs(c.latency - (c.queue_wait + c.exec_wall)) < 1e-9
    finally:
        rt.shutdown()


# -- span trees ---------------------------------------------------------------

def test_call_lifecycle_spans():
    t = telemetry.enable()
    rt = FaasmRuntime(n_hosts=1)
    try:
        rt.upload(FunctionDef("echo", lambda api: 0))
        cid = rt.invoke("echo")
        assert rt.wait(cid, timeout=10) == 0
        rt.wait_all([rt.invoke("echo")], timeout=10)
        got = t.spans()
        for name in ("call.queue", "call.restore", "call.exec",
                     "call.reset", "call.settle"):
            assert _spans_named(got, name), name
        ex = _spans_named(got, "call.exec")
        assert any(s.call == cid for s in ex)
        s = next(s for s in ex if s.call == cid)
        assert s.fence == rt.call(cid).fence_id
        assert s.host is not None and s.t1 >= s.t0
        assert s.tags["status"] == "done" and s.tags["rc"] == 0
        settle = next(x for x in _spans_named(got, "call.settle")
                      if x.call == cid)
        assert settle.tags["queue_wait"] >= 0.0
        assert settle.tags["exec_wall"] > 0.0
    finally:
        rt.shutdown()
        telemetry.disable()


def test_speculation_twin_sibling_spans():
    """A straggler's speculative twin shares the primary's fence with a
    distinct epoch and call id — sibling spans of one logical call."""
    t = telemetry.enable()
    rt = FaasmRuntime(n_hosts=2, straggler_timeout=0.3)
    try:
        state = {"n": 0}

        def sometimes_slow(api):
            state["n"] += 1
            if state["n"] == 1:
                time.sleep(2.5)
            return 0

        rt.upload(FunctionDef("s", sometimes_slow))
        cid = rt.invoke("s")
        assert rt.wait(cid, timeout=30) == 0
        fence = rt.call(cid).fence_id
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            fam = [s for s in t.spans()
                   if s.fence == fence and s.name == "call.exec"]
            if len({s.epoch for s in fam}) >= 2:
                break
            time.sleep(0.1)
        assert len({s.epoch for s in fam}) >= 2, fam      # twin + primary
        assert len({s.call for s in fam}) >= 2, fam       # distinct attempts
    finally:
        rt.shutdown()
        telemetry.disable()


def test_retry_after_crash_sibling_spans():
    """A call requeued past a dead host re-runs under the same fence with
    a bumped epoch; both attempts' spans are visible."""
    t = telemetry.enable()
    rt = FaasmRuntime(n_hosts=2, capacity=1, backoff=0.001)
    try:
        release = threading.Event()

        def gated(api):
            release.wait(10.0)
            return 0

        rt.upload(FunctionDef("gated", gated))
        cid = rt.invoke("gated")
        deadline = time.monotonic() + 5.0
        victim = None
        while victim is None and time.monotonic() < deadline:
            victim = next((h for h in rt.alive_hosts()
                           if h._inflight > 0), None)
        assert victim is not None
        rt.fail_host(victim.id)
        release.set()
        assert rt.wait(cid, timeout=30) == 0
        got = t.spans()
        fence = rt.call(cid).fence_id
        fam = [s for s in got if s.fence == fence
               and s.name in ("call.queue", "call.exec")]
        assert len({s.epoch for s in fam}) >= 2, fam
        hosts = {s.host for s in fam if s.name == "call.exec"}
        assert victim.id in {s.host for s in fam} or len(hosts) >= 1
    finally:
        rt.shutdown()
        telemetry.disable()


def test_fault_hits_become_instant_spans():
    t = telemetry.enable()
    gt, tier = _fabric()
    sub = LocalTier("sub", gt)
    sub.pull(KEY)
    sub.subscribe(KEY)
    plan = faults.FaultPlan(0).add("wire-frame-drop", nth=1, times=1)
    with faults.armed(plan):
        tier.replica(KEY).buf.view(np.float32)[0] += 1.0
        tier.push_delta(KEY, wire="exact")
        gt.flush_broadcasts()            # the drop fires on the pump thread
    assert plan.fired("wire-frame-drop") == 1
    hits = _spans_named(t.spans(), "fault.wire-frame-drop")
    assert hits and hits[0].tags["action"] == "drop"
    assert hits[0].t0 == hits[0].t1                       # instant
    telemetry.disable()


# -- wire spans ---------------------------------------------------------------

@pytest.mark.parametrize("replica,backend,path", [
    ("host", "xla", "host"), ("host", "pallas_interpret", "host"),
    ("device", "pallas_interpret", "device")])
def test_int8_encode_path_tag_and_counter(replica, backend, path):
    """An int8 push of a host replica encodes on the host whatever the
    backend; a fresh f32 device replica's encodes on the device.  The
    ``wire.encode`` span's ``path`` tag and the tier's counters say
    which, and the push lands either way."""
    t = telemetry.enable()
    try:
        gt, tier = _fabric(64 * 1024)
        if replica == "device":
            dv = tier.to_device(KEY, track_delta=True)
            tier.update_device(KEY, dv + 1.0)
        else:
            tier.replica(KEY).buf.view(np.float32)[:] += 1.0
        tier.push_delta(KEY, wire="int8", backend=backend)
        enc = _spans_named(t.spans(), "wire.encode")
    finally:
        telemetry.disable()
    assert [s.tags["path"] for s in enc] == [path]
    assert (tier.host_encodes, tier.device_encodes) == (
        (1, 0) if path == "host" else (0, 1))
    np.testing.assert_allclose(_global(gt), 1.0, atol=1e-5)


def test_encode_counters_lose_no_update_across_keys():
    """Pushes of different keys encode concurrently, each under its own
    replica lock, and the tier's encode counter loses none of them."""
    n_keys, n_pushes = 16, 20
    gt = GlobalTier()
    tier = LocalTier("push0", gt)
    keys = [f"k{i}" for i in range(n_keys)]
    for k in keys:
        gt.set(k, np.zeros(1024, np.float32).tobytes(), host="seed")
        tier.pull(k)
        tier.snapshot_base(k)

    def pusher(k):
        for _ in range(n_pushes):
            tier.replica(k).buf.view(np.float32)[:] += 1.0
            tier.push_delta(k, wire="int8")

    threads = [threading.Thread(target=pusher, args=(k,)) for k in keys]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    assert (tier.host_encodes, tier.device_encodes) == (n_keys * n_pushes, 0)
    for k in keys:
        np.testing.assert_allclose(_global(gt, k), n_pushes, atol=1e-3)


def test_wire_span_tags():
    t = telemetry.enable()
    n = 64 * 1024                     # big enough for the int8 wire
    gt, tier = _fabric(n)
    sub = LocalTier("sub", gt)
    sub.pull(KEY)
    sub.subscribe(KEY)
    tier.replica(KEY).buf.view(np.float32)[:] += 1.0
    tier.push_delta(KEY, wire="int8")
    gt.flush_broadcasts()                # bcast spans record on the pump
    puller = LocalTier("puller", gt)
    puller.pull(KEY)
    got = t.spans()

    push = _spans_named(got, "wire.push")
    assert push, got
    p = push[-1]
    assert p.tags["key"] == KEY and p.tags["wire"] == "int8"
    assert p.tags["nbytes"] > 0 and p.tags["encode_ns"] > 0
    assert p.tags["version"] == p.tags["prev_version"] + 1

    bcast = _spans_named(got, "wire.bcast")
    assert bcast and bcast[-1].tags["applied"] is True
    assert bcast[-1].tags["subscriber"] == "sub"

    # the cold pull moved the full value
    full = _spans_named(got, "wire.full_pull")
    assert full and full[-1].tags["puller"] == "puller"
    assert full[-1].tags["nbytes"] > 0
    telemetry.disable()


def _within(inner, outer):
    return outer.t0 <= inner.t0 <= inner.t1 <= outer.t1


def test_infer_body_spans_nest_under_their_call(tiny_lm):
    """An armed ``infer`` call records the weights' bind, the forward,
    the int8 encode and the replica-lock waits, each on the call's thread
    with its id, inside ``call.exec`` (the encode inside ``wire.push``)."""
    t = telemetry.enable()
    try:
        cids, _ = _serve(tiny_lm, n=2)
        got = t.spans()
    finally:
        telemetry.disable()
    _, _, leaves, _ = tiny_lm
    placed = []
    for cid in cids:
        mine = [s for s in got if s.call == cid]
        one = {name: _spans_named(mine, name) for name in (
            "call.exec", "serve.weights", "serve.forward", "wire.push",
            "wire.encode")}
        assert all(len(v) == 1 for v in one.values()), one
        one = {name: v[0] for name, v in one.items()}
        ex = one["call.exec"]
        for name in ("serve.weights", "serve.forward", "wire.push"):
            assert _within(one[name], ex), name
            assert one[name].thread == ex.thread
        assert _within(one["wire.encode"], one["wire.push"])
        assert one["serve.weights"].t1 <= one["serve.forward"].t0
        assert one["serve.forward"].t1 <= one["wire.push"].t0
        tags = dict(one["serve.weights"].tags)
        placed.append(tags.pop("placed"))
        assert tags == {
            "nbytes": sum(x.nbytes for x in leaves), "leaves": len(leaves)}
        assert one["wire.encode"].tags == {"key": "serve/stats",
                                           "wire": "int8", "path": "host"}
        # the waits for the replica lock in the stats pull and base snapshot
        locks = _spans_named(mine, "state.lock")
        assert {s.tags["site"] for s in locks} == {"pull", "base"}
        assert all(_within(s, ex) for s in locks)
    # one snapshot, one placement: the first bind places, the other binds
    assert sorted(placed) == [False, True]


def test_served_pushes_count_as_host_encodes(tiny_lm):
    """Every served call's int8 ``serve/stats`` push encodes the host
    replica on the host: the runtime publishes one host encode per call
    and no device encode."""
    _, got = _serve(tiny_lm, n=3)
    assert got["faasm_wire_host_encodes_total"] == 3
    assert got["faasm_wire_device_encodes_total"] == 0
    assert got["faasm_wire_codec_fallbacks_total"] == 0


def test_compile_inside_a_call_is_a_jax_compile_span():
    """A backend compile inside a call becomes a ``jax.compile`` span with
    the compiled function's name and the call's id; after ``disable()``
    the listener is gone and a compile records nothing."""
    import jax
    import jax.numpy as jnp

    def recompile(api):
        def fresh_fn(x):                 # a new function: a fresh compile
            return x * 3.0 + 1.0
        jax.jit(fresh_fn)(jnp.ones(5)).block_until_ready()
        return 0

    t = telemetry.enable()
    rt = FaasmRuntime(n_hosts=1)
    try:
        rt.upload(FunctionDef("recompile", recompile))
        cid = rt.invoke("recompile")
        assert rt.wait(cid, timeout=60) == 0
        comp = _spans_named(t.spans(), "jax.compile")
    finally:
        rt.shutdown()
        telemetry.disable()
    mine = [s for s in comp if s.call == cid]
    assert any(s.tags["fun_name"] == "jit(fresh_fn)" for s in mine), comp
    assert all(s.t1 > s.t0 for s in mine)
    assert _compile_listeners() == []
    writes = t.writes
    jax.jit(lambda x: x - 2.0)(jnp.ones(6)).block_until_ready()
    assert t.writes == writes


# the interval spans a profiler session sees, each with its call's id
MIRRORED = ("call.exec", "serve.weights", "serve.forward", "wire.push",
            "wire.encode")


def test_mirrored_spans_share_the_profiler_clock(tiny_lm, tmp_path):
    """Under a CPU profiler session the armed tracer's interval spans
    appear as host events of the same name carrying ``call=<id>``, and
    the harness's one-marker join (``bench.window_mark``) puts both ends
    of each within 1 ms of its ring span."""
    import jax
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import trace_reduce

    t = telemetry.enable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        mark = clock.now_ns()
        with jax.profiler.TraceAnnotation(trace_reduce.MARK):
            pass
        cids, _ = _serve(tiny_lm, n=2)
    finally:
        jax.profiler.stop_trace()
        ring = {(s.name, s.call): s for s in t.spans() if s.name in MIRRORED}
        telemetry.disable()
    pd = trace_reduce.load(tmp_path)
    off = trace_reduce.mark_ns(pd) - mark
    seen = set()
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name not in MIRRORED:
                    continue
                assert plane.name.startswith("/host:"), plane.name
                call = dict(e.stats)["call"]
                s = ring[(e.name, int(call))]
                assert abs(e.start_ns - (s.t0 * 1e9 + off)) < 1e6
                assert abs(e.start_ns + e.duration_ns
                           - (s.t1 * 1e9 + off)) < 1e6
                seen.add((e.name, int(call)))
    assert seen == {(name, cid) for name in MIRRORED for cid in cids}


def test_fence_reject_instant():
    t = telemetry.enable()
    gt = GlobalTier()
    assert gt.fence_admit(KEY, ("c1", 1, 1)) is True
    gt.fence_supersede("c1", 2)
    assert gt.fence_admit(KEY, ("c1", 2, 2)) is False     # dead epoch
    assert gt.fence_rejections == 1
    rej = _spans_named(t.spans(), "fence.reject")
    assert rej and rej[0].fence == "c1" and rej[0].epoch == 2
    assert rej[0].tags["key"] == KEY and rej[0].tags["seq"] == 2
    telemetry.disable()


# -- metrics registry ---------------------------------------------------------

def test_metric_name_validation():
    reg = metrics.Registry()
    with pytest.raises(ValueError):
        reg.counter("bad_name")
    with pytest.raises(ValueError):
        reg.gauge("faasm_thing")                          # no unit suffix
    with pytest.raises(ValueError):
        reg.histogram("faasm_Upper_case_ms")
    c = reg.counter("faasm_test_things_total")
    assert reg.counter("faasm_test_things_total") is c    # get-or-create
    with pytest.raises(TypeError):
        reg.gauge("faasm_test_things_total")              # kind mismatch
    with pytest.raises(ValueError):
        c.inc(-1)


def test_histogram_percentiles_vs_numpy(rng):
    sample = rng.lognormal(mean=1.0, sigma=1.2, size=20_000)
    h = metrics.Histogram("faasm_test_lat_ms")
    for v in sample:
        h.observe(v)
    assert h.count == sample.size
    assert abs(h.sum - float(sample.sum())) < 1e-6 * sample.size
    for p in (0.50, 0.90, 0.99, 0.999):
        want = float(np.percentile(sample, 100 * p))
        got = h.percentile(p)
        # half-bucket geometric error is ~2.2%; allow headroom for the
        # rank-interpolation difference on the tail
        assert abs(got - want) / want < 0.06, (p, got, want)
    assert h.min == pytest.approx(float(sample.min()))
    assert h.max == pytest.approx(float(sample.max()))


def test_histogram_zero_bucket():
    h = metrics.Histogram("faasm_test_zero_ms")
    for v in (0.0, -1.0, 0.0, 5.0):
        h.observe(v)
    assert h.percentile(0.5) == 0.0
    assert h.percentile(0.999) <= 5.0


def test_registry_render_text_and_collector():
    reg = metrics.Registry()
    reg.counter("faasm_test_events_total", "things that happened").inc(3)
    reg.histogram("faasm_test_lat_ms").observe(2.0)
    pulls = {"n": 0}
    reg.register_collector(
        lambda r: r.gauge("faasm_test_live_count").set(
            pulls.__setitem__("n", pulls["n"] + 1) or pulls["n"]))
    text = reg.render_text()
    assert pulls["n"] == 1                                 # collector ran
    assert "# TYPE faasm_test_events_total counter" in text
    assert "faasm_test_events_total 3" in text
    assert 'faasm_test_lat_ms{quantile="0.99"}' in text
    assert "faasm_test_live_count 1" in text
    snap = reg.snapshot()
    assert snap["faasm_test_events_total"] == 3.0
    assert snap["faasm_test_lat_ms_count"] == 1.0


def test_runtime_metrics_single_source_of_truth():
    rt = FaasmRuntime(n_hosts=1)
    try:
        rt.upload(FunctionDef("echo", lambda api: 0))
        for _ in range(3):
            assert rt.wait(rt.invoke("echo"), timeout=10) == 0
        stats = rt.cold_start_stats()
        snap = rt.metrics.snapshot()
        assert snap["faasm_host_warm_hits_total"] == stats["warm_hits"]
        assert snap["faasm_host_resets_total"] == stats["resets"] >= 3
        assert snap["faasm_runtime_calls_done_total"] >= 3
        text = rt.metrics_text()
        assert "faasm_tier_net_bytes" in text
        assert "faasm_host_init_ms" in text
    finally:
        rt.shutdown()


def test_metrics_http_endpoint():
    import urllib.request
    reg = metrics.Registry()
    reg.counter("faasm_test_hits_total").inc()
    srv = metrics.serve_http(reg, 0)                      # ephemeral port
    try:
        port = srv.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "faasm_test_hits_total 1" in body
    finally:
        srv.shutdown()


# -- Chrome/Perfetto export ---------------------------------------------------

def test_chrome_export_schema(tmp_path):
    t = telemetry.enable()
    n = 64 * 1024
    gt, tier = _fabric(n)
    sub = LocalTier("sub", gt)
    sub.pull(KEY)
    sub.subscribe(KEY)
    tier.replica(KEY).buf.view(np.float32)[:] += 1.0
    tier.push_delta(KEY, wire="int8")
    gt.flush_broadcasts()                # bcast flow-finish records on the pump

    path = tmp_path / "trace.json"
    n_events = trace.export_chrome(str(path))
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert len(events) == n_events > 0
    assert doc["displayTimeUnit"] == "ms"
    phases = {e["ph"] for e in events}
    assert "X" in phases and "M" in phases
    for e in events:
        assert e["pid"] == 1 and "tid" in e
        if e["ph"] == "M":
            assert e["name"] == "thread_name" and e["args"]["name"]
            continue
        assert isinstance(e["ts"], float)
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
        if e["ph"] == "i":
            assert e["s"] == "t"
    # wire flow: every finish has a matching start with the same id
    starts = {e["id"] for e in events if e["ph"] == "s"}
    finishes = [e for e in events if e["ph"] == "f"]
    assert starts                                          # push emitted one
    for f in finishes:
        assert f["id"] in starts and f["bp"] == "e"
    telemetry.disable()


# -- sanitizer integration ----------------------------------------------------

@pytest.mark.sanitize
def test_drain_under_key_lock_reported():
    """Ring writes under a fabric lock are fine; a collector drain there
    is a telemetry-under-lock report."""
    from repro.analysis import sanitizer

    t = telemetry.enable()
    gt = GlobalTier()                    # built with sanitizer armed
    gt.set(KEY, np.zeros(8, np.float32).tobytes(), host="seed")
    lock = gt.lock(KEY)
    lock.acquire_write()
    try:
        t.instant("probe.write", "wire", key=KEY)          # allowed
        t.drain()                                          # not allowed
    finally:
        lock.release_write()
    reports = sanitizer.take_reports()
    assert [r.check for r in reports] == ["telemetry-under-lock"], reports
    # outside the lock the same drain is clean
    t.drain()
    assert sanitizer.take_reports() == []
    telemetry.disable()


# -- traced chaos smoke (runs in scripts/tier1.sh via -k smoke) ---------------

@pytest.mark.sanitize
def test_traced_chaos_smoke(tmp_path):
    """Seed-0 runtime chaos with tracing armed under the sanitizer: the
    run converges exactly-once AND exports a non-empty, well-formed
    Perfetto trace with restore/exec/wire spans."""
    t = telemetry.enable()
    rt = FaasmRuntime(n_hosts=2, capacity=2, backoff=0.001)
    try:
        VectorAsync.create(rt.global_tier, KEY, np.zeros(8, np.float32))

        def inc(api):
            v = VectorAsync(api, KEY)
            v.pull(track_delta=True)
            v.add(0, 1.0)
            v.push_delta(wire="exact")
            return 0

        rt.upload(FunctionDef("inc", inc))
        with faults.armed(faults.FaultPlan.random(0)):
            cids = rt.invoke_many("inc", [b""] * 8, state_hint=[KEY])
            assert rt.wait_all(cids, timeout=60) == [0] * 8
        assert _global(rt.global_tier)[0] == 8.0          # exactly once

        names = {s.name for s in t.spans()}
        assert {"call.restore", "call.exec", "wire.push"} <= names, names
        path = tmp_path / "chaos_trace.json"
        n_events = trace.export_chrome(str(path))
        doc = json.loads(path.read_text())
        assert n_events > 0 and len(doc["traceEvents"]) == n_events
        assert all("ph" in e and "pid" in e for e in doc["traceEvents"])
    finally:
        rt.shutdown()
        telemetry.disable()
