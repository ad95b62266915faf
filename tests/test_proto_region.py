"""The Proto-Faaslet's shared device region (``core/proto.DeviceRegion``).

A snapshot's weights are placed on the device once per snapshot per
process: in ``faaslet`` isolation every restore binds the one region, in
``container`` isolation each container re-runs the init and places its
own.  Pickling carries the numpy leaves and never the device arrays, and
the served tokens are those of the per-call ``jnp.asarray`` copy the region
replaced."""
import pickle
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import DeviceRegion, FaasmRuntime, FunctionDef
from repro.models import ExecConfig, build_model
from repro.telemetry import metrics as tmetrics

N_CALLS = 24


def _tree():
    params = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
              "b": [np.ones(4, np.float32), np.zeros(2, np.int32)]}
    flat, treedef = jax.tree_util.tree_flatten(params)
    return treedef, [np.asarray(x) for x in flat]


def _counts(reg):
    return tuple(reg.get(f"faasm_proto_region_{n}_total").value
                 for n in ("placements", "binds", "placed_bytes"))


def _region_fn(treedef, leaves):
    """A function that binds the init's region and returns the sum of its
    leaves, so every call reads the placed arrays."""
    gate, opened = threading.Barrier(8, timeout=30), threading.Event()

    def init(api):
        return {"params": DeviceRegion(treedef, leaves)}

    def body(api):
        if not opened.is_set():     # the first 8 calls bind all at once
            gate.wait()
            opened.set()
        tree, _ = api.host.user_state(api.faaslet)["params"].bind(
            api.runtime.metrics)
        total = sum(float(jnp.sum(x)) for x in jax.tree_util.tree_leaves(tree))
        api.write_call_output(np.float32(total).tobytes())
        return 0

    return FunctionDef("region", body, init_fn=init)


def _race(n, fn):
    """``fn()`` from ``n`` threads released together, with the switch
    interval shortened; returns their results."""
    gate = threading.Barrier(n, timeout=30)
    got = [None] * n

    def worker(i):
        gate.wait()
        got[i] = fn()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    return got


def _run(isolation):
    treedef, leaves = _tree()
    rt = FaasmRuntime(n_hosts=1, capacity=8, isolation=isolation)
    try:
        rt.upload(_region_fn(treedef, leaves))
        cids = rt.invoke_many("region", [b""] * N_CALLS)
        assert rt.wait_all(cids, timeout=120) == [0] * N_CALLS
        want = sum(float(x.sum()) for x in leaves)
        assert all(np.frombuffer(rt.output(c), np.float32)[0] == want
                   for c in cids)
        return (_counts(rt.metrics), rt.cold_start_stats()["cold_starts"],
                sum(x.nbytes for x in leaves))
    finally:
        rt.shutdown()


def test_faaslets_share_one_placement():
    """8 executors cold-starting together in ``faaslet`` isolation place
    the snapshot's region once; every call binds it."""
    (placements, binds, nbytes), cold, size = _run("faaslet")
    assert cold == 8
    assert (placements, binds, nbytes) == (1, N_CALLS, size)


def test_containers_place_one_region_each():
    """In ``container`` isolation the init runs again per container, so
    each container places its own region once for its life."""
    (placements, binds, nbytes), cold, size = _run("container")
    assert cold == 8
    assert (placements, binds, nbytes) == (cold, N_CALLS, cold * size)


def test_concurrent_binds_place_once():
    """Many threads binding one fresh region at once (switch interval
    shortened) see one placement and the same device arrays."""
    treedef, leaves = _tree()
    region = DeviceRegion(treedef, leaves)
    reg = tmetrics.Registry()
    n = 32
    got = _race(n, lambda: region.bind(reg))
    assert sum(placed for _, placed in got) == 1
    first = jax.tree_util.tree_leaves(got[0][0])
    for tree, _ in got:
        assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(tree),
                                          first))
    assert _counts(reg) == (1, n, region.nbytes)


def test_concurrent_cold_starts_share_one_proto():
    """Restores racing to fetch a function's snapshot all get the same
    Proto-Faaslet, so they bind one template and one device region.  A
    snapshot of 16 MB keeps its decode long enough for the race to show."""
    treedef, leaves = _tree()
    leaves[0] = np.zeros((1 << 22,), np.float32)
    rt = FaasmRuntime(n_hosts=1, capacity=2)
    try:
        rt.upload(_region_fn(treedef, leaves))
        host = next(iter(rt.hosts))
        got = _race(32, lambda: rt.proto_for("region", host=host))
    finally:
        rt.shutdown()
    assert all(p is got[0] for p in got)
    assert len({id(p.user_state_template()["params"]) for p in got}) == 1


def test_snapshot_carries_no_device_arrays():
    """A proto whose region has been placed serializes to the same bytes
    as before the placement, a placed region pickles without its device
    arrays, and the deserialized proto's region holds only numpy leaves and
    places anew on its first bind."""
    treedef, leaves = _tree()
    rt = FaasmRuntime(n_hosts=1, capacity=2)
    try:
        rt.upload(_region_fn(treedef, leaves))
        proto = rt.proto_for("region", host=next(iter(rt.hosts)))
        size, data = proto.size_bytes(), proto.serialize()
        placed_region = proto.user_state_template()["params"]
        tree, placed = placed_region.bind(rt.metrics)
        assert placed
        assert proto.size_bytes() == size
        assert proto.serialize() == data
        assert pickle.loads(pickle.dumps(placed_region))._tree is None
    finally:
        rt.shutdown()
    again = type(proto).deserialize(proto.serialize())
    region = again.user_state_template()["params"]
    assert region._tree is None
    assert all(type(x) is np.ndarray for x in pickle.loads(
        again.user_state)["params"].leaves)
    reg = tmetrics.Registry()
    tree2, placed2 = region.bind(reg)
    assert placed2 and _counts(reg) == (1, 1, region.nbytes)
    for a, b in zip(jax.tree_util.tree_leaves(tree2),
                    jax.tree_util.tree_leaves(tree)):
        assert a is not b
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "deepseek-v2-lite"])
def test_served_tokens_equal_the_per_call_copy(arch):
    """Tokens served through ``make_infer_function`` (shared region, 4
    executors) equal those of the per-call ``jnp.asarray`` tree the region
    replaced, on the same seed."""
    from repro.launch.serve import run_faasm_fanout
    cfg = smoke_config(arch)
    model = build_model(cfg, ExecConfig(backend="xla", loss_chunk=0))
    params = model.init(jax.random.PRNGKey(3))
    r = run_faasm_fanout(model, params, cfg.vocab_size, n_requests=8,
                         prompt_len=16, capacity=4)
    assert r["codes"] == [0] * 8
    flat, treedef = jax.tree_util.tree_flatten(params)
    leaves = [np.asarray(x) for x in flat]
    routed = arch == "deepseek-v2-lite"
    fwd = jax.jit(model.routed_logits if routed else model.logits)
    want = []
    for prompt in r["prompts"]:
        p = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(x) for x in leaves])
        logits = fwd(p, jnp.asarray(prompt[None]))
        if routed:
            logits = logits[0]
        want.append(int(jnp.argmax(logits[0, -1])))
    assert r["tokens"] == want
