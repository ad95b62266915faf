"""Serving launcher: ``python -m repro.launch.serve --arch <id> [--smoke]``.

Builds prefill + serve steps for the selected architecture and runs a batched
request loop (greedy decode) — the per-request orchestration that the FAASM
runtime drives in `examples/inference_serving.py`.

``--faasm-requests N`` additionally pushes an N-request wave through the FAASM
runtime's batch invocation path (``invoke_many`` + ``wait_all`` on a shared
completion latch) and reports p50/p99 dispatch latency and batch throughput.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import ExecConfig, build_model
from repro.telemetry import clock as tclock
from repro.telemetry import metrics as tmetrics
from repro.telemetry import spans as tspans

# telemetry hook slot, installed by repro.telemetry.spans._install: while
# disarmed the ``infer`` body's spans cost a pointer compare per boundary
_TEL = None


def make_infer_function(model, treedef, host_leaves, prompt_len: int = 16,
                        cache_key=("serve", "fwd"), state_wire: str = None):
    """Build the FAASM ``infer`` FunctionDef for a single-shot forward pass.

    The jitted executable lands in the runtime's ExecutableCache under
    ``cache_key``.  The weights are the init's
    :class:`~repro.core.proto.DeviceRegion`: its numpy leaves travel in the
    Proto-Faaslet snapshot, and they are placed on the device once per
    snapshot per process, on the first call's bind.  Every call restored
    from that snapshot binds the same read-only device arrays (a container
    re-runs the init, so it places its own).  The jitted forward donates
    nothing: donating the shared arrays would invalidate them for every
    other call.  Shared by :func:`run_faasm_fanout` and
    ``examples/inference_serving.py``.

    With ``state_wire`` set, each request additionally accumulates the
    predicted token into the shared ``serve/stats`` histogram and pushes the
    delta with that wire format (``"int8"`` = the quantised
    ``kernels/state_push`` path; ``"auto"`` = the per-key adaptive
    ``WirePolicy``) — the stateful-serving traffic the wire choice is
    about.  The warm-replica refresh before each push rides the wire fabric
    too: only the retained delta is pulled.

    A model whose parameters hold a router (``w_router``) is served through
    ``model.routed_logits``: the token slots routed to each held expert in
    each MoE layer come to the host with the token, ride on the call's
    ``serve.forward`` span (``moe_rows``, ``moe_rows_max``) and add to the
    runtime's ``faasm_serve_moe_routed_rows_total`` and
    ``faasm_serve_moe_busiest_rows_total`` counters."""
    from repro.core import DeviceRegion, FunctionDef

    routed = any(getattr(path[-1], "key", None) == "w_router"
                 for path, _ in jax.tree_util.tree_flatten_with_path(
                     jax.tree_util.tree_unflatten(treedef, host_leaves))[0])
    forward = model.routed_logits if routed else model.logits

    def _build_fwd():
        fwd = jax.jit(lambda p, t: forward(p, t))
        p = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(x) for x in host_leaves])
        jax.block_until_ready(fwd(p, jnp.zeros((1, prompt_len), jnp.int32)))
        return fwd

    def init(api):
        api.runtime.exec_cache.get_or_build(cache_key, _build_fwd)
        return {"params": DeviceRegion(treedef, host_leaves)}

    def infer(api):
        tel = _TEL
        region = api.host.user_state(api.faaslet)["params"]
        fwd, _, _ = api.runtime.exec_cache.get_or_build(cache_key, _build_fwd)
        tokens = np.frombuffer(api.read_call_input(),
                               np.int32).reshape(1, -1)
        # serve.weights: the bind of the snapshot's device region; the
        # first bind in the process places the leaves (placed=True)
        span = (tel.begin("serve.weights", "serve", nbytes=region.nbytes,
                          leaves=len(region.leaves))
                if tel is not None else None)
        p, placed = region.bind(api.runtime.metrics)
        # serve.forward: dispatch until the token is on the host, so it
        # holds the forward and any wait for a placement still in flight
        if span is not None:
            tel.end(span, placed=placed)
            span = tel.begin("serve.forward", "serve")
        if routed:
            logits, rows = fwd(p, jnp.asarray(tokens))
            tok, rows = jax.device_get((jnp.argmax(logits[0, -1]), rows))
            tok = int(tok)
            busiest = int(rows.max())
            if span is not None:
                tel.end(span, moe_rows=rows.tolist(), moe_rows_max=busiest)
            reg = api.runtime.metrics
            reg.counter("faasm_serve_moe_routed_rows_total",
                        "token slots routed to held experts").inc(
                            int(rows.sum()))
            reg.counter("faasm_serve_moe_busiest_rows_total",
                        "per call, the slots of its busiest held expert in "
                        "any MoE layer").inc(busiest)
        else:
            logits = fwd(p, jnp.asarray(tokens))
            tok = int(np.asarray(jnp.argmax(logits[0, -1])))
            if span is not None:
                tel.end(span)
        if state_wire is not None:
            from repro.state.ddo import VectorAsync
            stats = VectorAsync(api, "serve/stats")
            stats.pull(track_delta=True)
            stats.add([tok], 1.0)
            stats.push_delta(wire=state_wire)
        api.write_call_output(np.int32(tok).tobytes())
        return 0

    return FunctionDef("infer", infer, init_fn=init)


# canonical overload return codes live with the overload control plane;
# re-exported here for back-compat (this module defined SHED_RC first)
from repro.overload import SHED_RC  # noqa: E402

_SHED_CHUNK = 32      # degradation re-check granularity within one wave


def submit_degradable(rt, fn: str, payloads, *, min_alive_hosts: int = 1,
                      state_hint=None, timeout: float = 600.0) -> dict:
    """Submit a request wave with fail-fast shedding (graceful degradation).

    A healthy cluster takes the whole wave through the batched
    ``invoke_many`` path.  Once the alive-host count drops below
    ``min_alive_hosts`` the cluster is **degraded**: requests from that
    point on are shed immediately (code :data:`SHED_RC`, never queued)
    instead of piling onto the survivors — a bounded brown-out in place of
    a collapse.  The wave is submitted in :data:`_SHED_CHUNK`-sized slices
    so a host dying mid-wave starts shedding within one slice, not after
    the whole wave queued.

    Returns ``{"codes": [...], "call_ids": [...], "shed": n,
    "degraded": bool}`` — ``call_ids[i]`` is ``None`` for shed requests.
    Shed requests are the caller's to retry (e.g.
    ``repro.core.chain.scatter_gather``) once capacity returns.
    """
    n = len(payloads)
    codes: list = [SHED_RC] * n
    call_ids: list = [None] * n
    degraded = False
    submitted: list = []                 # (index, call_id)
    for lo in range(0, n, _SHED_CHUNK):
        chunk = payloads[lo:lo + _SHED_CHUNK]
        if len(rt.alive_hosts()) < min_alive_hosts:
            degraded = True              # fail fast: shed the rest of the slice
            continue
        cids = rt.invoke_many(fn, chunk, state_hint=state_hint)
        submitted.extend(zip(range(lo, lo + len(chunk)), cids))
    if submitted:
        rcs = rt.wait_all([c for _, c in submitted], timeout=timeout)
        for (i, cid), rc in zip(submitted, rcs):
            codes[i], call_ids[i] = rc, cid
    shed = sum(1 for c in call_ids if c is None)
    return {"codes": codes, "call_ids": call_ids, "shed": shed,
            "degraded": degraded or shed > 0}


def run_faasm_fanout(model, params, vocab_size: int, n_requests: int,
                     prompt_len: int = 16, n_hosts: int = 1,
                     capacity: int = 8, state_wire: str = None,
                     min_alive_hosts: int = 1,
                     max_queue_depth: int = None,
                     default_deadline_ms: float = None) -> dict:
    """Serve ``n_requests`` single-shot requests through the FAASM runtime.

    Each request is one Faaslet call running the jitted forward pass; the
    whole wave is submitted with ``invoke_many`` and awaited on one shared
    latch (``wait_all``), the thousand-call fan-out path.  ``state_wire``
    turns on the shared serving-stats state (see
    :func:`make_infer_function`) and picks its push wire format; the batch
    then also carries a ``state_hint`` so placement prefers hosts already
    holding the stats replica.

    ``max_queue_depth`` / ``default_deadline_ms`` arm the overload control
    plane (``repro.overload``): bounded per-host admission queues with
    spill-to-peer, and an end-to-end deadline stamped on every request.
    Requests refused everywhere settle with ``SHED_RC``; requests whose
    deadline expires settle with ``overload.DEADLINE_RC``.  Both are
    reported in the returned dict instead of inflating the latency tail.

    Per request ``i`` the dict also carries ``codes[i]``, ``prompts[i]``
    (int32 token ids) and ``tokens[i]``, the greedy next token the call
    returned (``None`` when it was not served)."""
    from repro import overload as oload
    from repro.core import FaasmRuntime
    from repro.state.ddo import VectorAsync

    flat, treedef = jax.tree_util.tree_flatten(params)
    host_leaves = [np.asarray(x) for x in flat]
    policy = None
    if max_queue_depth is not None or default_deadline_ms is not None:
        policy = oload.OverloadPolicy(
            max_queue_depth=max_queue_depth,
            default_deadline_s=(default_deadline_ms / 1e3
                                if default_deadline_ms else None))
    rt = FaasmRuntime(n_hosts=n_hosts, capacity=capacity, overload=policy)
    hint = ["serve/stats"] if state_wire is not None else None
    try:
        if state_wire is not None:
            VectorAsync.create(rt.global_tier, "serve/stats",
                               np.zeros(vocab_size, np.float32))
        rt.upload(make_infer_function(model, treedef, host_leaves,
                                      prompt_len=prompt_len,
                                      state_wire=state_wire))
        rng = np.random.default_rng(0)
        payloads = [rng.integers(0, vocab_size, prompt_len,
                                 dtype=np.int32).tobytes()
                    for _ in range(n_requests)]
        # warm every executor before timing the wave
        rt.wait_all(rt.invoke_many("infer", payloads[:capacity],
                                   state_hint=hint), timeout=300)
        rt.global_tier.reset_metrics()
        t0 = tclock.now()
        wave = submit_degradable(rt, "infer", payloads,
                                 min_alive_hosts=min_alive_hosts,
                                 state_hint=hint, timeout=600)
        wall = tclock.now() - t0
        from repro.overload import DEADLINE_RC
        ok_codes = (0, SHED_RC, DEADLINE_RC)
        assert all(r in ok_codes for r in wave["codes"]), wave["codes"]
        served = [c for c, r in zip(wave["call_ids"], wave["codes"])
                  if c is not None and r == 0]
        n_deadline = sum(1 for r in wave["codes"] if r == DEADLINE_RC)
        n_shed = (wave["shed"]
                  + sum(1 for r in wave["codes"] if r == SHED_RC))
        # one source of truth: per-request latency lands in the runtime's
        # registry (mirrored to the process registry for --metrics-port)
        hist = rt.metrics.histogram("faasm_serve_request_ms",
                                    "end-to-end request latency")
        mirror = tmetrics.registry().histogram("faasm_serve_request_ms",
                                               "end-to-end request latency")
        for c in served:
            ms = rt.call(c).latency * 1e3
            hist.observe(ms)
            mirror.observe(ms)
        out = {"requests": n_requests, "wall_s": wall,
               "throughput_rps": len(served) / wall,
               "p50_ms": hist.percentile(0.50) if served else 0.0,
               "p99_ms": hist.percentile(0.99) if served else 0.0,
               "degraded": wave["degraded"], "shed": n_shed,
               "deadline_expired": n_deadline,
               "codes": wave["codes"],
               "prompts": [np.frombuffer(p, np.int32) for p in payloads],
               "tokens": [int(np.frombuffer(rt.output(c), np.int32)[0])
                          if c is not None and r == 0 else None
                          for c, r in zip(wave["call_ids"], wave["codes"])]}
        if state_wire is not None:
            out["state_wire"] = state_wire
            out["state_push_mb"] = sum(
                rt.global_tier.bytes_pushed.values()) / 1e6
        return out
    finally:
        rt.shutdown()


def generate(model, params, tokens, new_tokens: int, extra=None):
    """Greedy serving outside the runtime: one prefill over ``tokens``
    (B, S) and ``new_tokens - 1`` decode steps through the KV cache.

    Returns the generated ids (B, new_tokens) as numpy and the logits
    (B, vocab) that chose the last of them.  Prefill and decode wall times
    land in the ``faasm_serve_prefill_ms`` / ``faasm_serve_decode_ms``
    histograms (and spans, when tracing is on)."""
    cfg = model.cfg
    B, S = tokens.shape
    n_prefix = cfg.n_image_tokens if cfg.family == "vlm" else 0
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)

    reg = tmetrics.registry()
    cache = model.init_cache(B, S + new_tokens + n_prefix)
    tel = tspans.tracer()
    t0 = tclock.now()
    logits, cache, n = prefill(params, tokens, cache, extra)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    t1 = tclock.now()
    reg.histogram("faasm_serve_prefill_ms").observe((t1 - t0) * 1e3)
    if tel is not None:
        tel.record("serve.prefill", "serve", t0, t1, arch=cfg.name, tokens=S)
    n_total = int(n) if not hasattr(n, "shape") else S + n_prefix

    out = [tok]
    t0 = tclock.now()
    for i in range(new_tokens - 1):
        idx = jnp.full((B,), n_total + i, jnp.int32)
        logits, cache = decode(params, tok, cache, idx)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t1 = tclock.now()
    reg.histogram("faasm_serve_decode_ms").observe((t1 - t0) * 1e3)
    if tel is not None:
        tel.record("serve.decode", "serve", t0, t1, arch=cfg.name,
                   steps=new_tokens - 1)
    return np.stack([np.asarray(t) for t in out], axis=1), logits


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--faasm-requests", type=int, default=0,
                    help="also fan out N requests through the FAASM runtime "
                         "(invoke_many/wait_all batch path)")
    ap.add_argument("--faasm-hosts", type=int, default=1)
    ap.add_argument("--min-alive-hosts", type=int, default=1,
                    help="graceful-degradation floor: shed requests (fail "
                         "fast) once fewer hosts than this are alive")
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="bound each host's admission queue at this many "
                         "calls beyond its executor capacity; overflow "
                         "spills to a peer with room or is shed (SHED_RC)")
    ap.add_argument("--default-deadline-ms", type=float, default=None,
                    help="stamp this end-to-end deadline (ms) on every "
                         "request; expired work settles with DEADLINE_RC "
                         "at admission, dequeue, or the next checkpoint")
    ap.add_argument("--state-wire", choices=("auto", "exact", "int8"),
                    default=None,
                    help="track shared serving stats through the state tier "
                         "and move deltas with this wire format (auto = "
                         "per-key adaptive WirePolicy)")
    ap.add_argument("--metrics-port", type=int, default=0,
                    help="expose the telemetry registry as Prometheus text "
                         "on this port (0 = off)")
    args = ap.parse_args()
    enable_compile_cache()

    reg = tmetrics.registry()
    if args.metrics_port:
        tmetrics.serve_http(reg, args.metrics_port)
        print(f"metrics: http://127.0.0.1:{args.metrics_port}/metrics")

    if args.smoke:
        cfg = smoke_config(args.arch)
        ec = ExecConfig(backend="xla", loss_chunk=0)
    else:
        cfg = get_config(args.arch)
        ec = ExecConfig(backend="auto", loss_chunk=0)
    model = build_model(cfg, ec)
    params = model.init(jax.random.PRNGKey(0))

    B, S = args.batch, args.prompt_len
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    extra = None
    if cfg.family == "vlm":
        extra = jnp.asarray(rng.normal(size=(B, cfg.n_image_tokens,
                                             cfg.d_model)), jnp.bfloat16)
    if cfg.family == "encdec":
        extra = jnp.asarray(rng.normal(size=(B, cfg.n_frames, cfg.d_model)),
                            jnp.bfloat16)

    gen, _ = generate(model, params, tokens, args.new_tokens, extra)
    # the printed line reads the registry — generate()'s timers are its only
    # writers, so the log and /metrics can never disagree
    snap = reg.snapshot()
    prefill_s = snap["faasm_serve_prefill_ms_sum"] / 1e3
    decode_s = snap["faasm_serve_decode_ms_sum"] / 1e3
    print(f"{cfg.name}: prefill {S} toks in {prefill_s * 1e3:.1f}ms; "
          f"{args.new_tokens - 1} decode steps in {decode_s * 1e3:.1f}ms "
          f"({(args.new_tokens - 1) * B / max(decode_s, 1e-9):.1f} tok/s)")
    print("generated ids[0]:", gen[0][:12], "...")

    if args.faasm_requests > 0:
        r = run_faasm_fanout(model, params, cfg.vocab_size,
                             args.faasm_requests, prompt_len=S,
                             n_hosts=args.faasm_hosts,
                             state_wire=args.state_wire,
                             min_alive_hosts=args.min_alive_hosts,
                             max_queue_depth=args.max_queue_depth,
                             default_deadline_ms=args.default_deadline_ms)
        print(f"faasm fan-out: {r['requests']} reqs in {r['wall_s']:.2f}s "
              f"({r['throughput_rps']:.1f} req/s) "
              f"p50={r['p50_ms']:.1f}ms p99={r['p99_ms']:.1f}ms")
        if r.get("degraded"):
            print(f"  DEGRADED: {r['shed']} requests shed (alive hosts "
                  f"below --min-alive-hosts={args.min_alive_hosts})")
        if r.get("deadline_expired"):
            print(f"  {r['deadline_expired']} requests expired their "
                  f"--default-deadline-ms={args.default_deadline_ms} budget")
        if "state_push_mb" in r:
            print(f"  serve/stats pushes ({r['state_wire']} wire): "
                  f"{r['state_push_mb']:.2f}MB to the global tier")


if __name__ == "__main__":
    main()
