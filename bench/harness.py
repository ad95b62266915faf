"""One measured run of a cell: set-up, the window, the readings, and the
check of what the timed path produced.

The system under test is Faaslet inference served through the runtime's
normal path: ``FaasmRuntime`` with the configuration's deployment, the
``infer`` function of ``repro.launch.serve.make_infer_function`` with the
shared ``serve/stats`` vector in the global tier, and calls submitted with
``invoke``/``invoke_many``.  Nothing of the program is changed; the harness
drives it from the outside.  ``bench/run.py`` is the entry point and looks
for the chip; tests drive :func:`run_cell` directly.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import queue
import shutil
import sys
import threading
import time
from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np

from bench import correct, manifest, trace_reduce, traffic, weights
from repro.configs import get_config
from repro.core import FaasmRuntime
from repro.core.runtime import BatchTimeout
from repro.launch import serve
from repro.models import ExecConfig, build_model
from repro.state.ddo import VectorAsync
from repro.telemetry import spans as tspans

STATS_KEY = "serve/stats"
DRAIN_S = 60.0                  # how long past the close a call may settle
TRACE_DIR = manifest.ROOT / ".bench_runs" / "trace"
TRACE_S = 8.0                   # the profiler records this much of the window
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class _Compiles:
    """Counts JAX backend compiles (persistent-cache loads included)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


_COMPILES: Optional[_Compiles] = None


def compiles() -> _Compiles:
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = _Compiles()
        jax.monitoring.register_event_duration_secs_listener(_COMPILES)
    return _COMPILES


@dataclass
class Record:
    """One call: its prompt and what the runtime stamped."""
    cid: int
    prompt: np.ndarray
    submit: float = 0.0
    start: float = 0.0
    end: float = 0.0
    rc: Optional[int] = None        # None: never settled
    token: Optional[int] = None

    @property
    def length(self) -> int:
        return len(self.prompt)


@dataclass
class Run:
    """What metric readers read."""
    cell: dict
    config: dict
    traffic: dict
    t0: float
    t1: float
    calls: list                     # every call the window's loop made
    setup_s: float
    memory_peak_bytes: Optional[int]
    peaks: Optional[dict]
    spans: Optional[dict] = None    # call id -> [Span], traced runs only
    trace: Optional[dict] = None    # trace_reduce.reduce(...), traced runs

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def load_config(bench: dict, name: str) -> dict:
    entry = manifest.config(bench, name)
    return json.loads((manifest.ROOT / entry["file"]).read_text())


def registry_config(cfg: dict):
    """The program's registry entry, after checking that every published
    size in the configuration file is the one it runs."""
    reg = get_config(cfg["registry"])
    ref = correct.reference(cfg)
    for key, attr in ref.REGISTRY_KEYS.items():
        want, got = cfg[key], getattr(reg, attr)
        if (isinstance(want, float) and abs(want - got) > 1e-9 * abs(want)) \
                or (not isinstance(want, float) and want != got):
            raise ValueError(f"{cfg['registry']}: {key}={want} in the "
                             f"configuration file, {attr}={got} in the program")
    return reg


def reader(name: str):
    path = manifest.BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- driving the window --------------------------------------------------------

def _closed_loop(rt, prompts, clients: int, t1: float) -> list:
    """``clients`` calls in flight until ``t1``; each settled call is
    replaced at once, the replacements of one wake-up in one
    ``invoke_many``."""
    done: queue.SimpleQueue = queue.SimpleQueue()
    recs = []

    def submit(n: int) -> None:
        ps = prompts.take(n)
        ids = rt.invoke_many("infer", [p.tobytes() for p in ps])
        for cid, p in zip(ids, ps):
            recs.append(Record(cid, p))
            rt.call(cid).add_done_callback(lambda c: done.put(c.id))

    submit(clients)
    while True:
        left = t1 - time.perf_counter()
        if left <= 0:
            break
        try:
            done.get(timeout=left)
        except queue.Empty:
            break
        n = 1
        while True:
            try:
                done.get_nowait()
                n += 1
            except queue.Empty:
                break
        if time.perf_counter() < t1:
            submit(n)
    return recs


def _settle(rt, recs: list) -> None:
    """Wait for every call, up to :data:`DRAIN_S` past the close, then copy
    the runtime's stamps into the records."""
    try:
        rt.wait_all([r.cid for r in recs], timeout=DRAIN_S)
    except BatchTimeout:
        pass
    for r in recs:
        c = rt.call(r.cid)
        if not c.event.is_set():
            continue
        r.submit, r.start, r.end = c.t_submit, c.t_start, c.t_end
        r.rc = c.return_code
        if r.rc == 0:
            r.token = int(np.frombuffer(c.output, np.int32)[0])


def _set_cache(on: bool) -> None:
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()


def _start_trace() -> int:
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    TRACE_DIR.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    mark = time.perf_counter_ns()
    with jax.profiler.TraceAnnotation(trace_reduce.MARK):
        pass
    return mark


# -- the run -------------------------------------------------------------------

def run_cell(bench: dict, cell: dict, seed: int, seconds: float, trace: bool,
             *, t_start: float, peaks: Optional[dict] = None,
             model_cfg=None, control: bool = False) -> dict:
    """Set up, measure ``seconds``, read the metrics and check the answers.

    ``model_cfg`` replaces the registry configuration (the tests' small
    sizes).  ``control`` puts the float8 reference in the program's place
    for ``token_gap``: the sampled calls are judged by the tokens that
    reference puts first, so ``correct`` must come out false; the
    program's own gap on the same sample is kept under ``_readings``
    (calibration only, never in a benchmark run)."""
    cfg = load_config(bench, cell["config"])
    spec = traffic.load(cell["traffic"])
    model_cfg = model_cfg or registry_config(cfg)
    dep = cfg["deployment"]
    vocab = model_cfg.vocab_size
    lengths = traffic.lengths(spec)
    dev = jax.devices()[0]
    comp_setup = compiles().count, compiles().seconds
    phases = {}

    def phase(name: str, t: float) -> float:
        now = time.perf_counter()
        phases[name] = now - t
        return now

    # set-up: weights from the seed, the runtime, the function, warm-up
    t = time.perf_counter()
    phases["start"] = t - t_start
    model = build_model(model_cfg, ExecConfig(backend="auto", loss_chunk=0))
    flat, treedef = jax.tree_util.tree_flatten(weights.make(model.init, seed))
    host_leaves = [np.asarray(x) for x in flat]
    del flat
    t = phase("weights", t)
    rt = FaasmRuntime(n_hosts=dep["hosts"], capacity=dep["capacity"],
                      isolation=dep["isolation"])
    all_recs: list = []
    try:
        VectorAsync.create(rt.global_tier, STATS_KEY,
                           np.zeros(vocab, np.float32))
        rt.upload(serve.make_infer_function(
            model, treedef, host_leaves, prompt_len=lengths[0],
            state_wire=dep["state_wire"]))
        t = phase("upload", t)
        warm_rng = traffic.seed_seq(seed, 1)
        n_warm = max(dep["capacity"], len(lengths))
        for _ in range(2):          # every length, every executor, twice
            ps = [warm_rng.integers(0, vocab, lengths[i % len(lengths)],
                                    dtype=np.int32) for i in range(n_warm)]
            recs = [Record(cid, p) for cid, p in zip(
                rt.invoke_many("infer", [p.tobytes() for p in ps]), ps)]
            _settle(rt, recs)
            all_recs += recs
            bad = [r.rc for r in recs if r.rc != 0]
            if bad:
                raise RuntimeError(f"warm-up calls failed: {bad} "
                                   f"{[rt.call(r.cid).error for r in recs]}")
        t = phase("warm_up", t)

        prompts = traffic.Prompts(spec, vocab, traffic.seed_seq(seed, 0))
        # set-up's objects leave the collector's view for the window, so a
        # collection inside it walks only what the window allocates
        gc.collect()
        gc.freeze()
        # the window runs with the persistent cache off: the program as
        # deployed caches only compiles of a second or more, so anything it
        # compiles per call must compile in full here too
        cache_was = jax.config.jax_enable_compilation_cache
        _set_cache(False)
        comp0, comp_s0 = compiles().count, compiles().seconds
        cold0 = rt.cold_start_stats()["cold_starts"]
        mark = _start_trace() if trace else None
        if trace:
            tspans.enable()

        t0 = time.perf_counter()
        setup_s = t0 - t_start
        phase("to_window", t)
        log(f"setup: {setup_s!r} s; " + ", ".join(
            f"{k} {v!r} s" for k, v in phases.items())
            + f"; compiles {comp0 - comp_setup[0]} "
            f"({comp_s0 - comp_setup[1]!r} s)")
        t1 = t0 + seconds
        t_trace = t0 + min(TRACE_S, seconds)
        if trace:
            stopper = threading.Timer(t_trace - time.perf_counter(),
                                      jax.profiler.stop_trace)
            stopper.start()
        recs = _closed_loop(rt, prompts, spec["clients"], t1)
        if trace:
            stopper.join()
        _settle(rt, recs)
        spans = None
        if trace:
            spans = {}
            for s in tspans.tracer().take():
                spans.setdefault(s.call, []).append(s)
            tspans.disable()
        all_recs += recs
        gc.unfreeze()
        _set_cache(cache_was)
        n_comp = compiles().count - comp0
        comp_s = compiles().seconds - comp_s0
        n_cold = rt.cold_start_stats()["cold_starts"] - cold0
        log(f"window: {seconds} s, {len(recs)} calls, compiles inside "
            f"{n_comp} ({comp_s!r} s), cold starts inside {n_cold}")
        mem = dev.memory_stats() or {}
        stats_vec = np.frombuffer(rt.global_tier.get(STATS_KEY, host="cache"),
                                  np.float32).copy()
    finally:
        rt.shutdown()
    del host_leaves, rt
    gc.collect()

    run = Run(cell=cell, config=cfg, traffic=spec, t0=t0, t1=t1,
              calls=recs, setup_s=setup_s,
              memory_peak_bytes=mem.get("peak_bytes_in_use"), peaks=peaks,
              spans=spans)
    if trace:
        run.trace = trace_reduce.reduce(TRACE_DIR, mark, t0, t_trace, run)
    wanted = (manifest.per_layer_for if trace
              else manifest.end_to_end_for)(bench, cell["name"])
    metrics = {}
    for m in wanted:
        v = setup_s if m["name"] == "setup_s" else reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the check: reference after the program's state is freed
    t_check = time.perf_counter()
    ref = correct.reference(cfg)
    ref_cfg = dict(cfg, **{k: getattr(model_cfg, a)
                           for k, a in ref.REGISTRY_KEYS.items()})
    picked = correct.sample(recs, traffic.seed_seq(seed, 3))
    prompts = [r.prompt for r in picked]
    params = weights.make(model.init, seed)
    ref_logits = correct.last_logits(ref, params, ref_cfg, prompts)
    tokens = [r.token for r in picked]
    readings = {"sample": len(picked)}
    if control:
        readings["program_token_gap"] = correct.widest_gap(ref_logits, tokens)
        tokens = correct.last_logits(ref, params, ref_cfg, prompts,
                                     mode="fp8").argmax(-1)
    del params
    limits = cfg["limits"]
    served = [r.token for r in all_recs if r.rc == 0]
    checks = {
        "token_gap": {"value": correct.widest_gap(ref_logits, tokens),
                      "limit": limits["token_gap"]},
        "stats_miscount": {"value": correct.stats_miscount(
            stats_vec, served, vocab), "limit": limits["stats_miscount"]},
    }
    failed = sum(r.rc != 0 for r in recs)
    ok = failed == 0 and bool(picked) and all(
        c["value"] <= c["limit"] for c in checks.values())
    result = {
        "correct": ok,
        "attempted": len(recs),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": mem.get("peak_bytes_in_use")},
    }
    if trace:
        result["device"]["busy_s"] = run.trace["busy_s"]
        result["device"]["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = checks
    result["_calls"] = recs
    result["_readings"] = dict(readings, compiles_in_window=n_comp,
                               compile_s_in_window=comp_s,
                               cold_starts_in_window=n_cold,
                               check_s=time.perf_counter() - t_check)
    return result
