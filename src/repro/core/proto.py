"""Proto-Faaslets: ahead-of-time snapshots restored in ~µs (Faasm §5.2).

Two cold-start costs exist on a TPU serving/training host, both attacked here:

  1. **Execution state** — the function's initialised linear memory plus any
     host objects its init code built (e.g. weights already laid out).  A
     ``ProtoFaaslet`` captures these once; ``restore()`` stamps out a fresh
     Faaslet from the snapshot.  Snapshots are plain bytes: OS-independent and
     restorable on any host in the cluster (cross-host restore).
  2. **XLA compilation** — seconds-to-minutes per (function, arch, shape,
     mesh).  The ``ExecutableCache`` is the Proto-Faaslet of the compiled
     artifact: the first lowering pays the compile; every Faaslet spawned
     afterwards binds the cached executable.

After every call the runtime *resets* the Faaslet from its Proto-Faaslet
(§5.2 multi-tenant reset): no information from the previous call survives in
private memory.

Restore cost is O(1), not O(arena): the snapshot is decoded once per process
into a shared read-only :class:`~repro.core.faaslet.ArenaBase` that every
restore maps copy-on-write (``Faaslet.bind_base``), and the pickled
init-code products are decoded once into a cached template instead of paying
``pickle.loads`` per restore.  The template is shared read-only across all
restores on the process — the same discipline as the shared state tier
(§3.3); functions must not mutate it.  The pre-CoW full-copy path survives
as :meth:`ProtoFaaslet.restore_copy` (the benchmark baseline).

Init products that a function reads on the accelerator (a model's weights)
travel as a :class:`DeviceRegion`: the snapshot carries their numpy leaves,
and they are placed on the device once per snapshot per process, on the
first bind.  Every Faaslet restored from the snapshot binds the same
read-only device arrays instead of copying the leaves over the host-device
link on each call; they must never be donated to a jitted function.
"""
from __future__ import annotations

import pickle
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from repro.core.faaslet import ArenaBase, Faaslet
from repro.telemetry import clock as tclock

_cache_lock = threading.Lock()
_PICKLE_FIELDS = ("func_name", "arena", "brk", "memory_limit", "user_state")


class DeviceRegion:
    """A shared, read-only device region for a snapshot's arrays.

    Holds a treedef and the numpy leaves; :meth:`bind` returns the tree of
    device arrays.  The first bind places every leaf in one
    ``jax.device_put`` (double-checked under a lock, so Faaslets that
    cold-start together place once); later binds get the same arrays back.
    The arrays are shared by every call that binds them: read-only, and
    never donated to a jitted function, which would invalidate them for
    every other holder.  Pickling drops the device arrays, so snapshot bytes
    stay portable and a region restored elsewhere places its own copy on its
    first bind."""

    def __init__(self, treedef, leaves):
        self.treedef = treedef
        self.leaves = list(leaves)
        self.nbytes = sum(x.nbytes for x in self.leaves)
        self._tree = None
        self._lock = threading.Lock()

    def bind(self, metrics) -> Tuple[Any, bool]:
        """The device tree, and whether this bind placed it.  Counts the
        bind (``faasm_proto_region_binds_total``) and a placement with its
        bytes (``faasm_proto_region_placements_total``,
        ``faasm_proto_region_placed_bytes_total``) in the registry
        ``metrics``."""
        tree, placed = self._tree, False
        if tree is None:
            with self._lock:
                tree = self._tree
                if tree is None:
                    import jax
                    tree = jax.tree_util.tree_unflatten(
                        self.treedef, jax.device_put(self.leaves))
                    self._tree, placed = tree, True
        metrics.counter("faasm_proto_region_binds_total",
                        "device region binds (one per call)").inc()
        if placed:
            metrics.counter("faasm_proto_region_placements_total",
                            "device regions placed on the device").inc()
            metrics.counter("faasm_proto_region_placed_bytes_total",
                            "bytes placed on the device by region "
                            "placements").inc(self.nbytes)
        return tree, placed

    def __getstate__(self):
        return {"treedef": self.treedef, "leaves": self.leaves}

    def __setstate__(self, state):
        self.__init__(state["treedef"], state["leaves"])


@dataclass(frozen=True)
class ProtoFaaslet:
    func_name: str
    arena: bytes
    brk: int
    memory_limit: int
    user_state: bytes = b""               # pickled init-code products

    @staticmethod
    def capture(faaslet: Faaslet, user_state: Any = None) -> "ProtoFaaslet":
        return ProtoFaaslet(
            func_name=faaslet.func_name,
            arena=faaslet.snapshot_arena(),
            brk=faaslet.brk_value,
            memory_limit=faaslet.memory_limit,
            user_state=pickle.dumps(user_state) if user_state is not None else b"",
        )

    # -- per-process decoded caches (built once, shared by every restore) ------

    def arena_base(self) -> ArenaBase:
        """The shared read-only CoW base for this snapshot (decoded once)."""
        base = self.__dict__.get("_arena_base")
        if base is None:
            with _cache_lock:
                base = self.__dict__.get("_arena_base")
                if base is None:
                    base = ArenaBase(self.arena, self.memory_limit)
                    object.__setattr__(self, "_arena_base", base)
        return base

    def user_state_template(self) -> Any:
        """Init-code products decoded once (no per-restore ``pickle.loads``).

        Shared read-only across every Faaslet restored from this proto."""
        if not self.user_state:
            return None
        if "_user_state_tpl" not in self.__dict__:
            with _cache_lock:
                if "_user_state_tpl" not in self.__dict__:
                    object.__setattr__(self, "_user_state_tpl",
                                       pickle.loads(self.user_state))
        return self.__dict__["_user_state_tpl"]

    # -- restore ---------------------------------------------------------------

    def restore(self, host_id: str) -> Tuple[Faaslet, Any]:
        """Stamp out a fresh Faaslet from this snapshot (any host).

        O(1) in arena size: binds the shared CoW base instead of copying."""
        f = Faaslet(self.func_name, host_id, memory_limit=self.memory_limit,
                    initial_pages=0)
        f.bind_base(self.arena_base(), self.brk)
        f.restored_from_proto = True
        return f, self.user_state_template()

    def restore_copy(self, host_id: str) -> Tuple[Faaslet, Any]:
        """Full-copy restore: the pre-CoW path (O(arena) memcpy + fresh
        ``pickle.loads``), kept as the benchmark comparison baseline."""
        f = Faaslet(self.func_name, host_id, memory_limit=self.memory_limit)
        f.restore_arena(self.arena, self.brk)
        f.restored_from_proto = True
        state = pickle.loads(self.user_state) if self.user_state else None
        return f, state

    # -- cross-host / global-tier transport -----------------------------------

    def __getstate__(self):
        # decoded caches (memfd-backed ArenaBase, live template objects) must
        # not travel with the snapshot bytes
        return {k: getattr(self, k) for k in _PICKLE_FIELDS}

    def __setstate__(self, state):
        for k in _PICKLE_FIELDS:
            object.__setattr__(self, k, state[k])

    def serialize(self) -> bytes:
        return pickle.dumps(self)

    @staticmethod
    def deserialize(data: bytes) -> "ProtoFaaslet":
        obj = pickle.loads(data)
        if not isinstance(obj, ProtoFaaslet):
            raise TypeError("not a ProtoFaaslet snapshot")
        return obj

    def size_bytes(self) -> int:
        return len(self.arena) + len(self.user_state)


class ExecutableCache:
    """Compiled-executable snapshots keyed by (fn, arch, shape, mesh) fingerprint."""

    def __init__(self):
        self._cache: Dict[Tuple, Any] = {}
        self._lock = threading.Lock()

    def get_or_build(self, key: Tuple, build: Callable[[], Any]):
        """Returns (executable, was_hit, seconds_spent).  The seconds are
        the whole of ``build``, its warm-up run included; the compiles
        themselves are ``jax.compile`` spans while tracing is armed."""
        with self._lock:
            if key in self._cache:
                return self._cache[key], True, 0.0
        t0 = tclock.now()
        built = build()
        dt = tclock.now() - t0
        with self._lock:
            self._cache.setdefault(key, built)
        return built, False, dt
