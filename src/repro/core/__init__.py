"""FAASM core: Faaslets, host interface, Proto-Faaslets, scheduler, runtime."""
from repro.core.faaslet import (CONTAINER_OVERHEAD_BYTES,
                                FAASLET_OVERHEAD_BYTES, ArenaBase, Faaslet,
                                FaasletMemoryFault, ResourceLimitExceeded)
from repro.core.host_interface import CallCancelled, FaasmAPI, StateKeyError
from repro.core.proto import DeviceRegion, ExecutableCache, ProtoFaaslet
from repro.core.runtime import (BatchTimeout, Call, CompletionLatch,
                                FaasmRuntime, FunctionDef, Host)
from repro.core.scheduler import LocalScheduler
from repro.core.chain import await_all, chain, outputs
from repro.core.vfs import VirtualFS

__all__ = [
    "ArenaBase", "Faaslet", "FaasletMemoryFault", "ResourceLimitExceeded",
    "FaasmAPI", "CallCancelled",
    "StateKeyError", "DeviceRegion", "ExecutableCache", "ProtoFaaslet",
    "Call",
    "BatchTimeout", "CompletionLatch", "FaasmRuntime",
    "FunctionDef", "Host", "LocalScheduler", "await_all", "chain", "outputs",
    "VirtualFS", "FAASLET_OVERHEAD_BYTES", "CONTAINER_OVERHEAD_BYTES",
]
