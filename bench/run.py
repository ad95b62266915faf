#!/usr/bin/env python3
"""Benchmark of Faaslet inference on the chip, one cell per run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for.  Exits nonzero, printing no result, without a TPU.  The last line of
standard output is the JSON result: the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``.  The numbers that
decide ``correct`` are printed with their limits as the last lines of
standard error and under ``checks``, the result's last key.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
PEAKS = ROOT / "bench" / "peaks.json"


def peaks_for(kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"{PEAKS.name}; add them with their source")
    return table["devices"][kind]


def finite(x):
    """``x`` with every non-finite number replaced by 1e300, so the line
    stays strict JSON (a check of a token outside the vocabulary reads
    infinite)."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return 1e300
    return x


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    # the compile cache lives in the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import manifest
    bench = manifest.load()
    cell = manifest.cell(bench, args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3
    peaks = peaks_for(devs[0].device_kind)
    from repro.kernels.common import resolve_backend
    from repro.launch.compile_cache import enable_compile_cache
    if resolve_backend("auto") != "pallas":
        print("bench: the kernels do not resolve to Pallas", file=sys.stderr)
        return 3
    print(f"device: {devs[0].device_kind} x{len(devs)}", file=sys.stderr,
          flush=True)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from bench import harness
    result = harness.run_cell(bench, cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START, peaks=peaks)
    result.pop("_calls")
    readings = result.pop("_readings")
    print(f"readings: {json.dumps(readings)}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
