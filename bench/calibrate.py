#!/usr/bin/env python3
"""Readings for setting the limits of ``correct``, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 20

Runs the cell in one process, once per seed, with the float8 control put in
the program's place (``harness.run_cell(control=True)``).  Each run prints
one JSON line: the program's own ``token_gap`` and whether the program's
answers pass every limit (``program_correct``), the control's ``token_gap``
on the same sample and the run's verdict with the control in place
(``correct``, which has to be false), and ``stats_miscount``.  Not part of a
benchmark run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from bench import harness, manifest
    from bench.run import peaks_for
    from repro.launch.compile_cache import enable_compile_cache
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = manifest.load()
    cell = manifest.cell(bench, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        r = harness.run_cell(bench, cell, seed, args.seconds, False,
                             t_start=t, peaks=peaks_for(dev.device_kind),
                             control=True)
        checks = {k: v["value"] for k, v in r["checks"].items()}
        rd = r["_readings"]
        program = dict(checks, token_gap=rd["program_token_gap"])
        line = {"seed": seed, "correct": r["correct"],
                "program_correct": r["failed"] == 0 and all(
                    program[k] <= v["limit"] for k, v in r["checks"].items()),
                "failed": r["failed"], "attempted": r["attempted"],
                "program": program, "control_token_gap": checks["token_gap"],
                "readings": rd,
                "metrics": {k: v["value"] for k, v in r["metrics"].items()},
                "peak": r["device"]["memory_peak_bytes"],
                "run_s": time.perf_counter() - t}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
