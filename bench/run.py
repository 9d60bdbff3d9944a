#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

  python3 bench/run.py --workload qwen3-4b.chat --seed 7 --seconds 45 --trace 0

The cell (``BENCHMARK.json``'s ``workloads`` entry) is read from data
alone; see ``bench/cell.py``.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a run that also records
a profiler trace.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and last
``checks``: each number compared beside its limit, which also end standard
error).  With no TPU, or fewer chips than the cell asks for, the run exits
2 and prints no result.  JAX's compilation cache is ``.jax_cache/`` in the
checkout, so only a cell's first run there compiles.
"""
import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def start_jax(chips: int):
    """JAX with the checkout's compilation cache; the devices, or None when
    there is no TPU or fewer chips than ``chips``."""
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"no result: {chips} TPU chip(s) needed, JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return None
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT)]
    from bench import cell as cell_mod
    cell = cell_mod.load(args.workload)
    if start_jax(cell["chips"]) is None:
        return 2

    from bench import harness
    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), t_proc=T_PROC, log=log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
