#!/usr/bin/env python3
"""The readings that a cell's correctness limit is set from, on the chip.

  python3 bench/calibrate.py --workload qwen3-4b.chat --seeds 1,2,3 --seconds 15

For each seed in one process: the cell's set-up, a short window at the
cell's own load and its drain (``harness.serve``), then the sample that a
run checks.  It prints one JSON line per seed with the widest gap of the
served tokens (the program's reading) and the widest gap of the tokens
that the fp8 reference puts first at the same positions (the control's
reading).  The limit in ``limits/<workload>.json`` lies between the
largest program reading and the smallest control reading.  The
benchmark's own runs never call this.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT)]
    from bench.run import start_jax
    if start_jax(1) is None:
        return 2
    from bench import cell as cell_mod
    from bench import correct, harness

    cell = cell_mod.load(args.workload)
    cfg, mix = cell["config"], cell["traffic"]
    for seed in (int(s) for s in args.seeds.split(",")):
        record, w = harness.serve(cell, seed=seed, seconds=args.seconds,
                                  trace=False, t_proc=time.perf_counter())
        finished = [r for r in record["requests"] if r.done]
        seqs = [(r.req.prompt, list(r.req.out))
                for r in correct.sample(finished, seed)]
        size = (cfg, mix["s_max"], mix["output"]["max"], seqs)
        prog = correct.served_gaps(cell["family"], w, *size)
        ctrl = correct.served_gaps(cell["family"], w, *size, control=True)
        print(json.dumps({
            "seed": seed, "finished": len(finished), "tokens": len(prog),
            "program_max_gap": float(prog.max()),
            "program_flips": int((prog > 0).sum()),
            "control_max_gap": float(ctrl.max()),
            "control_flips": int((ctrl > 0).sum())}), flush=True)
        del record, w
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
