"""Serving launcher: the ES-side engine under the LyMDO controller.

``--smoke`` serves the reduced config on a 1x1 host mesh with synthetic
requests and prints per-request latency; without it the full config runs
on a ``(data=1, model=M)`` mesh of the live devices (M = ``--model``,
default every device: one chip serves unsharded).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --requests 6
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs.base import get_config, reduced
from ..models import transformer
from ..serving.engine import Request, ServingEngine
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh, make_live_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--model", type=int, default=None,
                    help="model-axis size (default: every live device)")
    ap.add_argument("--sync-batching", action="store_true",
                    help="use the synchronized-batch compat engine instead "
                         "of continuous batching (A/B baseline)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
        mesh = make_host_mesh()
    else:
        mesh = make_live_mesh(args.model)
    if cfg.enc_layers:
        raise SystemExit("enc-dec serving needs src embeddings; use "
                         "examples/serve_partitioned.py patterns")

    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    print(f"[serve] {cfg.name}: {transformer.param_count(params)/1e6:.2f}M "
          f"params, {args.slots} slots, mesh {dict(mesh.shape)}")

    from ..shardctx import activation_sharding
    with mesh, activation_sharding(mesh):
        eng = ServingEngine(cfg, params, slots=args.slots,
                            s_max=args.prompt_len + args.max_new + 8,
                            sync_batching=args.sync_batching)
        rng = np.random.default_rng(0)
        t_submit = {}
        reqs = []
        for rid in range(args.requests):
            r = Request(rid=rid,
                        prompt=rng.integers(0, cfg.vocab,
                                            args.prompt_len).astype(np.int32),
                        max_new=args.max_new)
            reqs.append(r)
            eng.submit(r)
            t_submit[rid] = time.time()
        steps = 0
        t_done = {}
        while eng.step():
            steps += 1
            for r in reqs:
                if r.done and r.rid not in t_done:
                    t_done[r.rid] = time.time()
        for r in reqs:
            lat = (t_done.get(r.rid, time.time()) - t_submit[r.rid]) * 1e3
            print(f"  req {r.rid}: {len(r.out)} tokens, {lat:7.1f} ms, "
                  f"out[:4]={r.out[:4]}")
        mode = "sync" if args.sync_batching else "continuous"
        print(f"[serve] {len(reqs)} requests in {steps} engine steps "
              f"({mode}: {eng.decode_steps} decode dispatches, "
              f"{eng.prefill_compiles} prefill compiles, "
              f"{eng.preemptions} preemptions)")


if __name__ == "__main__":
    main()
