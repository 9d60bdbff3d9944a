"""LM training launcher.

Without ``--smoke`` this runs on a ``(data=1, model=M)`` mesh of the live
devices with the recommended sharding policy; ``--smoke`` trains the
reduced config of the same family on a 1x1 host mesh end-to-end with
checkpointing, straggler monitoring, and restart-from-latest — the full
training path.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --steps 50 --ckpt-dir /tmp/run1
"""
from __future__ import annotations

import argparse
import time

import jax

from ..configs.base import get_config, reduced
from ..data.pipeline import for_arch
from ..models import transformer
from ..models.steps import default_microbatches, make_train_step
from ..runtime.checkpoint import CheckpointManager
from ..runtime.resilience import StragglerMonitor
from .mesh import make_host_mesh, make_live_mesh
from . import sharding


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on the host mesh (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--model", type=int, default=None,
                    help="model-axis size (default: every live device)")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
        mesh = make_host_mesh()
    else:
        mesh = make_live_mesh(args.model)
    opts = sharding.recommended_options(cfg, "train")

    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    stream = for_arch(cfg, batch=args.batch, seq=args.seq)
    mb = opts.microbatches or default_microbatches(cfg, args.batch)
    mb = min(mb, args.batch)
    opt_init, train_step = make_train_step(cfg, lr=args.lr, microbatches=mb)
    opt = opt_init(params)
    print(f"[train] {cfg.name}: {transformer.param_count(params)/1e6:.2f}M "
          f"params, mesh {dict(mesh.shape)}, microbatches {mb}")

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if mgr and mgr.latest_step() is not None:
        (params, opt), manifest = mgr.restore((params, opt))
        start = manifest["step"]
        print(f"[restore] resuming at step {start}")

    from ..shardctx import activation_sharding
    mon = StragglerMonitor()
    with mesh, activation_sharding(mesh):
        step_fn = jax.jit(train_step)
        t0 = time.time()
        for step in range(start, args.steps):
            mon.start_step(step)
            params, opt, metrics = step_fn(params, opt,
                                           stream.get_batch(step))
            slow = mon.end_step()
            if step % 10 == 0 or step == args.steps - 1:
                # logging-cadence sync (every 10th step), not per-step
                print(f"step {step:5d} loss {float(metrics['loss']):.4f}"  # reprolint: ignore[host-sync]
                      f" ({time.time()-t0:.1f}s)"
                      + ("  [straggler]" if slow else ""), flush=True)
            if mgr and (step + 1) % args.ckpt_every == 0:
                mgr.save(step + 1, (params, opt), extra={"data_step": step + 1})
        if mgr:
            mgr.wait()
    if mon.events:
        print(f"[stragglers] {len(mon.events)} slow steps flagged")


if __name__ == "__main__":
    main()
