#!/usr/bin/env python3
"""Chip smoke check: drive the system's two main paths once on a TPU.

  python chip_smoke.py              # one chip: serving, controller, LyMDO
  python chip_smoke.py --chips 4    # the cells-sharded controller grid only

Phases on one chip:

* serving -- qwen3-0.6b at its published widths (28 layers, d 1024, 16/8
  heads of 128, vocab 151936, bf16) with random weights from ``--seed``,
  served by ``ServingEngine`` in continuous mode: paged KV pool, 8 slots,
  s_max 1024, 16 requests whose prompts range from 5 to 800 tokens, so
  ragged (left-padded) admission and chunked prefill both run.  Every
  request must finish its ``max_new`` tokens, and each is checked against
  a solo ``transformer.prefill`` + ``decode_step`` of its prompt on the
  same chip (see ``serving_phase`` for the tolerance).
* controller -- ``ScenarioGrid(multicell_grid(cells=1024, ues=16))``, the
  Oracle rollout of 200 slots through ``run_fixed_batched``.  The compiled
  rollout must hold the Pallas ``partition_sweep`` kernel, and its per-cell
  delays must match the rollout with the ``lax`` sweep to 1e-5.
* lymdo -- four PPO updates of LyMDO on ``paper_table1`` through
  ``core.lymdo.Runner``; every logged reward must be finite.

``--chips 4`` runs only the grid above on a 4-chip ``("cells",)`` mesh and
holds it to the one-device rollout at 1e-5, with the cells spread over
four distinct devices.

The last line of stdout is ``{"ok": true, "device": {...}}`` with the
device as JAX reports it.  Without a TPU, outside a checkout of this
repository, or when any phase fails, the script exits non-zero and prints
no such line.  Weights and data come from ``--seed``; nothing is read but
the checkout's own files.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

SERVE_ARCH = "qwen3-0.6b"
SLOTS = 8
S_MAX = 1024
# 8 prompt lengths x 2 requests: <= 32 tokens admit whole (5 and 29 left-pad
# to their bucket), longer ones stream through chunked prefill
PROMPT_LENS = (5, 16, 29, 32, 47, 100, 333, 800)
MAX_NEW = 16
# bf16 logits: the engine and the solo reference round different
# intermediates (bucket padding, chunk boundaries, the paged vs the dense
# cache), so a logit may move by a few bf16 steps of the row's largest
# magnitude (2^-8 relative each).  An engine token is accepted when the
# reference logit it picked lies within 2^-5 of that magnitude -- eight
# such steps -- of the reference maximum.
LOGIT_TOL = 2.0 ** -5
PROMPT_DRAWS = 8

GRID_CELLS = 1024
GRID_UES = 16
GRID_STEPS = 200
PARITY_RTOL = 1e-5          # the grid's sharded/backend parity contract

LYMDO_EPISODES = 4


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CheckFailed(Exception):
    pass


def require(ok, message) -> None:
    """A check that holds under ``python -O`` too (unlike ``assert``)."""
    if not ok:
        raise CheckFailed(message)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def _reference_programs(cfg, s_max):
    """Solo prefill / teacher-forced decode, each judging its logits on the
    device: (argmax, top-2 gap, max - logit[tok], max |logit|)."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer

    def judge(logits, tok):
        row = logits[0]
        top = jax.lax.top_k(row, 2)[0]
        return (jnp.argmax(row).astype(jnp.int32), top[0] - top[1],
                top[0] - row[tok], jnp.max(jnp.abs(row)))

    def prefill(params, toks):
        logits, cache = transformer.prefill(params, cfg, {"tokens": toks},
                                            s_max=s_max)
        return judge(logits, 0), cache

    def decode(params, cache, tok, nxt):
        logits, cache = transformer.decode_step(params, cfg, cache, tok)
        return judge(logits, nxt[0]), cache

    return jax.jit(prefill), jax.jit(decode, donate_argnums=1)


def serving_phase(cfg, *, slots, s_max, prompt_lens, max_new, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer
    from repro.serving.engine import Request, ServingEngine

    params = transformer.init_params(jax.random.PRNGKey(seed), cfg)
    ref_prefill, ref_decode = _reference_programs(cfg, s_max)
    rng = np.random.default_rng(seed)

    # Prompts whose first token the reference decides by more than the
    # tolerance: a closer top-2 is a bf16 tie that either side may break,
    # and the first-token identity check below is exact.
    t0 = time.perf_counter()
    reqs, first_ref, draws = [], {}, 0
    for rid, n in enumerate(prompt_lens + prompt_lens):
        for _ in range(PROMPT_DRAWS):
            draws += 1
            prompt = rng.integers(0, cfg.vocab, n).astype(np.int32)
            (top, gap, _, scale), _ = ref_prefill(params, prompt[None])
            if float(gap) > LOGIT_TOL * float(scale):
                break
        else:
            raise CheckFailed(
                f"no prompt of length {n} with a clear first token in "
                f"{PROMPT_DRAWS} draws (last gap {float(gap):.3g}, scale "
                f"{float(scale):.3g})")
        first_ref[rid] = int(top)
        reqs.append(Request(rid=rid, prompt=prompt, max_new=max_new))
    t_ref = time.perf_counter() - t0

    eng = ServingEngine(cfg, params, slots=slots, s_max=s_max)
    require(not eng.sync_batching and eng.prefill_chunk is not None,
            "engine is not in continuous mode with chunked prefill")
    require(any(len(r.prompt) > eng.prefill_chunk for r in reqs),
            "no prompt is long enough to stream through chunked prefill")
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    done = eng.run_until_idle()
    t_serve = time.perf_counter() - t0
    require(len(done) == len(reqs), f"{len(done)} of {len(reqs)} completed")
    for r in reqs:
        require(r.done and len(r.out) == max_new,
                f"request {r.rid}: {len(r.out)} of {max_new} tokens")
        require(r.out[0] == first_ref[r.rid],
                f"request {r.rid}: first token {r.out[0]} != solo "
                f"{first_ref[r.rid]}")

    # teacher-forced: feed the engine's own tokens to the solo reference
    worst, agree, total = 0.0, 0, 0
    for r in reqs:
        _, cache = ref_prefill(params, r.prompt[None])
        for prev, nxt in zip(r.out[:-1], r.out[1:]):
            (top, _, margin, scale), cache = ref_decode(
                params, cache, jnp.asarray([prev], jnp.int32),
                jnp.asarray([nxt], jnp.int32))
            rel = float(margin) / float(scale)
            worst = max(worst, rel)
            agree += int(top) == nxt
            total += 1
            require(rel <= LOGIT_TOL,
                    f"request {r.rid}: engine token {nxt} sits {rel:.3g} of "
                    f"the logit scale below the solo maximum (tolerance "
                    f"{LOGIT_TOL})")
    say("serving", arch=cfg.name, layers=cfg.n_layers, d_model=cfg.d_model,
        vocab=cfg.vocab, dtype=cfg.compute_dtype, slots=slots, s_max=s_max,
        requests=len(reqs), max_new=max_new,
        prompt_lens=sorted({len(r.prompt) for r in reqs}),
        prompt_draws=draws, decode_dispatches=eng.decode_steps,
        prefill_compiles=eng.prefill_compiles, preemptions=eng.preemptions,
        first_tokens_identical=len(reqs), tokens_checked=total,
        tokens_equal_solo_argmax=agree, worst_rel_margin=worst,
        logit_tol=LOGIT_TOL, serve_s=t_serve, reference_s=t_ref)


# ---------------------------------------------------------------------------
# controller
# ---------------------------------------------------------------------------

def _max_rel(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-30)))


def controller_phase(*, cells, ues, steps, seed):
    import jax
    import numpy as np
    from repro.core import scenarios as sc
    from repro.core.lymdo import run_fixed_batched

    grid = sc.ScenarioGrid(sc.multicell_grid(cells=cells, ues=ues,
                                             seed=seed))
    program = grid.make_rollout("oracle", steps).lower(
        jax.random.PRNGKey(seed)).compile().as_text()
    require("tpu_custom_call" in program,
            "the Oracle rollout holds no Pallas sweep kernel")
    t0 = time.perf_counter()
    m_pl, r_pl = run_fixed_batched(grid, "oracle", steps=steps, seed=seed)
    jax.block_until_ready(r_pl)
    t_run = time.perf_counter() - t0
    m_lax, _ = run_fixed_batched(grid, "oracle", steps=steps, seed=seed,
                                 oracle_backend="lax")
    err = _max_rel(m_pl["delay"], m_lax["delay"])
    require(np.all(np.isfinite(m_pl["delay"])), "non-finite delays")
    require(err <= PARITY_RTOL, f"pallas vs lax per-cell delay rel err {err}")
    cut_diff = float(np.max(np.abs(m_pl["cut_mean"] - m_lax["cut_mean"])))
    say("controller", cells=grid.b, ues=grid.n_ue, cuts=grid.num_cuts,
        slots=steps, kernel_in_program=True,
        delay_rel_err_vs_lax=err, cut_mean_abs_diff_vs_lax=cut_diff,
        mean_delay_s=float(np.mean(m_pl["delay"])), rollout_s=t_run)


def lymdo_phase(*, episodes, seed):
    import numpy as np
    from repro.core.lymdo import RunConfig, Runner
    from repro.core.policies import GaussianTanhPolicy
    from repro.core.ppo import PPO, PPOConfig
    from repro.core.scenarios import make

    env = make("paper_table1").build()
    agent = PPO(GaussianTanhPolicy(env.obs_dim, env.L), env.obs_dim,
                PPOConfig())
    runner = Runner(env, agent, steps=200, mode="lymdo")
    t0 = time.perf_counter()
    _, hist = runner.train(RunConfig(episodes=episodes, steps=200, seed=seed,
                                     chunk=2, log=False))
    t_train = time.perf_counter() - t0
    rewards = np.asarray(hist["reward"])
    require(rewards.shape == (episodes,), f"reward log {rewards.shape}")
    bad = [k for k, v in hist.items() if not np.all(np.isfinite(v))]
    require(not bad, f"non-finite training logs: {bad}")
    say("lymdo", scenario="paper_table1", updates=episodes,
        rewards=[float(x) for x in rewards], train_s=t_train)


def sharded_grid_phase(*, chips, cells, ues, steps, seed):
    import jax
    import numpy as np
    from repro.core import scenarios as sc
    from repro.core.lymdo import run_fixed_batched
    from repro.launch.mesh import make_cells_mesh

    scen = sc.multicell_grid(cells=cells, ues=ues, seed=seed)
    g_one = sc.ScenarioGrid(scen)
    g_mesh = sc.ScenarioGrid(scen).use_mesh(make_cells_mesh(chips))
    t0 = time.perf_counter()
    m_mesh, r_mesh = run_fixed_batched(g_mesh, "oracle", steps=steps,
                                       seed=seed)
    jax.block_until_ready(r_mesh)
    t_run = time.perf_counter() - t0
    m_one, _ = run_fixed_batched(g_one, "oracle", steps=steps, seed=seed)
    errs = {}
    for name in m_one:
        np.testing.assert_allclose(m_mesh[name], m_one[name],
                                   rtol=PARITY_RTOL, atol=1e-7,
                                   err_msg=name)
        errs[name] = float(np.max(np.abs(m_mesh[name] - m_one[name])))
    shards = r_mesh.delay.addressable_shards
    devices = {s.device for s in shards}
    per_dev = sorted({s.data.shape[1] for s in shards})
    require(len(devices) == chips, f"cells on {len(devices)} devices")
    require(per_dev == [cells // chips], f"cells per device {per_dev}")
    say("sharded_grid", chips=chips, cells=cells, ues=ues, slots=steps,
        devices=sorted(str(d) for d in devices), cells_per_device=per_dev[0],
        max_abs_diff_vs_one_device=errs, rollout_s=t_run)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the cells-sharded grid on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"error: {SRC / 'repro'} not found; run chip_smoke.py from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"error: no TPU found (JAX sees {devices[0].platform}); this "
              "check runs on the chip only", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"error: --chips {args.chips} but {len(devices)} TPU "
              "device(s) are live", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    say("start", cache_dir=enable_compile_cache(), chips=args.chips,
        device_kind=devices[0].device_kind, device_count=len(devices))

    if args.chips == 1:
        from repro.configs.base import get_config
        cfg = get_config(SERVE_ARCH)
        serving_phase(cfg, slots=SLOTS, s_max=S_MAX, prompt_lens=PROMPT_LENS,
                      max_new=MAX_NEW, seed=args.seed)
        controller_phase(cells=GRID_CELLS, ues=GRID_UES, steps=GRID_STEPS,
                         seed=args.seed)
        lymdo_phase(episodes=LYMDO_EPISODES, seed=args.seed)
    else:
        sharded_grid_phase(chips=args.chips, cells=GRID_CELLS, ues=GRID_UES,
                           steps=GRID_STEPS, seed=args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
