"""Unified model core for all 10 assigned architectures.

The layer stack is organized as ``n_units`` repetitions of
``cfg.block_pattern`` (+ an explicit ``tail_pattern``), scanned with
``lax.scan`` over stacked unit parameters — heterogeneous stacks (5:1
local:global, dense/MoE alternation, Griffin 1:2, interleaved cross-attn)
stay exact while the HLO stays one-unit-sized (DESIGN §4).

Three entry points:
  * ``forward_train``: teacher-forced logits (+ MoE aux loss)
  * ``prefill``:       builds the serving cache, returns last-token logits
  * ``decode_step``:   one token against the cache

Caches are pytrees mirroring the unit structure; "l" layers hold ring
buffers (window slots), "r"/"s" layers hold recurrent state — constant
memory in context length (why hybrid/ssm archs run long_500k).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from .. import shardctx
from . import attention as attn
from . import ffn as ffn_mod
from . import rglru as rglru_mod
from . import ssm as ssm_mod
from .common import dense_init, dtype_of, embed_init, rms_norm

Params = Any
Cache = Any


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg, kind: str) -> Params:
    ks = jax.random.split(key, 6)
    dt = dtype_of(cfg.param_dtype)
    d = cfg.d_model
    norm = lambda: jnp.zeros((d,), dt)
    if kind == "s":
        return {"ssm": ssm_mod.init_ssm(ks[0], cfg)}
    if kind == "r":
        return {"norm1": norm(), "rglru": rglru_mod.init_rglru(ks[0], cfg),
                "norm2": norm(), "ffn": ffn_mod.init_ffn(ks[1], cfg)}
    if kind == "m":
        return {"norm1": norm(), "attn": attn.init_attention(ks[0], cfg),
                "norm2": norm(), "moe": ffn_mod.init_moe(ks[1], cfg)}
    if kind == "x":
        return {"norm1": norm(),
                "xattn": attn.init_attention(ks[0], cfg, cross=True),
                "norm2": norm(), "ffn": ffn_mod.init_ffn(ks[1], cfg)}
    if kind == "d":
        return {"norm1": norm(), "attn": attn.init_attention(ks[0], cfg),
                "norm_x": norm(),
                "xattn": attn.init_attention(ks[1], cfg, cross=True),
                "norm2": norm(), "ffn": ffn_mod.init_ffn(ks[2], cfg)}
    # "g" | "l" | "e"
    return {"norm1": norm(), "attn": attn.init_attention(ks[0], cfg),
            "norm2": norm(), "ffn": ffn_mod.init_ffn(ks[1], cfg)}


def _init_stack(key, cfg, pattern, n: int) -> Params:
    """Stacked params: {"slot{i}": vmapped init over n copies}."""
    out = {}
    for i, kind in enumerate(pattern):
        key, sub = jax.random.split(key)
        keys = jax.random.split(sub, n)
        out[f"slot{i}"] = jax.vmap(
            functools.partial(_init_layer, cfg=cfg, kind=kind))(keys)
    return out


def init_params(key, cfg) -> Params:
    ks = jax.random.split(key, 6)
    params = {
        "embed": embed_init(ks[0], (cfg.vocab, cfg.d_model),
                            dtype_of(cfg.param_dtype)),
        "units": _init_stack(ks[1], cfg, cfg.block_pattern, cfg.n_units),
        "final_norm": jnp.zeros((cfg.d_model,), dtype_of(cfg.param_dtype)),
    }
    if cfg.tail_pattern:
        params["tail"] = [
            _init_layer(k, cfg, kind) for k, kind in
            zip(jax.random.split(ks[2], len(cfg.tail_pattern)),
                cfg.tail_pattern)]
    if not cfg.tie_embeddings:
        params["head"] = dense_init(ks[3], (cfg.d_model, cfg.vocab),
                                    dtype_of(cfg.param_dtype))
    if cfg.enc_layers:
        params["encoder"] = {
            "units": _init_stack(ks[4], cfg, ("e",), cfg.enc_layers),
            "final_norm": jnp.zeros((cfg.d_model,),
                                    dtype_of(cfg.param_dtype)),
        }
    return params


def param_count(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# full-sequence layer application (train / prefill)
# ---------------------------------------------------------------------------

def _layer_full(p, cfg, kind, x, positions, ctx, want_cache: bool,
                s_max: int = 0, pad_mask=None):
    """Apply one layer to a full sequence.  Returns (x, aux, cache).

    ``pad_mask`` (B, S) marks valid (non-left-pad) positions of ragged
    serving batches, and EVERY kind honors it: attention layers mask pad
    keys, recurrent kinds ("r"/"s") zero pad inputs ahead of their causal
    convs and thread a reset mask through the scan kernels -- a left-padded
    row equals its solo run on any stack the engine can serve.
    """
    aux = jnp.zeros((), jnp.float32)
    cache = ()
    cdt = dtype_of(cfg.compute_dtype)
    if kind == "s":
        if want_cache:
            y, cache = ssm_mod.apply_ssm(p["ssm"], cfg, x, want_cache=True,
                                         pad_mask=pad_mask)
        else:
            y = ssm_mod.apply_ssm(p["ssm"], cfg, x, pad_mask=pad_mask)
        return x + y, aux, cache
    if kind == "r":
        normed = rms_norm(x, p["norm1"])
        if want_cache:
            h, cache = rglru_mod.apply_rglru(p["rglru"], cfg, normed,
                                             want_cache=True,
                                             pad_mask=pad_mask)
        else:
            h = rglru_mod.apply_rglru(p["rglru"], cfg, normed,
                                      pad_mask=pad_mask)
        x = x + h
        x = x + ffn_mod.apply_ffn(p["ffn"], cfg, rms_norm(x, p["norm2"]))
        return x, aux, cache
    if kind == "x":
        normed = rms_norm(x, p["norm1"])
        kv = attn.context_kv(p["xattn"], cfg, ctx)
        x = x + attn.cross_attention(p["xattn"], cfg, normed, kv)
        x = x + ffn_mod.apply_ffn(p["ffn"], cfg, rms_norm(x, p["norm2"]))
        if want_cache:
            cache = {"ctx_kv": kv}
        return x, aux, cache
    if kind == "d":
        normed = rms_norm(x, p["norm1"])
        out, (k, v) = attn.self_attention(p["attn"], cfg, normed,
                                          positions, kind="g",
                                          pad_mask=pad_mask)
        x = x + out
        kv = attn.context_kv(p["xattn"], cfg, ctx)
        x = x + attn.cross_attention(p["xattn"], cfg,
                                     rms_norm(x, p["norm_x"]), kv)
        x = x + ffn_mod.apply_ffn(p["ffn"], cfg, rms_norm(x, p["norm2"]))
        if want_cache:
            cache = {"self": _fill_kv(cfg, k, v, s_max, cdt), "ctx_kv": kv}
        return x, aux, cache

    # attention layers: g / l / e / m
    akind = "l" if kind == "l" else ("e" if kind == "e" else "g")
    normed = rms_norm(x, p["norm1"])
    out, (k, v) = attn.self_attention(p["attn"], cfg, normed, positions,
                                      kind=akind, pad_mask=pad_mask)
    x = x + out
    if kind == "m":
        y, aux = ffn_mod.apply_moe(p["moe"], cfg, rms_norm(x, p["norm2"]))
        x = x + y
    else:
        x = x + ffn_mod.apply_ffn(p["ffn"], cfg, rms_norm(x, p["norm2"]))
    if want_cache:
        if kind == "l":
            ring = attn.init_ring_cache(cfg, x.shape[0], cdt)
            cache = attn.prefill_into_ring(ring, k.astype(cdt),
                                           v.astype(cdt), k.shape[1])
        elif kind != "e":
            cache = _fill_kv(cfg, k, v, s_max, cdt)
    return x, aux, cache


def _fill_kv(cfg, k, v, s_max, dtype):
    full = attn.init_kv_cache(cfg, k.shape[0], s_max, dtype)
    return attn.prefill_into_kv(full, k.astype(dtype), v.astype(dtype))


def _run_stack(params, cfg, pattern, x, positions, ctx, want_cache,
               s_max=0, remat=False, pad_mask=None):
    """Scan over stacked units, then apply tail layers.  Returns
    (x, aux_sum, caches) with caches = {"units": ..., "tail": [...]}.
    """

    def unit_fn(x, unit_p):
        aux = jnp.zeros((), jnp.float32)
        caches = {}
        x = shardctx.constrain(x, "dp", "sp", None)
        for i, kind in enumerate(pattern):
            x, a, c = _layer_full(unit_p[f"slot{i}"], cfg, kind, x,
                                  positions, ctx, want_cache, s_max,
                                  pad_mask=pad_mask)
            x = shardctx.constrain(x, "dp", "sp", None)
            aux = aux + a
            caches[f"slot{i}"] = c
        return x, (aux, caches)

    if remat:
        if shardctx.remat_offload_active():
            # host-offloaded carry stacks: HBM holds one unit's activations,
            # the saved per-unit inputs stream to host DRAM (§Perf cell B).
            policy = jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded=["unit_carry"],
                offload_src="device", offload_dst="pinned_host")
            inner = unit_fn

            def named_unit(x, unit_p):
                from jax.ad_checkpoint import checkpoint_name
                return inner(checkpoint_name(x, "unit_carry"), unit_p)

            unit_fn = jax.checkpoint(named_unit, policy=policy)
        else:
            unit_fn = jax.checkpoint(unit_fn)

    def scan_body(carry, unit_p):
        x, aux = carry
        x, (a, caches) = unit_fn(x, unit_p)
        return (x, aux + a), caches

    (x, aux), unit_caches = jax.lax.scan(
        scan_body, (x, jnp.zeros((), jnp.float32)), params["units"])

    tail_caches = []
    for tp, kind in zip(params.get("tail", []), cfg.tail_pattern):
        x, a, c = _layer_full(tp, cfg, kind, x, positions, ctx,
                              want_cache, s_max, pad_mask=pad_mask)
        aux = aux + a
        tail_caches.append(c)
    return x, aux, {"units": unit_caches, "tail": tail_caches}


def _encode(params, cfg, src_embeds):
    """Run the (bidirectional) encoder stack on frame embeddings."""
    enc = params["encoder"]
    pos = jnp.arange(src_embeds.shape[1])
    x = src_embeds.astype(dtype_of(cfg.compute_dtype))

    def unit_fn(x, unit_p):
        x, _, _ = _layer_full(unit_p["slot0"], cfg, "e", x, pos, None, False)
        return x, None

    x, _ = jax.lax.scan(lambda c, p: unit_fn(c, p), x, enc["units"])
    return rms_norm(x, enc["final_norm"])


def _context(params, cfg, batch):
    """Cross-attention context: image embeds (vlm) or encoder output (audio)."""
    if cfg.frontend == "vision":
        return batch["image_embeds"].astype(dtype_of(cfg.compute_dtype))
    if cfg.enc_layers:
        return _encode(params, cfg, batch["src_embeds"])
    return None


def _logits(params, cfg, x):
    x = rms_norm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = (x @ head).astype(jnp.float32)
    return shardctx.constrain(logits, "dp", None, "tp")


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def forward_train(params, cfg, batch):
    """Teacher-forced logits.  batch: tokens (B,S) [+ image_embeds /
    src_embeds].  Returns (logits (B,S,V) fp32, aux)."""
    tokens = batch["tokens"]
    x = params["embed"][tokens].astype(dtype_of(cfg.compute_dtype))
    x = shardctx.constrain(x, "dp", "sp", None)
    ctx = _context(params, cfg, batch)
    positions = jnp.arange(tokens.shape[1])
    x, aux, _ = _run_stack(params, cfg, cfg.block_pattern, x, positions, ctx,
                           want_cache=False, remat=cfg.remat)
    return _logits(params, cfg, x), aux


def prefill(params, cfg, batch, s_max: int, pad=None):
    """Build the serving cache from a prompt.  Returns (last-token logits
    (B,V), cache).  ``s_max`` sizes the KV buffers (prompt + decode budget).

    ``pad`` (B,) int32 gives each row's LEFT-pad token count for ragged
    batches: attention masks the pad positions and RoPE uses the shifted
    per-row positions; recurrent ("r"/"s") layers zero pad inputs ahead of
    their convs and reset the scan state at the pad boundary -- a padded
    prompt's logits, KV/ring caches, and recurrent state exactly equal its
    solo run on every stack kind.  The pad vector rides in the cache
    (``caches["pad"]``) so ``decode_step`` keeps masking those slots;
    padless calls leave the cache structure unchanged.
    """
    # named for profiler dumps (the engine runs it as the ``prefill``
    # program inside its ``engine.admit`` span; docs/observability.md)
    with jax.named_scope("repro.prefill"):
        tokens = batch["tokens"]
        x = params["embed"][tokens].astype(dtype_of(cfg.compute_dtype))
        ctx = _context(params, cfg, batch)
        s = tokens.shape[1]
        if pad is None:
            positions = jnp.arange(s)
            pad_mask = None
        else:
            pad = jnp.asarray(pad, jnp.int32)
            # row i's first real token sits at index pad[i] -> position 0
            positions = jnp.maximum(jnp.arange(s)[None, :] - pad[:, None], 0)
            pad_mask = jnp.arange(s)[None, :] >= pad[:, None]  # (B, S) valid
        x, _, caches = _run_stack(params, cfg, cfg.block_pattern, x,
                                  positions, ctx, want_cache=True,
                                  s_max=s_max, remat=False,
                                  pad_mask=pad_mask)
        caches["pos"] = jnp.int32(s)
        if pad is not None:
            caches["pad"] = pad
        logits = _logits(params, cfg, x[:, -1:])[:, 0]
        return logits, caches


# -- decode -------------------------------------------------------------------

def _layer_decode(p, cfg, kind, x, cache, pos, pad=None):
    """Single-token layer step.  Returns (x, new_cache)."""
    if kind == "s":
        y, cache = ssm_mod.apply_ssm_decode(p["ssm"], cfg, x, cache)
        return x + y, cache
    if kind == "r":
        h, cache = rglru_mod.apply_rglru_decode(
            p["rglru"], cfg, rms_norm(x, p["norm1"]), cache)
        x = x + h
        x = x + ffn_mod.apply_ffn(p["ffn"], cfg, rms_norm(x, p["norm2"]))
        return x, cache
    if kind == "x":
        normed = rms_norm(x, p["norm1"])
        x = x + attn.decode_cross_attention(p["xattn"], cfg, normed,
                                            cache["ctx_kv"])
        x = x + ffn_mod.apply_ffn(p["ffn"], cfg, rms_norm(x, p["norm2"]))
        return x, cache
    if kind == "d":
        normed = rms_norm(x, p["norm1"])
        out, new_self = attn.decode_self_attention(p["attn"], cfg, normed,
                                                   cache["self"], pos,
                                                   kind="g", pad=pad)
        x = x + out
        x = x + attn.decode_cross_attention(p["xattn"], cfg,
                                            rms_norm(x, p["norm_x"]),
                                            cache["ctx_kv"])
        x = x + ffn_mod.apply_ffn(p["ffn"], cfg, rms_norm(x, p["norm2"]))
        return x, {"self": new_self, "ctx_kv": cache["ctx_kv"]}

    akind = "l" if kind == "l" else "g"
    normed = rms_norm(x, p["norm1"])
    out, cache = attn.decode_self_attention(p["attn"], cfg, normed, cache,
                                            pos, kind=akind, pad=pad)
    x = x + out
    if kind == "m":
        y, _ = ffn_mod.apply_moe(p["moe"], cfg, rms_norm(x, p["norm2"]))
        x = x + y
    else:
        x = x + ffn_mod.apply_ffn(p["ffn"], cfg, rms_norm(x, p["norm2"]))
    return x, cache


def decode_step(params, cfg, caches, tokens):
    """One decode step.  tokens: (B,) int32.  Returns (logits (B,V), caches).
    The write position comes from ``caches["pos"]`` (synchronized batch);
    a ``caches["pad"]`` vector (ragged prefill) keeps per-row RoPE positions
    shifted and pad cache slots masked."""
    pos = caches["pos"]
    pad = caches.get("pad")
    x = params["embed"][tokens][:, None, :].astype(dtype_of(cfg.compute_dtype))

    def scan_body(x, inp):
        unit_p, unit_c = inp
        new_c = {}
        for i, kind in enumerate(cfg.block_pattern):
            x, c = _layer_decode(unit_p[f"slot{i}"], cfg, kind, x,
                                 unit_c[f"slot{i}"], pos, pad)
            new_c[f"slot{i}"] = c
        return x, new_c

    x, new_unit_caches = jax.lax.scan(
        scan_body, x, (params["units"], caches["units"]))

    new_tail = []
    for tp, kind, tc in zip(params.get("tail", []), cfg.tail_pattern,
                            caches["tail"]):
        x, c = _layer_decode(tp, cfg, kind, x, tc, pos, pad)
        new_tail.append(c)

    logits = _logits(params, cfg, x)[:, 0]
    new_caches = {"units": new_unit_caches, "tail": new_tail,
                  "pos": pos + 1}
    if pad is not None:
        new_caches["pad"] = pad
    return logits, new_caches


def _layer_chunk(p, cfg, kind, x, cache, start, positions, ok):
    """One layer over a prefill chunk.  x (1, C, D) holds the chunk's tokens
    at absolute positions ``positions = start + arange(C)``; ``ok`` (C,) bool
    marks real (non-right-pad) tokens of the final partial chunk.

    Attention kinds ("g"/"m") run chunk-parallel against the dense scratch
    cache; stateful kinds ("l"/"r"/"s") scan the EXISTING single-token decode
    step across the chunk -- zero new recurrence math -- selecting the old
    cache carry on junk steps so right-pad never advances state.  Junk rows'
    activations are garbage by construction; callers slice the last valid
    row only.  Returns (x, new_cache).
    """
    def masked(step):
        # scan one decode-form step per chunk token; junk steps keep the
        # incoming cache so the final partial chunk is exact
        def body(c, inp):
            xt, pos_t, ok_t = inp
            y, new_c = step(xt[:, None, :], c, pos_t)
            new_c = jax.tree.map(lambda a, b: jnp.where(ok_t, a, b),
                                 new_c, c)
            return new_c, y[:, 0]
        return body

    if kind == "s":
        body = masked(lambda xt, c, _:
                      ssm_mod.apply_ssm_decode(p["ssm"], cfg, xt, c))
        cache, ys = jax.lax.scan(body, cache,
                                 (jnp.swapaxes(x, 0, 1), positions, ok))
        return x + jnp.swapaxes(ys, 0, 1), cache
    if kind == "r":
        normed = rms_norm(x, p["norm1"])
        body = masked(lambda xt, c, _:
                      rglru_mod.apply_rglru_decode(p["rglru"], cfg, xt, c))
        cache, hs = jax.lax.scan(body, cache,
                                 (jnp.swapaxes(normed, 0, 1), positions, ok))
        x = x + jnp.swapaxes(hs, 0, 1)
        x = x + ffn_mod.apply_ffn(p["ffn"], cfg, rms_norm(x, p["norm2"]))
        return x, cache
    if kind == "l":
        normed = rms_norm(x, p["norm1"])
        body = masked(lambda xt, c, pos_t: attn.decode_self_attention(
            p["attn"], cfg, xt, c, pos_t, kind="l"))
        cache, outs = jax.lax.scan(body, cache,
                                   (jnp.swapaxes(normed, 0, 1), positions, ok))
        x = x + jnp.swapaxes(outs, 0, 1)
        x = x + ffn_mod.apply_ffn(p["ffn"], cfg, rms_norm(x, p["norm2"]))
        return x, cache
    if kind != "g":
        # "m" is deliberately excluded: capacity-based MoE routing couples
        # every token in a dispatch group (cumsum capacity contention), so
        # a chunk-local pass cannot reproduce the whole-prompt dispatch
        # exactly -- the engine keeps whole-prompt prefill for MoE stacks
        # (ServingEngine disables prefill_chunk when the pattern has "m").
        # "x"/"d"/"e" are not continuously servable at all
        # (kvpool._check_pattern).
        raise NotImplementedError(
            f"chunked prefill does not serve kind {kind!r}")

    normed = rms_norm(x, p["norm1"])
    out, cache = attn.chunk_self_attention(p["attn"], cfg, normed, cache,
                                           start, positions)
    x = x + out
    x = x + ffn_mod.apply_ffn(p["ffn"], cfg, rms_norm(x, p["norm2"]))
    return x, cache


def prefill_chunk(params, cfg, caches, tokens, start, n_valid):
    """Advance a resumable chunked prefill by one chunk.

    ``caches`` is the {"units", "tail"} core of a batch-1 :func:`prefill`
    cache holding the first ``start`` prompt tokens (chunk 1 IS a plain
    ``prefill`` at the chunk width -- its KV scratch is already sized
    ``s_max``); ``tokens`` (1, C) carries the next chunk, right-padded past
    ``n_valid`` on the final partial chunk.  ``start`` and ``n_valid`` may be
    traced: the serving engine compiles ONE chunk program per chunk width.

    Returns (logits (1, V) of token ``start + n_valid - 1``, new caches with
    the same treedef) -- on the final chunk those logits ARE the whole-prompt
    prefill logits, exactly (attention kinds recompute the identical
    prefix-causal softmax; stateful kinds replay the decode-form recurrence).

    Named ``repro.prefill_chunk`` for profiler dumps (pairs with the host
    "prefill" span the serving telemetry records per chunk).
    """
    with jax.named_scope("repro.prefill_chunk"):
        c = tokens.shape[1]
        x = params["embed"][tokens].astype(dtype_of(cfg.compute_dtype))
        positions = start + jnp.arange(c)
        ok = jnp.arange(c) < n_valid

        def scan_body(x, inp):
            unit_p, unit_c = inp
            new_c = {}
            for i, kind in enumerate(cfg.block_pattern):
                x, cc = _layer_chunk(unit_p[f"slot{i}"], cfg, kind, x,
                                     unit_c[f"slot{i}"], start, positions, ok)
                new_c[f"slot{i}"] = cc
            return x, new_c

        x, new_unit_caches = jax.lax.scan(
            scan_body, x, (params["units"], caches["units"]))

        new_tail = []
        for tp, kind, tc in zip(params.get("tail", []), cfg.tail_pattern,
                                caches["tail"]):
            x, cc = _layer_chunk(tp, cfg, kind, x, tc, start, positions, ok)
            new_tail.append(cc)

        last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        logits = _logits(params, cfg, last)[:, 0]
        return logits, {"units": new_unit_caches, "tail": new_tail}


def _layer_decode_paged(p, cfg, kind, x, cache, block_table, seq_lens,
                        layer=None):
    """Single-token layer step with per-slot cache positions.  Recurrent
    kinds keep per-row O(1) state, so they are position-free and reuse the
    synchronized step; attention kinds go through the paged/per-slot path.
    A pool (:class:`attn.KVCache`) is read-only here, at unit ``layer`` of
    a stacked pool: the step returns the rows' new K/V in its place (see
    :func:`attn.decode_self_attention_paged`).
    """
    if kind in ("s", "r"):
        return _layer_decode(p, cfg, kind, x, cache, None)
    akind = "l" if kind == "l" else "g"
    normed = rms_norm(x, p["norm1"])
    out, cache = attn.decode_self_attention_paged(
        p["attn"], cfg, normed, cache, kind=akind,
        block_table=block_table, seq_lens=seq_lens, layer=layer)
    x = x + out
    if kind == "m":
        y, _ = ffn_mod.apply_moe(p["moe"], cfg, rms_norm(x, p["norm2"]))
        x = x + y
    else:
        x = x + ffn_mod.apply_ffn(p["ffn"], cfg, rms_norm(x, p["norm2"]))
    return x, cache


def decode_step_paged(params, cfg, caches, tokens, block_table, seq_lens):
    """One continuous-batching decode step.  tokens: (B,) int32; ``caches``
    is the pool state from ``serving.kvpool.init_decode_state`` (global KV
    paged, ring/recurrent per-slot); ``block_table`` (B, M) int32 and
    ``seq_lens`` (B,) int32 carry each slot's blocks and cache length --
    there is no shared ``pos`` frontier and no pad vector.  Returns
    (logits (B, V), caches).  Cross-attention kinds are not servable here
    (see ``kvpool._check_pattern``).

    The KV pools (:class:`attn.KVCache` leaves) are read-only inside the
    layer scan: each pool layer gathers its rows' blocks straight from the
    stacked pool and emits its rows' new K/V as the scan's output, and
    after the scan one scatter per tensor writes every layer's rows into
    the pool (:func:`attn.write_paged`).  The engine donates ``caches``, so
    that write is in place; carrying the pools through the scan instead
    would copy them whole every step.  Ring and recurrent state is per
    slot and small, and rides the scan as before.

    Named ``repro.decode_paged`` for profiler dumps (the engine runs it as
    the ``decode_paged`` program, between its ``engine.dispatch`` and
    ``engine.sync`` spans)."""
    with jax.named_scope("repro.decode_paged"):
        seq_lens = jnp.asarray(seq_lens, jnp.int32)
        x = params["embed"][tokens][:, None, :].astype(
            dtype_of(cfg.compute_dtype))
        units, tail = caches["units"], caches["tail"]
        pools = {s: c for s, c in units.items()
                 if isinstance(c, attn.KVCache)}
        state = {s: c for s, c in units.items() if s not in pools}
        kv = [c for c in (*pools.values(), *tail)
              if isinstance(c, attn.KVCache)]
        if kv:                      # every pool has the same block size
            blk, off = attn.paged_write_target(block_table, seq_lens,
                                               kv[0].k.shape[-3])

        def scan_body(x, inp):
            unit_p, unit_s, layer = inp
            new_c = {}
            for i, kind in enumerate(cfg.block_pattern):
                s = f"slot{i}"
                cache = pools[s] if s in pools else unit_s[s]
                x, new_c[s] = _layer_decode_paged(
                    unit_p[s], cfg, kind, x, cache, block_table, seq_lens,
                    layer)
            return x, new_c

        n_units = jax.tree.leaves(params["units"])[0].shape[0]
        x, new_c = jax.lax.scan(
            scan_body, x, (params["units"], state, jnp.arange(n_units)))
        new_units = {s: attn.write_paged(pools[s], c, blk, off)
                     if s in pools else c for s, c in new_c.items()}

        new_tail = []
        for tp, kind, tc in zip(params.get("tail", []), cfg.tail_pattern,
                                tail):
            x, c = _layer_decode_paged(tp, cfg, kind, x, tc, block_table,
                                       seq_lens)
            if isinstance(tc, attn.KVCache):
                c = attn.write_paged(tc, c, blk, off)
            new_tail.append(c)

        logits = _logits(params, cfg, x)[:, 0]
        return logits, {"units": new_units, "tail": new_tail}
