"""Serving layer: partitioned execution correctness + engine behavior."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.models import transformer
from repro.serving.engine import Request, ServingEngine
from repro.serving.partitioned import (PartitionedLM, layer_cut_to_unit,
                                       split_params)


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("qwen3-0.6b"), n_layers=6)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.mark.slow
def test_partitioned_equals_monolithic_all_cuts(setup):
    """UE half + ES half == full model, at EVERY unit cut (the paper's
    correctness requirement: partitioning must not change the function)."""
    cfg, params = setup
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    want, _ = transformer.forward_train(params, cfg, {"tokens": tokens})
    for cut in range(cfg.n_units + 1):
        plm = PartitionedLM(cfg, params, cut)
        got, boundary = plm.infer(tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4, err_msg=f"cut={cut}")


def test_boundary_payload_semantics(setup):
    cfg, params = setup
    plm0 = PartitionedLM(cfg, params, 0)
    plm3 = PartitionedLM(cfg, params, 3)
    assert plm0.boundary_bytes(2, 12) == 2 * 12 * 4           # raw tokens
    assert plm3.boundary_bytes(2, 12) == 2 * 12 * cfg.d_model * 2


def test_layer_cut_to_unit_mapping(setup):
    cfg, _ = setup
    assert layer_cut_to_unit(cfg, 0) == 0      # full edge
    assert layer_cut_to_unit(cfg, 1) == 0      # embed only -> still edge
    assert layer_cut_to_unit(cfg, cfg.n_layers + 2) == cfg.n_units


def test_split_params_partition(setup):
    cfg, params = setup
    ue, es = split_params(params, 2)
    stacked = jax.tree.leaves(params["units"])[0].shape[0]
    assert jax.tree.leaves(ue["units"])[0].shape[0] == 2
    assert jax.tree.leaves(es["units"])[0].shape[0] == stacked - 2
    assert "final_norm" in es and "final_norm" not in ue


def test_engine_serves_all_requests(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, slots=2, s_max=64)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                    max_new=5) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    steps = 0
    while eng.step():
        steps += 1
        assert steps < 200
    assert all(r.done for r in reqs)
    assert all(len(r.out) == 5 for r in reqs)


def test_run_until_idle_returns_completed(setup):
    """Regression: run_until_idle used to return [] unconditionally.  With
    more requests than slots, every request must come back done, with its
    full output, in completion order."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, slots=2, s_max=64)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                    max_new=3) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    finished = eng.run_until_idle()
    assert [r.rid for r in finished] == [0, 1, 2, 3, 4]
    assert all(r.done and len(r.out) == 3 for r in finished)
    # a second call finds nothing new
    assert eng.run_until_idle() == []


def test_engine_batch_matches_solo_equal_lengths(setup):
    """Equal-length prompts involve no ragged padding: each request's greedy
    tokens equal a solo (slots=1) run of the same prompt."""
    cfg, params = setup
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, 8).astype(np.int32)
               for _ in range(2)]
    eng = ServingEngine(cfg, params, slots=2, s_max=64)
    reqs = [Request(rid=i, prompt=p, max_new=4)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    finished = eng.run_until_idle()
    assert len(finished) == 2
    for p, r in zip(prompts, reqs):
        solo_eng = ServingEngine(cfg, params, slots=1, s_max=64)
        solo = Request(rid=0, prompt=p, max_new=4)
        solo_eng.submit(solo)
        solo_eng.run_until_idle()
        assert r.out == solo.out


def test_engine_mixed_lengths_match_solo(setup):
    """Mixed-length batches are EXACT: the pad counts flow into
    transformer.prefill as an attention mask + RoPE position shift, so each
    padded row's greedy tokens equal its solo run (the left-pad limitation
    the engine used to document is gone)."""
    cfg, params = setup
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (5, 9, 12)]
    eng = ServingEngine(cfg, params, slots=3, s_max=64)
    reqs = [Request(rid=i, prompt=p, max_new=4)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    finished = eng.run_until_idle()
    assert len(finished) == 3
    for p, r in zip(prompts, reqs):
        solo_eng = ServingEngine(cfg, params, slots=1, s_max=64)
        solo = Request(rid=0, prompt=p, max_new=4)
        solo_eng.submit(solo)
        solo_eng.run_until_idle()
        assert r.out == solo.out, f"prompt len {len(p)}"


def test_prefill_bucketing_avoids_recompiles(setup):
    """Steady-state serving must not churn the prefill jit cache: prompts
    pad to power-of-two width buckets, so every prompt-length mix inside
    one bucket shares one compiled shape.  Continuous mode prefills each
    request solo, so signatures are (batch=1, bucket, ragged?)."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, slots=2, s_max=64)
    rng = np.random.default_rng(3)
    # 4 waves x mixed lengths 9..15 -> all land in the 16 bucket
    # (always ragged: lengths stay below the bucket width)
    for wave in range(4):
        for i in range(2):
            n = int(rng.integers(9, 16))
            eng.submit(Request(rid=wave * 2 + i,
                               prompt=rng.integers(0, cfg.vocab, n)
                               .astype(np.int32), max_new=2))
        eng.run_until_idle()
    assert eng.prefill_compiles == 1
    assert eng._prefill_shapes == {(1, 16, True)}
    # a longer prompt moves to the next bucket: exactly one more compile
    eng.submit(Request(rid=99, prompt=rng.integers(0, cfg.vocab, 20)
                       .astype(np.int32), max_new=2))
    eng.run_until_idle()
    assert eng.prefill_compiles == 2
    # a pad-free prompt (exactly bucket-width) takes the maskless kernel
    # path: same width, separate signature
    eng.submit(Request(rid=200, prompt=rng.integers(0, cfg.vocab, 16)
                       .astype(np.int32), max_new=2))
    eng.run_until_idle()
    assert (1, 16, False) in eng._prefill_shapes


def test_prefill_bucketing_sync_mode(setup):
    """Compat mode batches the admitted wave into ONE prefill: signatures
    are (slots, bucket, ragged?) exactly as before the continuous engine."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, slots=2, s_max=64, sync_batching=True)
    rng = np.random.default_rng(3)
    for wave in range(3):
        for i in range(2):
            n = int(rng.integers(9, 16))
            eng.submit(Request(rid=wave * 2 + i,
                               prompt=rng.integers(0, cfg.vocab, n)
                               .astype(np.int32), max_new=2))
        eng.run_until_idle()
    assert eng._prefill_shapes == {(2, 16, True)}


def test_bucket_respects_decode_budget(setup):
    """Bucket slack must never eat the KV decode budget: with s_max=24 a
    13-token prompt cannot round up to the 16 bucket when max_new=10
    (16 + 10 > 24) -- the engine falls back to the exact width and the
    request still matches its solo run; a genuinely oversized request
    raises instead of silently clamping cache writes."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab, 13).astype(np.int32)
    eng = ServingEngine(cfg, params, slots=1, s_max=24)
    req = Request(rid=0, prompt=prompt, max_new=10)
    eng.submit(req)
    eng.run_until_idle()
    assert len(req.out) == 10
    assert eng._prefill_shapes == {(1, 13, False)}   # exact-width fallback
    solo = ServingEngine(cfg, params, slots=1, s_max=64)
    ref = Request(rid=0, prompt=prompt, max_new=10)
    solo.submit(ref)
    solo.run_until_idle()
    assert req.out == ref.out
    # prompt + decode budget > s_max: loud failure AT SUBMIT, before the
    # request enters the queue (admission-time rejection leaked its blocks)
    eng2 = ServingEngine(cfg, params, slots=1, s_max=24)
    with pytest.raises(ValueError, match="exceeds s_max"):
        eng2.submit(Request(rid=1, prompt=rng.integers(0, cfg.vocab, 20)
                            .astype(np.int32), max_new=10))
    assert not eng2.queue


def _solo_tokens(cfg, params, prompt, max_new, s_max=64):
    eng = ServingEngine(cfg, params, slots=1, s_max=s_max,
                        prefill_chunk=None)   # whole-prompt reference
    req = Request(rid=0, prompt=prompt, max_new=max_new)
    eng.submit(req)
    eng.run_until_idle()
    return req.out


def test_continuous_matches_sync_and_solo(setup):
    """The two engine modes may only differ in WHEN, never WHAT: identical
    request streams produce identical per-request greedy tokens, each equal
    to its solo run."""
    cfg, params = setup
    rng = np.random.default_rng(17)
    spec = [(rng.integers(0, cfg.vocab, n).astype(np.int32), m)
            for n, m in ((5, 6), (11, 3), (8, 4), (14, 2), (6, 5))]
    outs = {}
    for sync in (False, True):
        eng = ServingEngine(cfg, params, slots=2, s_max=64,
                            sync_batching=sync)
        reqs = [Request(rid=i, prompt=p, max_new=m)
                for i, (p, m) in enumerate(spec)]
        for r in reqs:
            eng.submit(r)
        assert len(eng.run_until_idle()) == 5
        outs[sync] = [r.out for r in reqs]
    assert outs[False] == outs[True]
    for (p, m), got in zip(spec, outs[False]):
        assert got == _solo_tokens(cfg, params, p, m), f"len {len(p)}"


@pytest.mark.parametrize("sync", [False, True], ids=["continuous", "sync"])
def test_budget_exhausted_at_admission_completes_same_tick(setup, sync):
    """Regression (off-by-one completion tick): max_new<=1 requests exhaust
    their budget at admit time (the single token comes from the prefill
    logits) -- they must complete AT the admission tick, not ride a wasted
    decode step, and must trigger NO decode dispatch."""
    from repro.traffic import TrafficRecorder
    cfg, params = setup
    rec = TrafficRecorder()
    eng = ServingEngine(cfg, params, slots=2, s_max=64, recorder=rec,
                        sync_batching=sync)
    rng = np.random.default_rng(7)
    one = Request(rid=0, prompt=rng.integers(0, cfg.vocab, 6)
                  .astype(np.int32), max_new=1)
    zero = Request(rid=1, prompt=rng.integers(0, cfg.vocab, 6)
                   .astype(np.int32), max_new=0)
    eng.submit(one)
    eng.submit(zero)
    assert eng.step() in (False, True)
    assert one.done and zero.done
    assert len(one.out) == 1 and len(zero.out) == 0
    assert eng.decode_steps == 0           # nothing to decode
    # pinned timestamps: submitted at tick 0, admitted AND completed at 1
    for rid in (0, 1):
        ev = rec.events[rid]
        assert (ev.submit, ev.admit, ev.complete) == (0, 1, 1), rid
    # the single token matches the solo run's first token
    assert one.out == _solo_tokens(cfg, params, one.prompt, 4)[:1]
    # and the engine is genuinely idle afterwards
    assert not eng.step()


def test_bucket_width_fallback_and_oversize(setup):
    """Direct unit coverage of the _bucket_width branches: the "no bucket
    fits -> exact width" fallback and the oversized-prompt ValueError, plus
    _bucket_ladder when s_max < lo."""
    from repro.serving.engine import _bucket_ladder
    cfg, params = setup
    eng = ServingEngine(cfg, params, slots=1, s_max=24)
    assert eng.prefill_buckets == (8, 16, 24)
    # 13 + 10: the 16 bucket violates 16 + 10 <= 24 -> exact width
    assert eng._bucket_width(13, 10) == 13
    # 13 + 2: the 16 bucket fits
    assert eng._bucket_width(13, 2) == 16
    with pytest.raises(ValueError, match="exceeds s_max"):
        eng._bucket_width(20, 10)
    assert _bucket_ladder(4) == (4,)       # s_max below the smallest bucket
    assert _bucket_ladder(8) == (8,)
    assert _bucket_ladder(33) == (8, 16, 32, 33)


def test_submit_rejects_negative_ue(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, slots=1, s_max=32)
    with pytest.raises(ValueError, match="ue must be >= 0"):
        eng.submit(Request(rid=0, prompt=np.zeros(4, np.int32), ue=-3))


def test_preemption_under_small_pool(setup):
    """A pool too small for all slots at once forces youngest-preemption --
    and preemption must be INVISIBLE to outputs: every request still equals
    its solo run."""
    cfg, params = setup
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (9, 10, 12)]
    # 3 slots x (prompt + 8 new tokens) needs ~9 blocks of 4; give it 6
    eng = ServingEngine(cfg, params, slots=3, s_max=32, kv_block=4,
                        kv_blocks=7)
    reqs = [Request(rid=i, prompt=p, max_new=8)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    finished = eng.run_until_idle()
    assert len(finished) == 3
    assert eng.preemptions > 0, "pool was sized to force preemption"
    for p, r in zip(prompts, reqs):
        assert r.out == _solo_tokens(cfg, params, p, 8, s_max=32), \
            f"prompt len {len(p)}"
    # all blocks returned to the free list at drain
    assert eng.allocator.n_free == eng.allocator.capacity


def test_oversized_request_rejected_by_pool(setup):
    """A request whose worst-case KV footprint can never fit the pool fails
    loudly at admission instead of preempt-looping forever."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, slots=1, s_max=32, kv_block=4,
                        kv_blocks=3)      # capacity: 2 blocks = 8 tokens
    eng.submit(Request(rid=0, prompt=np.zeros(12, np.int32), max_new=8))
    with pytest.raises(ValueError, match="KV blocks"):
        eng.run_until_idle()


def test_continuous_beats_sync_p99_flash_crowd(setup):
    """Acceptance pin: replaying a flash-crowd burst through the continuous
    engine strictly improves p99 submit->complete latency vs the
    synchronized compat mode at equal slot count -- with identical
    per-request tokens (tick-deterministic, no wall clocks involved)."""
    from repro.traffic import TrafficRecorder
    cfg, params = setup
    rng = np.random.default_rng(31)
    # burst of 8 heterogeneous requests at t=0, stragglers later
    sched = [(0, rng.integers(0, cfg.vocab, int(rng.integers(4, 10)))
              .astype(np.int32), int(rng.integers(2, 8))) for _ in range(8)]
    sched += [(6, rng.integers(0, cfg.vocab, 5).astype(np.int32), 3),
              (8, rng.integers(0, cfg.vocab, 7).astype(np.int32), 2)]
    stats, outs = {}, {}
    for sync in (False, True):
        rec = TrafficRecorder()
        eng = ServingEngine(cfg, params, slots=2, s_max=32, recorder=rec,
                            sync_batching=sync)
        reqs = [Request(rid=i, prompt=p, max_new=m)
                for i, (_, p, m) in enumerate(sched)]
        i = 0
        for _ in range(500):
            while i < len(sched) and sched[i][0] <= eng.clock:
                eng.submit(reqs[i])
                i += 1
            if not eng.step() and i == len(sched):
                break
        assert all(r.done for r in reqs)
        stats[sync] = rec.latency_stats()
        outs[sync] = [r.out for r in reqs]
    assert outs[False] == outs[True]
    assert stats[False]["p99"] < stats[True]["p99"], stats
    assert stats[False]["p50"] <= stats[True]["p50"], stats


def test_kvpool_block_allocator():
    from repro.serving.kvpool import BlockAllocator, blocks_for
    al = BlockAllocator(5, 4)
    assert al.capacity == 4 and al.n_free == 4     # block 0 reserved
    got = al.alloc(3)
    assert got is not None and 0 not in got
    assert al.alloc(2) is None                     # only 1 left: no effect
    assert al.n_free == 1
    al.free(got)
    assert al.n_free == 4
    with pytest.raises(ValueError, match="double free"):
        al.free([got[0]])                          # already back in the list
    with pytest.raises(ValueError, match="outside pool"):
        al.free([0])                               # the dummy block
    with pytest.raises(ValueError, match="reserved dummy"):
        BlockAllocator(1, 4)
    assert blocks_for(0, 4) == 1                   # at least one block
    assert blocks_for(8, 4) == 2
    assert blocks_for(9, 4) == 3


def test_kvpool_allocator_free_of_never_handed_block():
    from repro.serving.kvpool import BlockAllocator
    al = BlockAllocator(8, 4)
    al.alloc(2)
    with pytest.raises(ValueError, match="double free"):
        al.free([6])                    # never allocated: still in the list
    al._free.remove(6)                  # vanished block: in NEITHER set
    with pytest.raises(ValueError, match="never handed out"):
        al.free([6])


def test_kvpool_allocator_free_batch_is_atomic():
    """A bad free() batch must leave the allocator untouched -- a partial
    free would strand the valid blocks in limbo (neither free nor owned)."""
    from repro.serving.kvpool import BlockAllocator
    al = BlockAllocator(8, 4)
    got = al.alloc(3)
    n_free, handed = al.n_free, al.handed_out()
    with pytest.raises(ValueError, match="double free"):
        al.free([got[0], 6])            # 6 is still free
    assert al.n_free == n_free and al.handed_out() == handed
    with pytest.raises(ValueError, match="duplicated within"):
        al.free([got[1], got[1]])
    assert al.n_free == n_free and al.handed_out() == handed
    al.free(got)                        # the clean batch still drains fully
    assert al.n_free == al.capacity and al.handed_out() == frozenset()


def test_kvpool_allocator_corrupted_free_list_rolls_back():
    from repro.serving.kvpool import BlockAllocator
    al = BlockAllocator(6, 4)
    got = al.alloc(2)
    al._free.appendleft(got[0])         # simulate external corruption
    before = list(al._free)
    with pytest.raises(ValueError, match="corrupted"):
        al.alloc(3)
    assert list(al._free) == before     # pops rolled back


def test_kvpool_rejects_cross_attention_stacks(setup):
    """Cross-attention kinds have no paged path; the engine must reject
    them up front (pointing at the sync compat mode), before touching any
    cache state."""
    import dataclasses
    cfg, params = setup
    bad = dataclasses.replace(cfg, block_pattern=("g", "d"))
    with pytest.raises(ValueError, match="sync_batching"):
        ServingEngine(bad, params, slots=1, s_max=32)


def test_partitioned_es_engine_full_offload(setup):
    """cut_unit=0 hands the full stack to the ES tier; its continuous
    engine serves tokens identical to an engine on the original params."""
    cfg, params = setup
    rng = np.random.default_rng(41)
    prompt = rng.integers(0, cfg.vocab, 9).astype(np.int32)
    eng = PartitionedLM(cfg, params, 0).es_engine(slots=1, s_max=64)
    req = Request(rid=0, prompt=prompt, max_new=4)
    eng.submit(req)
    eng.run_until_idle()
    assert req.out == _solo_tokens(cfg, params, prompt, 4)
    with pytest.raises(ValueError, match="full-offload"):
        PartitionedLM(cfg, params, 2).es_engine(slots=1, s_max=64)


# -- chunked prefill ---------------------------------------------------------

def _chunk_archs():
    import dataclasses
    return [
        ("attention", reduced(get_config("qwen3-0.6b"), n_layers=4)),
        ("hybrid-grs", dataclasses.replace(
            reduced(get_config("mamba2-1.3b")),
            name="hybrid-grs-chunk", block_pattern=("g", "r", "s"),
            n_layers=6, n_heads=4, n_kv=2, head_dim=16, d_ff=128,
            rnn_width=32)),
        ("local-lg", dataclasses.replace(
            reduced(get_config("qwen3-0.6b"), n_layers=4),
            name="local-lg-chunk", block_pattern=("l", "g"), window=12)),
    ]


CHUNK_ARCHS = _chunk_archs()


@pytest.fixture(scope="module", params=[a[0] for a in CHUNK_ARCHS])
def chunk_arch(request):
    cfg = dict(CHUNK_ARCHS)[request.param]
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_chunked_prefill_matches_whole_and_solo(chunk_arch):
    """Tentpole exactness pin: admitting prompts in fixed-size prefill
    chunks must be invisible to outputs -- chunked == whole-prompt ==
    solo greedy tokens on attention, hybrid (g/r/s), and local-window
    stacks, with ragged prompt lengths spanning chunk boundaries."""
    cfg, params = chunk_arch
    rng = np.random.default_rng(51)
    spec = [(rng.integers(0, cfg.vocab, n).astype(np.int32), m)
            for n, m in ((20, 5), (11, 4), (41, 6), (5, 3))]
    outs = {}
    for chunk in (8, None):
        eng = ServingEngine(cfg, params, slots=2, s_max=64,
                            prefill_chunk=chunk)
        reqs = [Request(rid=i, prompt=p, max_new=m)
                for i, (p, m) in enumerate(spec)]
        for r in reqs:
            eng.submit(r)
        assert len(eng.run_until_idle()) == len(reqs)
        assert eng.allocator.n_free == eng.allocator.capacity
        outs[chunk] = [r.out for r in reqs]
    assert outs[8] == outs[None]
    for (p, m), got in zip(spec, outs[8]):
        assert got == _solo_tokens(cfg, params, p, m), f"len {len(p)}"


def test_chunked_preempt_mid_prefill_resumes_exact(setup):
    """A streaming prefill evicted mid-chunk restarts from scratch on
    re-admission and still produces exact tokens, with the KV sanitizer
    cross-checking every block handoff and the final drain."""
    from repro.traffic import TrafficRecorder
    cfg, params = setup
    rng = np.random.default_rng(53)
    pa = rng.integers(0, cfg.vocab, 10).astype(np.int32)
    pb = rng.integers(0, cfg.vocab, 21).astype(np.int32)
    rec = TrafficRecorder()
    # 9 allocatable blocks of 4: A (10 prompt + 20 new) grows past its
    # initial 3 blocks while B's 21-token prompt is mid-stream at 6 -- the
    # growth preempts B, the youngest, before its prefill completes
    eng = ServingEngine(cfg, params, slots=2, s_max=64, kv_block=4,
                        kv_blocks=10, prefill_chunk=8, sanitize=True,
                        recorder=rec)
    a = Request(rid=0, prompt=pa, max_new=20)
    b = Request(rid=1, prompt=pb, max_new=4)
    eng.submit(a)
    eng.submit(b)
    eng.run_until_idle()
    assert eng.preemptions > 0, "pool was sized to evict the stream"
    ev = rec.events[1]
    # the evicted window finished no prefill: the single done tick belongs
    # to the SECOND admission, so the eviction really hit mid-prefill
    assert len(ev.admits) == 2 and len(ev.prefill_dones) == 1
    assert ev.prefill_dones[0] >= ev.admits[1]
    assert a.out == _solo_tokens(cfg, params, pa, 20)
    assert b.out == _solo_tokens(cfg, params, pb, 4)
    assert eng.allocator.n_free == eng.allocator.capacity
    eng._san.check_drain()


def test_oversized_submit_no_block_leak(setup):
    """Regression (admission-path leak): an oversized request used to pass
    submit, then raise mid-admission AFTER allocating its prompt blocks --
    leaking them and dropping the request.  submit now rejects it up front
    and traffic behind it is untouched."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, slots=1, s_max=32, kv_block=4,
                        kv_blocks=9, sanitize=True)
    with pytest.raises(ValueError, match="exceeds s_max"):
        eng.submit(Request(rid=0, prompt=np.zeros(30, np.int32), max_new=8))
    assert not eng.queue
    assert eng.allocator.n_free == eng.allocator.capacity
    ok = Request(rid=1, prompt=np.arange(6, dtype=np.int32), max_new=4)
    eng.submit(ok)
    eng.run_until_idle()
    assert len(ok.out) == 4
    assert eng.allocator.n_free == eng.allocator.capacity
    eng._san.check_drain()


def test_sync_wave_per_request_budgets(setup):
    """Regression (sync-mode false rejection): the wave used to validate
    the joint width bucket against the batch's LARGEST max_new, so a
    (101-prompt, 4-new) + (8-prompt, 28-new) pair at s_max=128 was
    rejected even though each request fits its own budget.  The wave
    builder now tracks per-request budgets and serves the pair exactly."""
    cfg, params = setup
    rng = np.random.default_rng(59)
    pa = rng.integers(0, cfg.vocab, 101).astype(np.int32)
    pb = rng.integers(0, cfg.vocab, 8).astype(np.int32)
    eng = ServingEngine(cfg, params, slots=2, s_max=128, sync_batching=True)
    a = Request(rid=0, prompt=pa, max_new=4)
    b = Request(rid=1, prompt=pb, max_new=28)
    eng.submit(a)
    eng.submit(b)
    assert len(eng.run_until_idle()) == 2
    assert a.out == _solo_tokens(cfg, params, pa, 4, s_max=128)
    assert b.out == _solo_tokens(cfg, params, pb, 28, s_max=128)


def test_run_until_idle_raises_on_max_steps(setup):
    """Regression: hitting max_steps used to return normally with requests
    still in flight -- silent truncation, callers saw a short result list.
    Now it raises, naming the stuck work."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, slots=1, s_max=64)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=np.arange(5, dtype=np.int32) + i,
                           max_new=30))
    with pytest.raises(RuntimeError, match="did not drain"):
        eng.run_until_idle(max_steps=3)


def test_chunked_rejects_bad_chunk_and_moe(setup):
    """prefill_chunk validation: out-of-range sizes raise; MoE stacks
    silently fall back to whole-prompt prefill (capacity routing couples
    tokens across a dispatch group, so chunked prefill cannot be exact)."""
    cfg, params = setup
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingEngine(cfg, params, slots=1, s_max=64, prefill_chunk=0)
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingEngine(cfg, params, slots=1, s_max=64, prefill_chunk=65)
    moe = reduced(get_config("moonshot-v1-16b-a3b"))
    assert "m" in moe.block_pattern
    moe_params = transformer.init_params(jax.random.PRNGKey(0), moe)
    eng = ServingEngine(moe, moe_params, slots=1, s_max=64, prefill_chunk=8)
    assert eng.prefill_chunk is None


# -- paged decode step -------------------------------------------------------

def _paged_archs():
    import dataclasses
    return {
        "attention": reduced(get_config("qwen3-0.6b"), n_layers=4),
        "local-lg-tail": dataclasses.replace(
            reduced(get_config("qwen3-0.6b"), n_layers=5),
            name="local-lg-tail", block_pattern=("l", "g"),
            tail_pattern=("g",), window=6),
    }


PAGED_ARCHS = _paged_archs()


def _write_then_gather_step(params, cfg, caches, tokens, table, lens):
    """Plain reference for one paged decode step: every "g" layer writes
    each row's new K/V into its pool, each row gathers its blocks back from
    a pool holding its own write, and attends; ring layers run the model's
    own layer step.  Returns (logits, caches) like the model's step."""
    from repro.kernels import ops
    from repro.models import attention as attn
    from repro.models import ffn as ffn_mod
    from repro.models.common import dtype_of, rms_norm
    rows = jnp.arange(tokens.shape[0])

    def global_layer(p, x, pool):
        bs = pool.k.shape[1]
        h = rms_norm(x, p["norm1"])
        q = attn._project_q(p["attn"], cfg, h)
        k_new, v_new = attn._project_kv(p["attn"], cfg, h)
        q = attn.rope(q, lens[:, None], cfg.rope_theta)
        k_new = attn.rope(k_new, lens[:, None], cfg.rope_theta)
        k_new = k_new[:, 0].astype(pool.k.dtype)
        v_new = v_new[:, 0].astype(pool.v.dtype)
        blk, off = table[rows, lens // bs], lens % bs

        def view(t, b, o, new, tab):
            return t.at[b, o].set(new)[tab].reshape(-1, *t.shape[2:])
        k_rows = jax.vmap(view, (None, 0, 0, 0, 0))(pool.k, blk, off, k_new,
                                                    table)
        v_rows = jax.vmap(view, (None, 0, 0, 0, 0))(pool.v, blk, off, v_new,
                                                    table)
        valid = jnp.arange(k_rows.shape[1])[None, :] <= lens[:, None]
        out = ops.decode_attention(q, k_rows, v_rows, valid_mask=valid)
        x = x + out.reshape(*x.shape[:-1], -1) @ p["attn"]["wo"]
        x = x + ffn_mod.apply_ffn(p["ffn"], cfg, rms_norm(x, p["norm2"]))
        return x, attn.KVCache(k=pool.k.at[blk, off].set(k_new),
                               v=pool.v.at[blk, off].set(v_new))

    def layer(p, kind, x, cache):
        if kind == "g":
            return global_layer(p, x, cache)
        return transformer._layer_decode_paged(p, cfg, kind, x, cache,
                                               table, lens)

    x = params["embed"][tokens][:, None, :].astype(
        dtype_of(cfg.compute_dtype))
    units = []
    for u in range(cfg.n_units):
        unit_p = jax.tree.map(lambda a: a[u], params["units"])
        unit_c = jax.tree.map(lambda a: a[u], caches["units"])
        new_c = {}
        for i, kind in enumerate(cfg.block_pattern):
            x, new_c[f"slot{i}"] = layer(unit_p[f"slot{i}"], kind, x,
                                         unit_c[f"slot{i}"])
        units.append(new_c)
    tail = []
    for tp, kind, tc in zip(params.get("tail", []), cfg.tail_pattern,
                            caches["tail"]):
        x, c = layer(tp, kind, x, tc)
        tail.append(c)
    logits = transformer._logits(params, cfg, x)[:, 0]
    return logits, {"units": jax.tree.map(lambda *a: jnp.stack(a), *units),
                    "tail": tail}


@pytest.mark.parametrize("arch", list(PAGED_ARCHS))
def test_paged_decode_matches_write_then_gather(arch):
    """One paged decode step on a filled pool with live rows (one writing
    mid-block, one at a block's first offset), an idle row and a streaming
    row (the engine zeroes its table row and holds its length at 0): logits
    and every cache leaf equal the write-then-gather reference bit for bit,
    and each pool changes only at the rows' write targets
    ``(unit, blk[i], off[i])``."""
    from repro.models.attention import KVCache
    from repro.serving import kvpool
    cfg = PAGED_ARCHS[arch]
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    bs, m, n_blocks = 4, 8, 16
    lens = np.array([13, 8, 0, 0], np.int32)
    table = np.zeros((4, m), np.int32)
    table[0, :4] = [3, 7, 1, 12]
    table[1, :3] = [5, 2, 9]
    state = kvpool.init_decode_state(cfg, params, 4, n_blocks, bs)
    leaves, treedef = jax.tree.flatten(state)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    w = np.arange(cfg.window)
    # ring slot j holds the newest position p < len with p % window == j
    ring_pos = lens[:, None] - 1 - (lens[:, None] - 1 - w) % cfg.window
    ring_pos = jnp.asarray(np.where(ring_pos >= 0, ring_pos, -1), jnp.int32)
    state = jax.tree.unflatten(treedef, [
        jax.random.normal(k, a.shape, a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating)
        else jnp.broadcast_to(ring_pos, a.shape)
        for k, a in zip(keys, leaves)])
    tokens = jnp.asarray([17, 4, 0, 99], jnp.int32)
    table, lens = jnp.asarray(table), jnp.asarray(lens)

    want, want_state = jax.jit(
        lambda p, st: _write_then_gather_step(p, cfg, st, tokens, table,
                                              lens))(params, state)
    got, got_state = jax.jit(
        lambda p, st: transformer.decode_step_paged(p, cfg, st, tokens,
                                                    table, lens))(params,
                                                                  state)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert jax.tree.structure(got_state) == jax.tree.structure(state)

    # (blk, off): row 0 at table[0, 13 // 4] offset 1, row 1 at
    # table[1, 8 // 4] offset 0, the idle and streaming rows at dummy (0, 0)
    targets = {(12, 1), (9, 0), (0, 0)}
    n_pools = 0
    for before, after, ref in zip(
            *(jax.tree.leaves(t, is_leaf=kvpool._cache_leaf)
              for t in (state, got_state, want_state))):
        for a, b, r in zip(before, after, ref):
            a, b, r = np.asarray(a), np.asarray(b), np.asarray(r)
            if not isinstance(before, KVCache):
                np.testing.assert_array_equal(b, r)
                continue
            # the idle and streaming rows both write dummy block 0 at
            # offset 0, and which duplicate write lands is unspecified
            np.testing.assert_array_equal(b[..., 1:, :, :, :],
                                          r[..., 1:, :, :, :])
            changed = (a != b).any(axis=(-2, -1)).reshape(-1, n_blocks, bs)
            for u in range(changed.shape[0]):
                assert set(zip(*np.nonzero(changed[u]))) == targets
            n_pools += 1
    assert n_pools == 2 * (cfg.block_pattern.count("g")
                           + cfg.tail_pattern.count("g"))


@pytest.mark.slow
def test_engine_greedy_matches_manual_decode(setup):
    """Engine tokens == hand-rolled prefill+argmax decode for one request."""
    cfg, params = setup
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab, 8).astype(np.int32)
    eng = ServingEngine(cfg, params, slots=1, s_max=64)
    req = Request(rid=0, prompt=prompt, max_new=4)
    eng.submit(req)
    while eng.step():
        pass
    logits, cache = transformer.prefill(params, cfg,
                                        {"tokens": jnp.asarray(prompt)[None]},
                                        s_max=64)
    toks = []
    nxt = int(jnp.argmax(logits, -1)[0])
    toks.append(nxt)
    for _ in range(3):
        logits, cache = transformer.decode_step(params, cfg, cache,
                                                jnp.asarray([nxt], jnp.int32))
        nxt = int(jnp.argmax(logits, -1)[0])
        toks.append(nxt)
    assert req.out == toks
