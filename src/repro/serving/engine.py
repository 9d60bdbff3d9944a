"""Serving engine for the edge tier: continuous batching over a paged KV
cache, with the old synchronized-batch engine kept as a compat mode.

The ES side of the paper's system: requests (prompts) arrive continuously
and the paper's E2E-delay objective is a *serial queuing model* -- so the
default engine admits **per tick**: a queued request prefills into any free
decode slot while the other slots keep decoding, and its KV lands in
fixed-size blocks handed out by ``serving.kvpool.BlockAllocator`` (per-slot
block tables, free-list reuse).  Each slot carries its own cache length
(``seq_lens``) -- there is no shared write frontier -- and one jitted
``transformer.decode_step_paged`` call advances every active slot, gathering
each row's blocks through ``kernels/decode_attention`` with a per-row ragged
``valid_mask``.  When a slot outgrows its blocks and the pool is exhausted,
the **youngest** admitted request is preempted back to the front of the
queue (its blocks freed, its output discarded); greedy decode is
deterministic, so re-admission reproduces the same tokens and preemption is
invisible to parity.  ``sync_batching=True`` restores the old engine --
admission waits for ALL slots to drain and prompts share one batched
prefill -- kept for A/B latency baselines and parity tests.

Mixed-length prompts are EXACT in both modes.  Continuous mode prefills
each request SOLO (batch=1 at its bucket width, left-padded); the ragged
machinery (attention pad mask + shifted RoPE + reset-aware recurrent scans)
makes the bucket slack semantics-free, and ``kvpool.commit_prefill`` strips
the pad when writing the KV blocks, so the paged cache holds only real
tokens and decode needs no pad vector.  Sync mode batches the admitted
prompts into one left-padded prefill whose pad vector rides in the cache.
Either way a request's greedy tokens equal its solo run on every stack kind
(tests/test_serving.py, tests/test_ragged.py, tests/test_model_axis.py).

Prefill shapes are BUCKETED: prompts pad up to the next power-of-two width
(``prefill_buckets``), so the jitted prefill compiles once per bucket --
steady-state serving triggers no recompiles regardless of prompt-length mix
(pinned by tests/test_serving.py::test_prefill_bucketing_avoids_recompiles).
Bucket selection never eats the decode budget (``bucket + max_new - 1 <=
s_max``; see ``_bucket_width``).  Pad-free prompts skip the mask entirely
and keep the dense/Pallas kernel prefill path.

Long prompts prefill in CHUNKS (``prefill_chunk``; "auto" picks 32 when
``s_max`` allows): instead of stalling every decoding slot for one full
bucket-width prefill, admission streams the head request through
``transformer.prefill_chunk`` one chunk per tick, committing each chunk
incrementally into the slot's blocks (``kvpool.commit_chunk``) while the
other slots keep decoding -- decode-tick latency stays bounded by one chunk
regardless of prompt length.  At most one request streams at a time
(admission order is still strict FIFO), a mid-prefill slot can be preempted
like any other (the stream restarts from chunk 1 on re-admission), and the
final chunk's logits equal the whole-prompt prefill logits exactly, so
chunked == whole-prompt == solo greedy tokens on every servable stack kind
(tests/test_serving.py, tests/test_model_axis.py).

A traffic recorder (duck-typed; see ``repro.traffic.recorder``) can observe
the request lifecycle: the engine reports submit/admit/complete in units of
its step clock (one ``step()`` call == one tick), which
``TrafficRecorder.to_trace`` bins into a replayable arrival trace and
``TrafficRecorder.latency_stats`` turns into p50/p99 E2E latency.  A
request whose budget is exhausted at admission (``max_new <= 1``: one token
comes straight from the prefill logits, zero means none) completes AT its
admission tick in both modes -- it neither occupies a slot nor triggers a
decode dispatch.

A telemetry object (``telemetry=``, see :class:`repro.obs.Telemetry`) adds
metrics + spans at every lifecycle edge (submit/admit/prefill/decode-tick/
block-grow/preempt/complete) plus per-tick queue/KV-pool gauges.  Disabled
(the default) it costs one ``self.obs is None`` check per site; enabled it
reads only host state the engine already materialized -- never an extra
device->host sync (docs/observability.md).

See docs/serving.md for the full contract.
"""
from __future__ import annotations

import dataclasses
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from ..models import transformer
from . import kvpool


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (S,) int32
    max_new: int = 16
    ue: int | None = None       # originating UE (traffic-trace binning);
                                # None -> recorder falls back to rid % n_ue
    out: list = dataclasses.field(default_factory=list)
    done: bool = False


def _bucket_ladder(s_max: int, lo: int = 8) -> tuple[int, ...]:
    """Power-of-two prompt-width buckets up to s_max (always includes s_max)."""
    buckets = []
    w = lo
    while w < s_max:
        buckets.append(w)
        w *= 2
    buckets.append(s_max)
    return tuple(buckets)


class ServingEngine:
    """``mesh=`` (any mesh with a ``"model"`` axis, e.g.
    ``launch.mesh.make_cells_mesh(model=M)``) turns on tensor parallelism:
    params are placed with the ``launch.sharding`` policy and the jitted
    prefill/decode trace under the mesh's activation-sharding context, so
    GSPMD splits attention heads / FFN hidden / vocab M ways.  The KV block
    pool shards its kv-head dim the same way while the block tables stay
    replicated (every shard indexes the same table, gathers its own head
    shard).  Model-sharded serving produces the same greedy tokens as the
    unsharded engine (tests/test_model_axis.py pins it, ragged batches
    included).

    ``sync_batching=False`` (default): continuous batching -- per-tick
    admission into free slots, paged KV (``kv_block`` tokens per block,
    ``kv_blocks`` pool blocks; default sized so every slot can reach
    ``s_max``), youngest-request preemption when the pool runs dry.
    ``sync_batching=True``: the synchronized-batch compat engine.

    ``sanitize=True`` (debug; ``python -m repro.analysis --sanitize``)
    turns on the memory-safety layer: a :class:`analysis.sanitize.
    KVSanitizer` shadows every block handoff (double-free, free-of-
    unowned, cross-slot aliasing, dummy-block writes, leak-at-drain) and
    the jitted prefill/commit/decode programs run under ``checkify``
    NaN/index-OOB guards.  Off (the default) the only cost is one
    ``self._san is None`` check per lifecycle edge -- the same zero-cost
    discipline as telemetry (docs/serving.md, "Sanitizer runtime").
    """

    def __init__(self, cfg, params, *, slots: int = 4, s_max: int = 128,
                 prefill_buckets=None, recorder=None, mesh=None,
                 sync_batching: bool = False, kv_block: int = 16,
                 kv_blocks: int | None = None, telemetry=None,
                 sanitize: bool = False, prefill_chunk="auto"):
        self.mesh = mesh
        if mesh is not None:
            from ..launch.sharding import place_params
            params = place_params(mesh, cfg, params)
        self.cfg, self.params = cfg, params
        self.slots = slots
        self.s_max = s_max
        self.sync_batching = sync_batching
        self.prefill_buckets = tuple(sorted(
            _bucket_ladder(s_max) if prefill_buckets is None
            else prefill_buckets))
        if not self.prefill_buckets or self.prefill_buckets[-1] > s_max:
            raise ValueError(f"prefill buckets {self.prefill_buckets} must be "
                             f"non-empty and <= s_max={s_max}")
        # chunked prefill (continuous mode): prompts LONGER than this stream
        # through admission one chunk per tick instead of whole-prompt
        # prefilling in a single tick ("auto": 32 when s_max allows, else
        # off; None disables).  Sync mode ignores it (the compat engine IS
        # the head-of-line baseline).
        if prefill_chunk == "auto":
            prefill_chunk = 32 if s_max > 32 else None
        if prefill_chunk is not None and not 0 < int(prefill_chunk) <= s_max:
            raise ValueError(f"prefill_chunk={prefill_chunk} must be in "
                             f"[1, s_max={s_max}], None, or 'auto'")
        if "m" in (*cfg.block_pattern, *cfg.tail_pattern):
            # capacity-based MoE routing couples every token in a dispatch
            # group, so chunk-local prefill cannot match the whole-prompt
            # dispatch exactly -- MoE stacks keep whole-prompt prefill
            # (see transformer._layer_chunk)
            prefill_chunk = None
        self.prefill_chunk = None if prefill_chunk is None \
            else int(prefill_chunk)
        self.recorder = recorder
        self.clock = 0                       # engine ticks (step() calls)
        self.queue: deque[Request] = deque()
        self.active: list[Request | None] = [None] * slots
        self._completed: list[Request] = []
        self.remaining = np.zeros(slots, np.int32)
        self.decode_steps = 0                # jitted decode dispatches
        self.preemptions = 0                 # continuous mode only
        self.cache = None                    # sync mode's shared cache
        # (batch, width, ragged?) triples traced so far == jit compilations
        self._prefill_shapes: set[tuple] = set()
        # telemetry (repro.obs.Telemetry): every instrumentation site below
        # is one `self.obs is not None` check when disabled, and reads only
        # already-materialized host state when enabled (docs/observability.md)
        self.obs = None
        if telemetry is not None:
            from ..obs.enginehooks import EngineHooks
            self.obs = EngineHooks(telemetry, self)
        from ..launch.sharding import shard_ctx

        # sanitizer runtime (analysis.sanitize): OFF by default, costing one
        # `self._san is None` check per lifecycle edge -- same zero-cost
        # discipline as telemetry.  On, every jitted program gains checkify
        # NaN/index-OOB guards and the KV pool gets shadow ownership checks.
        self._san = None
        self.sanitize = sanitize
        if sanitize:
            from ..analysis.sanitize import checkify_wrap

        def _jit(fn, donate=None):
            """jit one engine program; in sanitize mode wrap it with
            checkify guards instead (no donation there: the checkified
            signature threads an error value, and sanitize is a debug
            mode)."""
            if sanitize:
                return shard_ctx(mesh, checkify_wrap(fn))
            jitted = jax.jit(fn) if donate is None else \
                jax.jit(fn, donate_argnums=donate)
            return shard_ctx(mesh, jitted)

        # Greedy argmax happens INSIDE the jitted programs: only the (B,)
        # int32 next-token ids ever cross to the host, never the (B, vocab)
        # logits, and the argmax fuses into the decode dispatch instead of
        # running as a separate eager op every tick (reprolint: host-sync).
        def greedy(out):
            logits, cache = out
            return jnp.argmax(logits, -1).astype(jnp.int32), cache

        # The weights enter every model program as its first ARGUMENT:
        # weights a jitted function closes over are baked into the program
        # as constants, one device copy per compiled program.
        self._prefill = _jit(
            lambda params, batch, pad: greedy(transformer.prefill(
                params, cfg, batch, s_max=s_max, pad=pad)))
        if sync_batching:
            self._decode = _jit(
                lambda params, cache, toks: greedy(transformer.decode_step(
                    params, cfg, cache, toks)))
            return

        # -- continuous-batching state ------------------------------------
        self.kv_block = kv_block
        self.table_width = -(-s_max // kv_block)            # blocks per slot
        if kv_blocks is None:
            # every slot can page out to s_max, plus the reserved dummy
            kv_blocks = slots * self.table_width + 1
        self.allocator = kvpool.BlockAllocator(kv_blocks, kv_block)
        state = kvpool.init_decode_state(cfg, params, slots, kv_blocks,
                                         kv_block)
        if mesh is not None:
            state = kvpool.place_decode_state(mesh, state)
        self._pool_state = state
        self.block_tables = np.zeros((slots, self.table_width), np.int32)
        self.seq_lens = np.zeros(slots, np.int32)
        self.last_tokens = np.zeros(slots, np.int32)
        self.owned: list[list[int]] = [[] for _ in range(slots)]
        self._admit_seq = np.full(slots, -1, np.int64)      # admission order
        self._admit_counter = 0
        # The per-tick state updates DONATE their input pool:
        # the engine always rebinds self._pool_state to the result, and
        # without donation every tick/commit briefly holds TWO full KV
        # pools live -- the exact peak-memory hazard
        # `analysis.shardcheck`'s donation probe gates.
        self._commit = _jit(
            lambda state, solo, pad, slot, ids: kvpool.commit_prefill(
                state, solo, pad, slot, ids, block_size=kv_block),
            donate=0)
        self._decode_paged = _jit(
            lambda params, state, toks, table, lens: greedy(
                transformer.decode_step_paged(params, cfg, state, toks,
                                              table, lens)),
            donate=1)
        # -- chunked-prefill stream state: at most ONE request mid-prefill
        # (see _start_stream / _advance_stream).  Both chunk programs take
        # traced scalars and a full table-width id row, so each compiles
        # exactly ONCE regardless of prompt length or chunk index
        # (analysis.retrace pins it).
        self._stream_req: Request | None = None
        self._stream_slot = -1
        self._stream_cache = None            # device {units, tail} scratch
        self._stream_done = 0                # prompt tokens advanced so far
        self._stream_ids = None              # device (table_width,) block row
        if self.prefill_chunk is not None:
            self._chunk_step = _jit(
                lambda params, cache, toks, start, n_valid: greedy(
                    transformer.prefill_chunk(params, cfg, cache, toks,
                                              start, n_valid)),
                donate=1)
            self._commit_chunk = _jit(
                lambda state, solo, start, n_new, slot, ids:
                    kvpool.commit_chunk(state, solo, start, n_new, slot,
                                        ids, block_size=kv_block),
                donate=0)
        if sanitize:
            from ..analysis.sanitize import KVSanitizer
            self._san = KVSanitizer(self)

    @property
    def prefill_compiles(self) -> int:
        """Distinct prefill signatures traced so far (== jit compilations):
        one per (batch, bucket width, ragged-or-not) combination."""
        return len(self._prefill_shapes)

    def submit(self, req: Request):
        if req.ue is not None and req.ue < 0:
            raise ValueError(f"request {req.rid}: ue must be >= 0, got "
                             f"{req.ue} (negative UEs would fold into valid "
                             f"trace columns)")
        # Budget check up front: the prompt plus max_new - 1 decode writes
        # (the first token comes from the prefill logits) must fit s_max.
        # Rejecting HERE -- not mid-admission, after blocks were allocated
        # and the request popped -- is what keeps an oversized request from
        # leaking KV blocks and vanishing from the queue.
        n = len(req.prompt)
        if n + max(req.max_new, 1) - 1 > self.s_max:
            raise ValueError(
                f"request {req.rid}: prompt width {n} + decode budget "
                f"{req.max_new} exceeds s_max={self.s_max}")
        self.queue.append(req)
        if self.recorder is not None:
            self.recorder.record_submit(req.rid, self.clock, ue=req.ue)
        if self.obs is not None:
            self.obs.on_submit(req, self.clock)

    def _bucket_width(self, width: int, max_new: int) -> int:
        """Smallest bucket >= width that still leaves ``max_new`` tokens.

        Bucket slack must never eat the decode budget: prefill starts the
        cache position at the bucket width and the first decode token comes
        from the prefill logits, so ``bucket + max_new - 1`` KV slots are
        written overall and must fit in ``s_max`` (decode's
        dynamic_update_slice would silently clamp past the end otherwise).
        When every bucket that fits is narrower than needed, fall back to
        the exact width (one extra compiled shape beats corrupt output);
        if even that cannot fit, the request is genuinely oversized.
        """
        limit = self.s_max - max_new + 1
        if width > limit:
            raise ValueError(
                f"prompt width {width} + decode budget {max_new} exceeds "
                f"s_max={self.s_max}")
        for b in self.prefill_buckets:
            if b >= width and b <= limit:
                return b
        return width

    # -- shared lifecycle helpers -------------------------------------------

    def _complete(self, req: Request):
        req.done = True
        self._completed.append(req)
        if self.recorder is not None:
            self.recorder.record_complete(req.rid, self.clock)
        if self.obs is not None:
            self.obs.on_complete(req, self.clock)

    def _complete_at_admission(self, req: Request):
        """Budget exhausted at admit time (max_new <= 1): the single token
        (if any) came from the prefill logits, so the request completes AT
        its admission tick -- no slot, no decode dispatch."""
        if self.recorder is not None:
            self.recorder.record_admit(req.rid, self.clock)
        if self.obs is not None:
            self.obs.on_admit(req, self.clock)
        self._record_prefill_done(req.rid)
        self._complete(req)

    def _solo_prefill(self, req: Request):
        """Batch-1 bucketed prefill.  Returns (next-token int, cache, pad)."""
        n = len(req.prompt)
        width = self._bucket_width(n, max(req.max_new, 1))
        toks = np.pad(np.asarray(req.prompt), (width - n, 0))[None]
        pad = width - n
        pad_arg = jnp.asarray([pad], jnp.int32) if pad else None
        self._prefill_shapes.add((1, width, pad_arg is not None))
        t0 = self.obs.now() if self.obs is not None else 0.0
        tok, cache = self._prefill(
            self.params, {"tokens": jnp.asarray(toks, jnp.int32)}, pad_arg)
        # admission's one sanctioned sync: a single int32 per admitted request
        nxt = int(np.asarray(tok)[0])    # reprolint: ignore[host-sync]
        if self.obs is not None:         # host state only: span + compile gauge
            self.obs.on_prefill(self, t0, batch=1, width=width)
        return nxt, cache, pad

    # -- continuous batching ------------------------------------------------

    def _record_prefill_done(self, rid: int):
        """Duck-typed like the other record_* hooks; older recorders
        without the method (or recorder=None) are skipped."""
        rec = getattr(self.recorder, "record_prefill_done", None)
        if rec is not None:
            rec(rid, self.clock)
        if self.obs is not None:
            self.obs.on_prefill_done(rid, self.clock)

    def _admit_continuous(self):
        """Admit from the queue head into free slots, one request per solo
        prefill, until slots or KV blocks run out (FIFO: a request that
        cannot be placed blocks the ones behind it).  While a chunked
        prefill is streaming, THIS tick's admission work is the stream's
        next chunk and nothing else -- strict FIFO, bounded tick cost
        (see _advance_stream)."""
        if self._stream_req is not None:
            self._advance_stream()
            return
        while self.queue:
            req = self.queue[0]
            n = len(req.prompt)
            if req.max_new <= 0:
                self.queue.popleft()
                self._complete_at_admission(req)
                continue
            if req.max_new == 1:
                self.queue.popleft()
                nxt, _, _ = self._solo_prefill(req)
                req.out.append(nxt)
                self._complete_at_admission(req)
                continue
            free = [i for i, r in enumerate(self.active) if r is None]
            if not free:
                return
            # worst case the request holds len + max_new - 1 KV tokens; a
            # request that could never fit the pool must fail loudly, not
            # preempt-loop forever
            total = kvpool.blocks_for(n + req.max_new - 1, self.kv_block)
            if total > self.allocator.capacity:
                raise ValueError(
                    f"request {req.rid} needs {total} KV blocks "
                    f"({n} prompt + {req.max_new} decode tokens) but the "
                    f"pool holds {self.allocator.capacity}")
            blocks = self.allocator.alloc(kvpool.blocks_for(n, self.kv_block))
            if blocks is None:
                return                       # pool full: wait for completions
            self.queue.popleft()
            slot = free[0]
            try:
                if self.prefill_chunk is not None and n > self.prefill_chunk:
                    self._start_stream(req, slot, blocks)
                    return               # one chunk of prefill work per tick
                nxt, cache, pad = self._solo_prefill(req)
            except Exception:
                # belt: submit() validates the budget up front, but any
                # raise past alloc/popleft must neither leak the blocks nor
                # silently drop the request
                self.allocator.free(blocks)
                self.queue.appendleft(req)
                raise
            width = len(req.prompt) + pad
            # ids length is the bucket width in blocks: one compile per
            # bucket, exactly like prefill itself
            ids = np.zeros(-(-width // self.kv_block), np.int32)
            ids[:len(blocks)] = blocks       # slack blocks -> dummy block 0
            solo = {"units": cache["units"], "tail": cache["tail"]}
            self._pool_state = self._commit(   # reprolint: ignore[recompile-hazard]
                self._pool_state, solo, jnp.int32(pad), jnp.int32(slot),
                jnp.asarray(ids))
            req.out.append(nxt)
            self.active[slot] = req
            self.owned[slot] = list(blocks)
            self.block_tables[slot, :] = 0
            self.block_tables[slot, :len(blocks)] = blocks
            self.seq_lens[slot] = n
            self.last_tokens[slot] = nxt
            self.remaining[slot] = req.max_new - 1
            self._admit_seq[slot] = self._admit_counter
            self._admit_counter += 1
            if self._san is not None:
                self._san.on_alloc(slot, blocks)
            if self.recorder is not None:
                self.recorder.record_admit(req.rid, self.clock)
            if self.obs is not None:
                self.obs.on_admit(req, self.clock)
            self._record_prefill_done(req.rid)

    def _start_stream(self, req: Request, slot: int, blocks):
        """Begin a chunked prefill: run chunk 1 (a plain batch-1 prefill at
        the chunk width -- its KV scratch is already ``s_max``-sized, so it
        doubles as the stream's resumable cache) and commit it into the
        slot's blocks.  The slot is admitted -- it owns its blocks and holds
        the request -- but stays OUT of the decode dispatch (``seq_lens`` 0
        plus a dummy-masked table row) until the final chunk lands; see
        :meth:`_advance_stream`."""
        c = self.prefill_chunk
        toks = np.asarray(req.prompt, np.int32)[None, :c]
        self._prefill_shapes.add((1, c, False))
        t0 = self.obs.now() if self.obs is not None else 0.0
        _, cache = self._prefill(self.params, {"tokens": jnp.asarray(toks)},
                                 None)
        cache = {"units": cache["units"], "tail": cache["tail"]}
        if self.obs is not None:
            self.obs.on_prefill(self, t0, batch=1, width=c, chunked=True)
        # the FULL table-width id row (slack -> dummy block 0): one
        # compiled chunk-commit signature for every request shape
        ids = np.zeros(self.table_width, np.int32)
        ids[:len(blocks)] = blocks
        self._stream_ids = jnp.asarray(ids)
        self._pool_state = self._commit_chunk(
            self._pool_state, cache, jnp.int32(0), jnp.int32(c),
            jnp.int32(slot), self._stream_ids)
        self._stream_req, self._stream_slot = req, slot
        self._stream_cache, self._stream_done = cache, c
        self.active[slot] = req
        self.owned[slot] = list(blocks)
        self.block_tables[slot, :] = 0
        self.block_tables[slot, :len(blocks)] = blocks
        self.seq_lens[slot] = 0
        self.last_tokens[slot] = 0
        self.remaining[slot] = req.max_new - 1
        self._admit_seq[slot] = self._admit_counter
        self._admit_counter += 1
        if self._san is not None:
            self._san.on_alloc(slot, blocks)
        if self.recorder is not None:
            self.recorder.record_admit(req.rid, self.clock)
        if self.obs is not None:
            self.obs.on_admit(req, self.clock)

    def _advance_stream(self):
        """One chunk of the streaming request's prefill -- one per tick, so
        every other slot's decode latency stays bounded by a chunk, never a
        whole prompt.  Each chunk commits incrementally into the slot's
        blocks (``kvpool.commit_chunk``); the final chunk's logits ARE the
        whole-prompt prefill logits, so its argmax is the request's first
        token and the slot joins THIS tick's decode dispatch, exactly like
        a whole-prefill admission."""
        req, slot, c = self._stream_req, self._stream_slot, self.prefill_chunk
        n = len(req.prompt)
        start = self._stream_done
        n_valid = min(c, n - start)
        chunk = np.zeros((1, c), np.int32)
        chunk[0, :n_valid] = req.prompt[start:start + n_valid]
        t0 = self.obs.now() if self.obs is not None else 0.0
        tok, cache = self._chunk_step(self.params, self._stream_cache,
                                      jnp.asarray(chunk), jnp.int32(start),
                                      jnp.int32(n_valid))
        self._pool_state = self._commit_chunk(
            self._pool_state, cache, jnp.int32(start), jnp.int32(n_valid),
            jnp.int32(slot), self._stream_ids)
        if self.obs is not None:
            self.obs.on_prefill(self, t0, batch=1, width=c, chunked=True)
        self._stream_cache = cache
        self._stream_done = start + n_valid
        if self._stream_done < n:
            return
        # the stream's one sanctioned sync: a single int32 at the final chunk
        nxt = int(np.asarray(tok)[0])    # reprolint: ignore[host-sync]
        req.out.append(nxt)
        self.seq_lens[slot] = n
        self.last_tokens[slot] = nxt
        self._end_stream()
        self._record_prefill_done(req.rid)

    def _end_stream(self):
        self._stream_req, self._stream_slot = None, -1
        self._stream_cache, self._stream_done = None, 0
        self._stream_ids = None

    def _release_slot(self, slot: int):
        if self._san is not None:
            self._san.on_free(slot, self.owned[slot])
        self.allocator.free(self.owned[slot])
        self.owned[slot] = []
        self.block_tables[slot, :] = 0
        self.seq_lens[slot] = 0
        self.last_tokens[slot] = 0
        self.remaining[slot] = 0
        self._admit_seq[slot] = -1
        self.active[slot] = None

    def _preempt(self, slot: int):
        """Evict the request in ``slot`` back to the FRONT of the queue,
        discarding its output and KV (recompute-style preemption: greedy
        decode is deterministic, so re-admission regenerates the same
        tokens)."""
        req = self.active[slot]
        if slot == self._stream_slot:
            # mid-prefill evict: drop the chunk cursor + scratch; the
            # stream restarts from chunk 1 on re-admission (recompute
            # preemption, same as a decoding slot)
            self._end_stream()
        req.out.clear()
        self._release_slot(slot)
        self.queue.appendleft(req)
        self.preemptions += 1
        # duck-typed like the other record_* hooks; older recorders without
        # the method (or recorder=None) are skipped
        rec_preempt = getattr(self.recorder, "record_preempt", None)
        if rec_preempt is not None:
            rec_preempt(req.rid, self.clock)
        if self.obs is not None:
            self.obs.on_preempt(req, self.clock)

    def _grow_blocks(self):
        """Before a decode tick, make sure every active slot owns the block
        its next KV write lands in.  Oldest slots grow first; when the pool
        is dry, the YOUNGEST active request is preempted until the
        allocation succeeds (head-of-line requests always make progress --
        the admission fit check guarantees a lone request can reach its
        full budget)."""
        order = sorted((i for i, r in enumerate(self.active) if r is not None),
                       key=lambda i: self._admit_seq[i])
        for slot in order:
            if self.active[slot] is None:    # preempted below, mid-loop
                continue
            bidx = int(self.seq_lens[slot]) // self.kv_block
            if bidx < len(self.owned[slot]):
                continue
            while True:
                got = self.allocator.alloc(1)
                if got is not None:
                    self.owned[slot].append(got[0])
                    self.block_tables[slot, bidx] = got[0]
                    if self._san is not None:
                        self._san.on_alloc(slot, got)
                    if self.obs is not None:
                        self.obs.on_block_grow()
                    break
                victim = max(
                    (j for j, r in enumerate(self.active) if r is not None),
                    key=lambda j: self._admit_seq[j])
                self._preempt(victim)
                if victim == slot:
                    break                    # this slot went back to queue

    def _step_continuous(self) -> bool:
        self._admit_continuous()
        self._grow_blocks()
        live = [i for i, r in enumerate(self.active)
                if r is not None and i != self._stream_slot]
        # per-tick telemetry is SAMPLED by clock stride: even an
        # early-returning method call costs us-scale on the cold post-
        # dispatch path, so the stride check is inline int arithmetic and
        # non-sampled ticks skip the calls entirely (sample_every=1 for
        # exact per-tick reads)
        obs = self.obs
        sampled = obs is not None and self.clock % obs.sample_every == 0
        if sampled:                      # host-state gauges (queue, KV pool)
            obs.sample(self)
        if not live:
            return self._stream_req is not None or bool(self.queue)
        t0 = obs.now() if sampled else 0.0
        table = self.block_tables
        if self._stream_req is not None:
            # a mid-prefill slot rides the dispatch as an idle row: the
            # zeroed table row routes its garbage "g" writes to dummy block
            # 0, and the garbage stepping of its ring/recurrent pool rows is
            # erased by the next chunk's wholesale commit BEFORE the slot's
            # first real decode (see kvpool.commit_chunk)
            table = table.copy()
            table[self._stream_slot] = 0
        toks, self._pool_state = self._decode_paged(
            self.params, self._pool_state, jnp.asarray(self.last_tokens),
            jnp.asarray(table), jnp.asarray(self.seq_lens))
        self.decode_steps += 1
        # the tick's one sanctioned sync: (slots,) int32 token ids
        nxt = np.asarray(toks)           # reprolint: ignore[host-sync]
        if sampled:
            obs.on_decode_tick(self, t0, len(live))
        for i in live:
            req = self.active[i]
            self.seq_lens[i] += 1
            self.last_tokens[i] = nxt[i]
            req.out.append(int(nxt[i]))
            self.remaining[i] -= 1
            if self.remaining[i] <= 0:
                self._release_slot(i)
                self._complete(req)
        if self._san is not None:
            self._san.check_tick()
        return True

    # -- synchronized-batch compat mode -------------------------------------

    def _admit_sync(self):
        """Compat-mode admission: wait until ALL slots are free, then prefill
        the next wave as one left-padded batch (pad counts ride in the cache
        so decode keeps masking them).  This is the architecture whose
        head-of-line blocking the continuous engine removes -- kept only for
        A/B baselines and parity tests (``sync_batching=True``)."""
        if any(r is not None for r in self.active) or not self.queue:
            return
        # Greedy wave build under PER-REQUEST budgets: the shared prefill
        # width w must cover every prompt AND leave every member its decode
        # room (w + max_new - 1 <= s_max per request -- row r decodes
        # max_new - 1 KV writes past the shared width).  Folding the wave's
        # budgets into one max(prompt) vs max(max_new) pair falsely
        # rejected individually-valid mixes (a long prompt with a short
        # budget + a short prompt with a long budget); instead a request
        # joins the wave only while a feasible width exists, and otherwise
        # starts the next wave.
        batch = []
        need, cap = 0, self.s_max + 1
        while self.queue and len(batch) < self.slots:
            r = self.queue[0]
            r_need = max(need, len(r.prompt))
            r_cap = min(cap, self.s_max + 1 - max(r.max_new, 1))
            if batch and r_need > r_cap:
                break                        # r starts the next wave
            batch.append(self.queue.popleft())
            need, cap = r_need, r_cap
        while len(batch) < self.slots:       # pad with a copy (masked out)
            batch.append(Request(rid=-1, prompt=batch[0].prompt, max_new=0))
        width = self._bucket_width(need, self.s_max + 1 - cap)
        toks = np.stack([np.pad(r.prompt, (width - len(r.prompt), 0))
                         for r in batch])    # left-pad to the bucket width
        pad = np.asarray([width - len(r.prompt) for r in batch], np.int32)
        # A pad-free batch (all prompts exactly bucket-width) skips the mask
        # entirely: prefill keeps its dense/Pallas kernel path and the cache
        # carries no "pad" entry (the decode fast path).
        pad_arg = jnp.asarray(pad) if pad.any() else None
        self._prefill_shapes.add(toks.shape + (pad_arg is not None,))
        t0 = self.obs.now() if self.obs is not None else 0.0
        tok_ids, cache = self._prefill(
            self.params, {"tokens": jnp.asarray(toks, jnp.int32)}, pad_arg)
        self.cache = cache
        # admission's one sanctioned sync (batch x int32)
        nxt = np.asarray(tok_ids)        # reprolint: ignore[host-sync]
        if self.obs is not None:
            self.obs.on_prefill(self, t0, batch=len(batch), width=width)
        for i, r in enumerate(batch):
            self.active[i] = r if r.rid >= 0 else None
            self.remaining[i] = r.max_new
            if r.rid < 0:
                continue
            if self.recorder is not None:
                self.recorder.record_admit(r.rid, self.clock)
            if self.obs is not None:
                self.obs.on_admit(r, self.clock)
            self._record_prefill_done(r.rid)
            if r.max_new > 0:
                r.out.append(int(nxt[i]))
                self.remaining[i] -= 1
            if self.remaining[i] <= 0:
                # budget exhausted by the prefill logits alone: complete at
                # the admission tick, don't ride through a decode step
                self.active[i] = None
                self._complete(r)
        self._last = nxt

    def _step_sync(self) -> bool:
        self._admit_sync()
        # sampled per-tick telemetry; see _step_continuous
        obs = self.obs
        sampled = obs is not None and self.clock % obs.sample_every == 0
        if sampled:                      # host-state gauges (queue, slots)
            obs.sample(self)
        if self.cache is None or all(r is None for r in self.active):
            self.cache = None
            return bool(self.queue)
        live = sum(1 for r in self.active if r is not None)
        t0 = obs.now() if sampled else 0.0
        toks, self.cache = self._decode(self.params, self.cache,
                                        jnp.asarray(self._last, jnp.int32))
        self.decode_steps += 1
        # the tick's one sanctioned sync: (slots,) int32 token ids
        nxt = np.asarray(toks)           # reprolint: ignore[host-sync]
        if sampled:
            obs.on_decode_tick(self, t0, live)
        self._last = nxt
        alive = False
        for i, r in enumerate(self.active):
            if r is None:
                continue
            if self.remaining[i] > 0:
                r.out.append(int(nxt[i]))
                self.remaining[i] -= 1
            if self.remaining[i] <= 0:
                self.active[i] = None
                self._complete(r)
            else:
                alive = True
        if not alive and not self.queue:
            self.cache = None
        return True

    # -- driver interface ----------------------------------------------------

    def step(self) -> bool:
        """One engine iteration (one clock tick).  Returns False when idle.

        The clock advances on every call -- idle ticks included -- so a
        driver that interleaves ``submit`` with ``step`` produces lifecycle
        timestamps on one monotonic time base for the traffic recorder.
        """
        self.clock += 1
        if self.sync_batching:
            return self._step_sync()
        alive = self._step_continuous()
        if self._san is not None and not alive:
            self._san.check_drain()         # idle engine: pool fully drained
        return alive

    def pop_completed(self) -> list[Request]:
        """Drain and return requests finished since the last drain, in
        completion order.  Callers driving the engine through ``step()``
        directly should call this each tick -- completions are held until
        drained, so an undrained engine retains every finished Request.
        """
        finished, self._completed = self._completed, []
        return finished

    def run_until_idle(self, max_steps: int = 10_000) -> list[Request]:
        """Step until the queue and all slots drain.

        Returns every request that completed during (or before, via manual
        ``step`` calls) this run, in completion order.  Raises RuntimeError
        when ``max_steps`` ticks pass with work still pending -- returning
        partial completions would be indistinguishable from a clean drain
        (callers that want bounded partial progress should drive ``step()``
        themselves and ``pop_completed()`` what finished).
        """
        for _ in range(max_steps):
            if not self.step():
                return self.pop_completed()
        raise RuntimeError(
            f"engine did not drain within max_steps={max_steps}: "
            f"{len(self.queue)} request(s) still queued, "
            f"{sum(r is not None for r in self.active)} slot(s) active")
