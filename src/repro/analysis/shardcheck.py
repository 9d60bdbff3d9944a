"""Layer 3: shardcheck -- static sharding + dtype-flow verification.

An abstract-evaluation pass over the ENTIRE sharding policy surface:
``launch/sharding.py`` (``param_spec`` / ``batch_spec`` / ``cache_spec``)
and ``serving/kvpool.py`` (``decode_state_specs``), walked for every
registry config x model degree in :data:`MODEL_DEGREES` on the contracts
layer's :class:`~repro.analysis.contracts.ShapeOnlyMesh` -- no arrays are
built, no devices needed, the whole registry checks in seconds.

Spec invariants (check ``spec`` / ``batch`` / ``cache`` / ``pool``):

* every sharded dim divides the product of its mesh axes, no mesh axis is
  consumed twice in one spec, no spec outranks its leaf
  (``launch.sharding.validate_spec``);
* attention projections shard HEAD-granularly: if a wq/wk/wv/wo/bias leaf
  carries ``"model"``, the relevant head count must divide the degree --
  the exact bug class PR 5 fixed (check ``kv-heads``);
* batch inputs never shard over ``"model"`` (tokens are replicated across
  tensor-parallel shards by contract);
* paged-pool leaves: only KV ``k``/``v`` tensors may shard, only on their
  kv-head dim; integer bookkeeping (ring positions -- and, by the same
  contract, the block tables / ``seq_lens`` the engine passes alongside)
  stays replicated; block-count / block-size axes never split;
* prefill-cache vs paged-pool CONSISTENCY: for each KV leaf, both
  policies must agree on whether the kv-head dim shards -- a mismatch
  means ``commit_prefill`` reshards every admission (check
  ``consistency``).

Dtype flow (check ``dtype``): ``eval_shape`` propagation over
``MecParams``, the serving prefill/decode-state programs, and the paged
per-tick update, flagging float64/complex128 leaves and weak-typed floats
(silent upcast fuel + retrace churn), and asserting the paged decode
returns its state with bit-identical dtypes (no tick-to-tick promotion
drift).

Donation (check ``donation``): the one check that builds a real (tiny)
engine -- it lowers the per-tick paged-decode update and the
commit-prefill bridge and asserts the input pool state is donated
(``donate_argnums``); without donation every tick holds two full KV
pools live.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from ..configs import base as config_base
from .contracts import ShapeOnlyMesh, _batch_struct, _params_struct

MODEL_DEGREES = (1, 2, 4, 8)

_B, _S, _SMAX = 2, 24, 48
_SLOTS, _BLOCK = 4, 8

# attention-projection leaves and which head count guards their "model" use
_Q_NAMES = ("wq", "bq", "wo")
_KV_NAMES = ("wk", "wv", "bk", "bv")


@dataclasses.dataclass(frozen=True)
class ShardFailure:
    arch: str
    check: str
    message: str

    def render(self) -> str:
        return f"{self.arch} [shardcheck:{self.check}]: {self.message}"


@dataclasses.dataclass(frozen=True)
class ShardcheckReport:
    covered: tuple            # (arch, check) pairs actually walked
    skipped: tuple            # (arch, check, reason)
    failures: tuple
    elapsed_s: float

    @property
    def ok(self) -> bool:
        return not self.failures


def _axes_of(entry) -> tuple:
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def _spec_axes(spec) -> set:
    out: set = set()
    for entry in tuple(spec):
        out.update(_axes_of(entry))
    return out


def _leaf_name(pstr: str) -> str:
    return pstr.rsplit("/", 1)[-1]


# ---------------------------------------------------------------------------
# param specs
# ---------------------------------------------------------------------------

def _check_param_specs(cfg, params, mesh, m: int, failures: list):
    from ..launch import sharding
    arch = cfg.name
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in leaves:
        pstr = sharding._path_str(path)
        spec = sharding.param_spec(mesh, cfg, pstr, leaf.shape)
        for err in sharding.validate_spec(mesh, leaf.shape, spec):
            failures.append(ShardFailure(
                arch, "spec", f"model={m} {pstr}: {err}"))
        # head-granular TP: "model" on an attention projection is only
        # legal when the head count divides the degree -- flat-dim
        # divisibility alone would split a head across shards
        name = _leaf_name(pstr)
        if "model" in _spec_axes(spec):
            heads = None
            if name in _Q_NAMES and len(leaf.shape) <= 3:
                heads = cfg.n_heads
            elif name in _KV_NAMES:
                heads = cfg.n_kv or cfg.n_heads
            if heads is not None and heads % m:
                failures.append(ShardFailure(
                    arch, "kv-heads",
                    f"model={m} {pstr}: spec {spec} splits {heads} head(s) "
                    f"across a {m}-way model axis (head-granular TP "
                    f"contract; docs/serving.md)"))


# ---------------------------------------------------------------------------
# batch / cache specs
# ---------------------------------------------------------------------------

def _check_batch_specs(cfg, mesh, m: int, failures: list):
    from ..launch import sharding
    arch = cfg.name
    batch = _batch_struct(cfg, _B, _S)
    for pstr, leaf in sorted(
            (k, v) for k, v in batch.items()):
        spec = sharding.batch_spec(mesh, leaf)
        for err in sharding.validate_spec(mesh, leaf.shape, spec):
            failures.append(ShardFailure(
                arch, "batch", f"model={m} {pstr}: {err}"))
        if "model" in _spec_axes(spec):
            failures.append(ShardFailure(
                arch, "batch",
                f"model={m} {pstr}: batch inputs replicate across the "
                f"model axis (got {spec})"))


def _kv_dim_axes(leaf_ndim: int, spec) -> tuple:
    """Axes on the kv-head dim (index -2) of a (…, S-or-block, KV, hd)
    leaf, given specs are leading-aligned."""
    entries = tuple(spec)
    kv_dim = leaf_ndim - 2
    if kv_dim < len(entries):
        return _axes_of(entries[kv_dim])
    return ()


def _check_cache_specs(cfg, cache, mesh, m: int, failures: list) -> dict:
    """Validate prefill-cache specs; returns {path: kv-dim-sharded?} for
    the consistency check."""
    from ..launch import sharding
    arch = cfg.name
    kv_sharded: dict[str, bool] = {}
    leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
    for path, leaf in leaves:
        pstr = sharding._path_str(path)
        spec = sharding.cache_spec(mesh, path, leaf, _B)
        for err in sharding.validate_spec(mesh, leaf.shape, spec):
            failures.append(ShardFailure(
                arch, "cache", f"model={m} {pstr}: {err}"))
        name = _leaf_name(pstr)
        if name in ("k", "v") and leaf.ndim >= 4:
            kv_axes = _kv_dim_axes(leaf.ndim, spec)
            kv_sharded[pstr] = "model" in kv_axes
            if "model" in kv_axes and leaf.shape[-2] % m:
                failures.append(ShardFailure(
                    arch, "cache",
                    f"model={m} {pstr}: kv-head dim {leaf.shape[-2]} "
                    f"split {m} ways"))
        elif "model" in _spec_axes(spec):
            failures.append(ShardFailure(
                arch, "cache",
                f"model={m} {pstr}: non-KV cache leaf shards over "
                f"'model' (got {spec})"))
    return kv_sharded


# ---------------------------------------------------------------------------
# paged-pool specs + prefill/pool consistency
# ---------------------------------------------------------------------------

def _check_pool_specs(cfg, state, mesh, m: int,
                      cache_kv: dict, failures: list):
    from ..launch import sharding
    from ..serving import kvpool
    arch = cfg.name
    for pstr, shape, spec in kvpool.decode_state_specs(mesh, state):
        for err in sharding.validate_spec(mesh, shape, spec):
            failures.append(ShardFailure(
                arch, "pool", f"model={m} {pstr}: {err}"))
        name = _leaf_name(pstr)
        axes_used = _spec_axes(spec)
        if name in ("k", "v") and len(shape) >= 4:
            kv_axes = _kv_dim_axes(len(shape), spec)
            bad = axes_used - set(kv_axes)
            if bad:
                failures.append(ShardFailure(
                    arch, "pool",
                    f"model={m} {pstr}: pool KV leaf shards non-kv-head "
                    f"dim(s) over {sorted(bad)} -- the block axis must "
                    f"stay whole (block tables index it on every shard)"))
            if "model" in kv_axes and shape[-2] % m:
                failures.append(ShardFailure(
                    arch, "pool",
                    f"model={m} {pstr}: kv-head dim {shape[-2]} split "
                    f"{m} ways"))
            # consistency with the prefill cache policy: commit_prefill
            # copies solo-prefill KV into the pool every admission; the
            # two policies disagreeing on the kv-head dim means a
            # reshard per admitted request
            want = cache_kv.get(pstr)
            got = "model" in kv_axes
            if want is not None and want != got:
                failures.append(ShardFailure(
                    arch, "consistency",
                    f"model={m} {pstr}: prefill cache "
                    f"{'shards' if want else 'replicates'} the kv-head "
                    f"dim but the paged pool "
                    f"{'shards' if got else 'replicates'} it -- "
                    f"commit_prefill reshards every admission"))
        elif axes_used:
            failures.append(ShardFailure(
                arch, "pool",
                f"model={m} {pstr}: non-KV pool leaf (bookkeeping / "
                f"recurrent state) must replicate, got {spec}"))


# ---------------------------------------------------------------------------
# dtype flow
# ---------------------------------------------------------------------------

_BAD_DTYPES = ("float64", "complex128")


def dtype_failures(tree, *, arch: str, what: str,
                   check: str = "dtype") -> list[ShardFailure]:
    """Flag f64/complex128 leaves and weak-typed floats anywhere in an
    ``eval_shape`` (or concrete) pytree."""
    failures: list[ShardFailure] = []
    from ..launch.sharding import _path_str
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        dt = jnp.dtype(leaf.dtype)
        pstr = _path_str(path)
        if dt.name in _BAD_DTYPES:
            failures.append(ShardFailure(
                arch, check,
                f"{what}/{pstr}: dtype {dt.name} (silent x64 promotion; "
                f"the stack is f32-sized end to end)"))
        if (getattr(leaf, "weak_type", False)
                and jnp.issubdtype(dt, jnp.floating)):
            failures.append(ShardFailure(
                arch, check,
                f"{what}/{pstr}: weak-typed {dt.name} leaf (promotes on "
                f"contact with narrower dtypes and retraces per weakness "
                f"pattern)"))
    return failures


def _check_dtype_flow(cfg, params, cache, state, failures: list):
    """Prefill cache, pool state, and the per-tick paged update must hold
    strong f32/int32 dtypes, and the paged update must return its state
    bit-identically typed (no promotion drift tick to tick)."""
    from ..models import transformer
    arch = cfg.name
    failures.extend(dtype_failures(cache, arch=arch, what="prefill-cache"))
    if state is None:
        return
    failures.extend(dtype_failures(state, arch=arch, what="pool-state"))
    table = jax.ShapeDtypeStruct((_SLOTS, -(-_SMAX // _BLOCK)), jnp.int32)
    lens = jax.ShapeDtypeStruct((_SLOTS,), jnp.int32)
    toks = jax.ShapeDtypeStruct((_SLOTS,), jnp.int32)
    logits, state2 = jax.eval_shape(
        lambda p, st, t, bt, sl: transformer.decode_step_paged(
            p, cfg, st, t, bt, sl),
        params, state, toks, table, lens)
    failures.extend(dtype_failures(logits, arch=arch, what="paged-logits"))
    in_leaves = jax.tree.leaves(state)
    out_leaves = jax.tree.leaves(state2)
    for i, (a, b) in enumerate(zip(in_leaves, out_leaves)):
        if a.dtype != b.dtype:
            failures.append(ShardFailure(
                arch, "dtype",
                f"paged decode promotes state leaf {i}: "
                f"{a.dtype} -> {b.dtype} (tick-to-tick drift; a weak "
                f"scalar in the update path?)"))


def mec_params_dtype_failures() -> list[ShardFailure]:
    """MecParams (the scenario-side pytree every rollout threads) must be
    f32/int32 throughout -- one f64 leaf doubles every cell's state and
    desyncs the jitted rollout dtype contract."""
    from ..core import scenarios
    params = scenarios.make("fixed_rate", rate=1.0).params()
    return dtype_failures(params, arch="mec-params", what="MecParams")


# ---------------------------------------------------------------------------
# donation probe
# ---------------------------------------------------------------------------

def donation_failures(fn, args, *, arch: str, what: str,
                      argnum: int = 0) -> list[ShardFailure]:
    """Lower a jitted callable with the given args and assert every array
    in ``args[argnum]`` is donated.  Traces only (no compile, no
    execute)."""
    failures: list[ShardFailure] = []
    try:
        lowered = fn.lower(*args)
    except AttributeError:
        return [ShardFailure(
            arch, "donation",
            f"{what}: not introspectable (no .lower -- wrapped "
            f"non-jit callable?)")]
    arg_info = lowered.args_info[0][argnum]
    not_donated = [i for i, leaf in enumerate(jax.tree.leaves(arg_info))
                   if not leaf.donated]
    if not_donated:
        failures.append(ShardFailure(
            arch, "donation",
            f"{what}: {len(not_donated)} state leaf/leaves not donated "
            f"(donate_argnums missing?) -- every tick holds two full KV "
            f"pools live"))
    return failures


def _check_donation(arch: str = "qwen3-0.6b") -> list[ShardFailure]:
    """Build ONE tiny real engine and verify its per-tick decode update
    and commit bridge donate their input pool state."""
    from ..configs.base import get_config, reduced
    from ..models import transformer
    from ..serving.engine import Request, ServingEngine

    import numpy as np
    cfg = reduced(get_config(arch), n_layers=1)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, slots=2, s_max=32)
    failures = donation_failures(
        eng._decode_paged,
        (eng.params, eng._pool_state, jnp.zeros((eng.slots,), jnp.int32),
         jnp.asarray(eng.block_tables), jnp.asarray(eng.seq_lens)),
        arch=cfg.name, what="decode_step_paged tick update", argnum=1)
    # the commit bridge: same donation contract on its state argument
    req = Request(rid=0, prompt=np.arange(5, dtype=np.int32), max_new=2)
    _, cache, pad = eng._solo_prefill(req)
    solo = {"units": cache["units"], "tail": cache["tail"]}
    ids = jnp.zeros((1,), jnp.int32)
    failures += donation_failures(
        eng._commit,
        (eng._pool_state, solo, jnp.int32(pad), jnp.int32(0), ids),
        arch=cfg.name, what="commit_prefill admission bridge")
    # chunked-prefill commit: same contract (auto chunking is off at this
    # s_max, so ask for it explicitly)
    eng_c = ServingEngine(cfg, params, slots=2, s_max=32, prefill_chunk=8)
    ids_full = jnp.zeros((eng_c.table_width,), jnp.int32)
    failures += donation_failures(
        eng_c._commit_chunk,
        (eng_c._pool_state, solo, jnp.int32(0), jnp.int32(5), jnp.int32(0),
         ids_full),
        arch=cfg.name, what="commit_chunk streaming bridge")
    return failures


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_shardcheck(arch_names=None, *, model_degrees=MODEL_DEGREES,
                   donation: bool = True,
                   verbose: bool = False) -> ShardcheckReport:
    from ..models import transformer
    from ..serving import kvpool

    configs = config_base.load_all()
    if arch_names:
        configs = {n: configs[n] for n in arch_names}
    t0 = time.perf_counter()
    failures: list[ShardFailure] = []
    covered: list[tuple[str, str]] = []
    skipped: list[tuple[str, str, str]] = []

    for name, cfg in sorted(configs.items()):
        t1 = time.perf_counter()
        try:
            params = _params_struct(cfg)
        except Exception as e:
            failures.append(ShardFailure(name, "init", repr(e)))
            continue
        # one trace each for the prefill cache and (plain decoders) the pool
        try:
            batch = _batch_struct(cfg, _B, _S)
            _, cache = jax.eval_shape(
                lambda p, b: transformer.prefill(p, cfg, b, s_max=_SMAX),
                params, batch)
        except Exception as e:
            failures.append(ShardFailure(name, "cache-trace", repr(e)))
            continue
        state = None
        try:
            kvpool._check_pattern(cfg)
            n_blocks = _SLOTS * (_SMAX // _BLOCK) + 1
            state = jax.eval_shape(
                lambda p: kvpool.init_decode_state(cfg, p, _SLOTS, n_blocks,
                                                   _BLOCK),
                params)
        except ValueError as e:
            skipped.append((name, "pool", str(e).split(";")[0]))

        for m in model_degrees:
            mesh = ShapeOnlyMesh(cells=1, model=m)
            _check_param_specs(cfg, params, mesh, m, failures)
            _check_batch_specs(cfg, mesh, m, failures)
            cache_kv = _check_cache_specs(cfg, cache, mesh, m, failures)
            if state is not None:
                _check_pool_specs(cfg, state, mesh, m, cache_kv, failures)
        covered.extend((name, c) for c in ("spec", "batch", "cache"))
        if state is not None:
            covered.extend((name, c) for c in ("pool", "consistency"))
        _check_dtype_flow(cfg, params, cache, state, failures)
        covered.append((name, "dtype"))
        if verbose:
            print(f"  {name}: {time.perf_counter() - t1:.2f}s")

    failures.extend(mec_params_dtype_failures())
    covered.append(("mec-params", "dtype"))
    if donation:
        failures.extend(_check_donation())
        covered.append(("qwen3-0.6b", "donation"))
    else:
        skipped.append(("qwen3-0.6b", "donation", "disabled by caller"))
    return ShardcheckReport(covered=tuple(covered), skipped=tuple(skipped),
                            failures=tuple(failures),
                            elapsed_s=time.perf_counter() - t0)
