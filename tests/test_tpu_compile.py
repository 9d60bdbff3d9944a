"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with JAX lowers each kernel at its
real widths against a ``v5e:2x2`` topology description and refuses what the
chip would refuse (tiling rules, VMEM budgets, primitives Mosaic lacks) --
the refusals interpret mode cannot see.  Each compiled kernel keeps the
name it was given, which is how the profiler's trace labels its operation.
The topology is described only inside the module-scoped fixture below,
never at import, so every test worker collects the same tests and only the
worker that runs this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs.base import get_config
from repro.core import gridshard
from repro.core import scenarios as sc
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.partition_sweep import partition_sweep_batched
from repro.kernels.rglru_scan import rglru_scan_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.models import transformer
from repro.serving import kvpool

pytestmark = pytest.mark.filterwarnings("ignore")

QWEN = get_config("qwen3-0.6b")
MAMBA = get_config("mamba2-1.3b")
RG = get_config("recurrentgemma-2b")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for(one_chip):
    """``compile_for(fn, *shapes)`` -> compiled HLO text.  The persistent
    cache is off around the compile: an entry compiled for a described chip
    cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def compile_(fn, *shapes, sharding=one_chip, donate=()):
        """Each shape is a tuple of ints (bf16), a (shape, dtype) pair, or
        a pytree of arrays or ``ShapeDtypeStruct``s."""
        def spec(s):
            if not isinstance(s, tuple):
                return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=sharding), s)
            return jax.ShapeDtypeStruct(
                *(s if isinstance(s[0], tuple) else (s, jnp.bfloat16)),
                sharding=sharding)
        args = [spec(s) for s in shapes]
        return jax.jit(fn, donate_argnums=donate).lower(
            *args).compile().as_text()

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield compile_
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.mark.parametrize("chips", [1, 4])
def test_partition_sweep_batched_1024_cells_16_ues(topo, compile_for, chips):
    """The controller's Oracle sweep over a deployment-size grid, on one
    chip and cell-sharded over four (one launch per shard: GSPMD cannot
    partition a Mosaic kernel, so the grid runs it under per_shard)."""
    cell = sc.multicell_grid(cells=1, ues=16)
    c = sc.ScenarioGrid(cell).num_cuts
    scalars = cell[0].sweep_scalars()
    table, vec = ((1024, 16, c), jnp.float32), ((1024, 16), jnp.float32)

    def sweep(*a):
        return partition_sweep_batched(*a, scalars)
    kw = {}
    if chips > 1:
        mesh = Mesh(np.asarray(topo.devices[:chips]), (gridshard.CELL_AXIS,))
        gs = gridshard.plan(1024, mesh)
        sweep = gridshard.per_shard(sweep, gs)
        kw["sharding"] = gs.sharding(2)
    text = compile_for(sweep, table, table, table, table, vec, vec, vec, vec,
                       vec, **kw)
    assert "tpu_custom_call" in text and "%partition_sweep" in text
    assert "all-gather" not in text


@pytest.mark.parametrize("padded", [False, True], ids=["causal", "padded"])
def test_flash_attention_qwen3_widths(compile_for, padded):
    """Prefill attention: solo causal at S 2048, and the engine's ragged
    (left-padded) prompt batch with its per-row pad count."""
    b, s = (4, 512) if padded else (1, 2048)
    h, kv, hd = QWEN.n_heads, QWEN.n_kv, QWEN.resolved_head_dim
    shapes = [(b, s, h, hd), (b, s, kv, hd), (b, s, kv, hd)]
    if padded:
        text = compile_for(
            lambda q, k, v, pad: flash_attention_pallas(q, k, v, pad=pad),
            *shapes, ((b,), jnp.int32))
    else:
        text = compile_for(lambda q, k, v: flash_attention_pallas(q, k, v),
                           *shapes)
    assert "tpu_custom_call" in text and "%flash_attention" in text


@pytest.mark.parametrize("slots", [1, 8])
def test_decode_attention_qwen3_widths(compile_for, slots):
    """One paged decode tick: every slot against a 1024-token cache."""
    h, kv, hd = QWEN.n_heads, QWEN.n_kv, QWEN.resolved_head_dim
    text = compile_for(
        lambda q, k, v, m: decode_attention_pallas(q, k, v, valid_mask=m),
        (slots, 1, h, hd), (slots, 1024, kv, hd), (slots, 1024, kv, hd),
        ((slots, 1024), jnp.bool_))
    assert "tpu_custom_call" in text and "%decode_attention" in text


@pytest.mark.parametrize("reset", [False, True], ids=["plain", "reset"])
def test_ssd_scan_mamba2_widths(compile_for, reset):
    """Mamba2-1.3B's SSD scan: 64 heads of 64, state 128, one B/C group;
    ``reset`` is the ragged serving batch."""
    d_inner = MAMBA.ssm_expand * MAMBA.d_model
    h, p, n = d_inner // MAMBA.ssm_headdim, MAMBA.ssm_headdim, MAMBA.ssm_state
    b, s, f32 = 2, 1024, jnp.float32
    shapes = [((b, s, h, p), f32), ((b, s, h), f32), ((h,), f32),
              ((b, s, 1, n), f32), ((b, s, 1, n), f32), ((h,), f32)]
    if reset:
        text = compile_for(
            lambda x, dt, a, bb, cc, d, r: ssd_scan_pallas(
                x, dt, a, bb, cc, d, chunk=MAMBA.ssm_chunk, reset=r),
            *shapes, ((b, s), jnp.bool_))
    else:
        text = compile_for(
            lambda x, dt, a, bb, cc, d: ssd_scan_pallas(
                x, dt, a, bb, cc, d, chunk=MAMBA.ssm_chunk), *shapes)
    assert "tpu_custom_call" in text and "%ssd_scan" in text


def test_rglru_scan_recurrentgemma_widths(compile_for):
    r = RG.resolved_rnn_width
    shape = ((2, 2048, r), jnp.float32)
    text = compile_for(lambda x, a, rs: rglru_scan_pallas(x, a, reset=rs),
                       shape, shape, ((2, 2048), jnp.bool_))
    assert "tpu_custom_call" in text and "%rglru_scan" in text


def test_paged_decode_writes_the_donated_pool_in_place(compile_for):
    """The engine's decode program at the burst cell's shapes (qwen3-0.6b,
    32 slots, 641 blocks of 16, table width 20), its state donated as the
    engine donates it: no copy, temporary buffer or dynamic-update-slice of
    a KV pool's shape, no per-layer slice of one, and every pool parameter
    aliased to its output (the new rows are scattered in place).  The
    decode kernel is the Pallas one, as on the chip."""
    slots, n_blocks, bs, width = 32, 641, 16, 20
    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), QWEN))
    state = jax.eval_shape(lambda p: kvpool.init_decode_state(
        QWEN, p, slots, n_blocks, bs), params)
    ops.set_impl("pallas")
    try:
        text = compile_for(
            lambda p, st, t, bt, sl: transformer.decode_step_paged(
                p, QWEN, st, t, bt, sl),
            params, state, ((slots,), jnp.int32),
            ((slots, width), jnp.int32), ((slots,), jnp.int32), donate=1)
    finally:
        ops.set_impl("auto")
    assert "%decode_attention" in text
    tail = f"{n_blocks},{bs},{QWEN.n_kv},{QWEN.resolved_head_dim}]"
    pool = f"bf16[{QWEN.n_units},{tail}"
    insts = re.findall(r"= (bf16\[[\d,]*\])\S* ([\w-]+)\((.*)", text)
    pool_ops = [(op, rest) for shape, op, rest in insts if shape == pool]
    assert pool_ops
    for op, rest in pool_ops:
        assert op not in ("copy", "copy-start", "dynamic-update-slice"), op
        assert "AllocateBuffer" not in rest
    assert not [op for shape, op, _ in insts if shape == f"bf16[{tail}"]
    entry = text[text.index("\nENTRY"):]
    pool_params = set(re.findall(re.escape(pool) + r"\S* parameter\((\d+)\)",
                                 entry))
    aliased = set(re.findall(r"\(\s*(\d+), \{\}, (?:may|must)-alias\)",
                             text.split("\n", 1)[0]))
    assert len(pool_params) == 2 and pool_params <= aliased
