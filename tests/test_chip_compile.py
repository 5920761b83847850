"""Compile rehearsal for one TPU v5e chip, without the chip.

The TPU compiler is installed beside the CPU backend and compiles for a
described ``v5e:2x2`` topology: what Mosaic or XLA would refuse on the chip
(an untileable kernel block, a program that does not fit HBM) is refused
here.  Nothing runs, so these tests say nothing about results or times.
The kernels are called directly with ``interpret=False`` — the ``ops``
wrappers pick interpret mode from the CPU backend and would hide a refusal.

The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the worker running this
file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.launch.serve import FULL_WIDTH_ENGINE, GiB, serving_memory
from repro.models import LM

V5E_HBM = 16 * GiB
CFG = get_config("stablelm-1.6b")
# chip_smoke.py's largest probe submission: max_probe_batch rows of the
# 1024 class (tweets compare prompts), reserve = max_new_tokens
ROWS, CLASS = FULL_WIDTH_ENGINE["max_probe_batch"], 1024
POOL, BLOCK = FULL_WIDTH_ENGINE["pool_blocks"], 16
DECODE_ROWS, MAXB = 32, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tree_specs(sharding, tree):
    return jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype), tree)


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _arena_specs(sharding):
    from repro.models.layers import PagedKV
    shape = (CFG.n_layers, POOL, BLOCK, CFG.n_kv_heads, CFG.hd)
    return [PagedKV(k=_spec(sharding, shape, jnp.bfloat16),
                    v=_spec(sharding, shape, jnp.bfloat16))]


def test_paged_attention_kernel_compiles(one_chip):
    from repro.kernels.paged_attention import paged_attention
    pool = (POOL, BLOCK, CFG.n_kv_heads, CFG.hd)
    fn = jax.jit(lambda *a: paged_attention(*a, interpret=False))
    compiled = fn.lower(
        _spec(one_chip, (DECODE_ROWS, CFG.n_heads, CFG.hd), jnp.bfloat16),
        _spec(one_chip, pool, jnp.bfloat16), _spec(one_chip, pool, jnp.bfloat16),
        _spec(one_chip, (DECODE_ROWS, MAXB), jnp.int32),
        _spec(one_chip, (DECODE_ROWS,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_kernel_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention
    qkv = _spec(one_chip, (1, CFG.n_heads, CLASS, CFG.hd), jnp.bfloat16)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=False))
    compiled = fn.lower(qkv, qkv, qkv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_probe_prefill_fits_one_chip(one_chip):
    """The smoke's largest probe submission, beside the pool arena, fits
    the chip's HBM with room for the eager pool write's second arena."""
    lm = LM(CFG)
    params = _tree_specs(one_chip, jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0))))
    tokens = _spec(one_chip, (ROWS, CLASS), jnp.int32)
    fn = jax.jit(lambda p, t: lm.prefill(
        p, {"tokens": t}, reserve=FULL_WIDTH_ENGINE["max_new_tokens"]))
    used = _bytes(fn.lower(params, tokens).compile())
    arenas = serving_memory(lm, CLASS, **FULL_WIDTH_ENGINE)["two_arenas"]
    assert used + arenas < V5E_HBM, used / GiB


def test_full_width_dense_paged_decode_fits_one_chip(one_chip):
    from functools import partial
    lm = LM(CFG)
    params = _tree_specs(one_chip, jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0))))
    fn = jax.jit(partial(lm.decode_step_paged, block_size=BLOCK),
                 donate_argnums=(1,))
    compiled = fn.lower(
        params, _arena_specs(one_chip),
        _spec(one_chip, (DECODE_ROWS, 1), jnp.int32),
        _spec(one_chip, (DECODE_ROWS,), jnp.int32),
        _spec(one_chip, (DECODE_ROWS, MAXB), jnp.int32)).compile()
    assert _bytes(compiled) < V5E_HBM


@pytest.mark.parametrize("name,rows,length,nb", [
    ("stablelm-1.6b", 2, 154, 10), ("stablelm-1.6b", 8, 256, 16),
    ("phi4-mini-3.8b", 2, 154, 10)])
def test_pool_write_is_in_place_at_full_width(one_chip, name, rows, length,
                                              nb):
    """The pool's block writer at a full-width arena of 512 blocks, for
    the placement the arena's default layout on the chip picks: block axis
    innermost for stablelm-1.6b (head size 64, under a lane tile), so one
    lane-placement pass; plain descending for phi4-mini-3.8b, so a slice a
    block.  Either way the arena is aliased and no copy of it is made."""
    import re

    from repro.models.layers import PagedKV
    from repro.serving.kv_pool import _block_writer
    cfg = get_config(name)
    shape = (cfg.n_layers, POOL, BLOCK, cfg.n_kv_heads, cfg.hd)
    leaf = _spec(one_chip, shape, jnp.bfloat16)
    default = jax.jit(lambda a: a).lower(leaf).compile()
    lanes = default.input_formats[0][0].layout.major_to_minor[-1] == 1
    assert lanes == (name == "stablelm-1.6b")
    cache = _spec(one_chip, (cfg.n_layers, rows, length, cfg.n_kv_heads,
                             cfg.hd), jnp.bfloat16)
    compiled = _block_writer([lanes]).lower(
        [PagedKV(k=leaf, v=leaf)], [(cache, cache)],
        _spec(one_chip, (rows * nb,), jnp.int32), 0, nb).compile()
    hlo = compiled.as_text()
    assert ("input_output_alias={ {0}: (0, {}, may-alias), "
            "{1}: (1, {}, may-alias) }") in hlo
    ops = re.findall(re.escape(f"bf16[{','.join(map(str, shape))}]")
                     + r"\{[^}]*\} ([\w-]+)\(", hlo)
    assert not {"copy", "transpose", "scatter"} & set(ops), ops
    leaf_bytes = 2 * int(jnp.prod(jnp.array(shape)))
    assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes


@pytest.mark.parametrize("name", ["stablelm-1.6b", "phi4-mini-3.8b"])
def test_pool_read_makes_no_arena_copy_at_full_width(one_chip, name):
    """The pool's block reader (prefix gathers, stashes) at a full-width
    arena: no relayout copy of the arena, which an eager gather along a
    block axis that is innermost in memory makes (stablelm-1.6b)."""
    import re

    from repro.models.layers import PagedKV
    from repro.serving.kv_pool import _block_reader
    cfg = get_config(name)
    shape = (cfg.n_layers, POOL, BLOCK, cfg.n_kv_heads, cfg.hd)
    leaf = _spec(one_chip, shape, jnp.bfloat16)
    default = jax.jit(lambda a: a).lower(leaf).compile()
    lanes = default.input_formats[0][0].layout.major_to_minor[-1] == 1
    compiled = _block_reader([lanes]).lower(
        [PagedKV(k=leaf, v=leaf)],
        _spec(one_chip, (10,), jnp.int32)).compile()
    ops = re.findall(re.escape(f"bf16[{','.join(map(str, shape))}]")
                     + r"\{[^}]*\} ([\w-]+)\(", compiled.as_text())
    assert not {"copy", "transpose"} & set(ops), ops
    leaf_bytes = 2 * int(jnp.prod(jnp.array(shape)))
    assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes // 8
