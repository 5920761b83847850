"""Paged flash-decode Pallas TPU kernel: one query token per sequence
against the sequence's block run in the shared paged KV pool — the
TPU-deployment counterpart of the continuous-batching decode step's
gather+attend XLA path (layers.paged_decode_attention_dense; DESIGN.md
"Paged KV pool").

Grid (batch, kv_head, table_slots) with the block dimension innermost.  The
per-sequence block table rides in scalar-prefetch memory
(``pltpu.PrefetchScalarGridSpec``), so each step's BlockSpec index_map
resolves ``tables[b, i]`` BEFORE the kernel body runs and the DMA engine
fetches exactly the (block_size, hd) KV tile that block id names — the pool
itself never needs to be contiguous per sequence, which is the whole point
of paging: no copy on admission, no compaction on retirement.  As in
decode_attention, the GQA query-head group for one KV head rides in a
single (G, hd) VMEM tile and accumulates online-softmax state (m, l, acc)
in fp32 scratch across table slots.  Slot validity is positional:
``i * block_size + slot < ctx_len[b]`` — padded table slots point at dummy
block 0 and mask to zero weight, so arbitrary table padding cannot perturb
the result.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, bs: int, n_slots: int):
    bi = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale           # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)                   # (bs, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    # the slot mask is needed as a row (bs on lanes, for the scores) and as
    # a column (bs on sublanes, for the K/V tiles); both come from their
    # own iota — Mosaic cannot transpose an i1 vector
    ctx = ctx_ref[bi]
    valid_row = (ki * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
                 < ctx)                                   # (1, bs)
    valid_col = (ki * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
                 < ctx)                                   # (bs, 1)
    k = jnp.where(valid_col, k, 0.0)
    v = jnp.where(valid_col, v, 0.0)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, bs)
    s = jnp.where(valid_row, s, NEG_INF)
    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + p @ v
    m_scr[...] = m_cur

    @pl.when(ki == n_slots - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, tables, ctx_len, *,
                    interpret: bool = False):
    """q: (B, H, hd); k_pool/v_pool: (NB, block_size, KV, hd) paged arenas;
    tables: (B, MAXB) int32 per-sequence block runs (0-padded);
    ctx_len: (B,) int32 valid KV length per sequence.  Returns (B, H, hd)."""
    b, h, hd = q.shape
    bs, kv = k_pool.shape[1], k_pool.shape[2]
    g = h // kv
    maxb = tables.shape[1]
    scale = 1.0 / math.sqrt(hd)

    # (B, KV, G, hd) query groups; pool flattened per KV head: (NB, KV, bs, hd)
    qg = q.reshape(b, kv, g, hd)
    kf = k_pool.transpose(0, 2, 1, 3)
    vf = v_pool.transpose(0, 2, 1, 3)

    kernel = functools.partial(_kernel, scale=scale, bs=bs, n_slots=maxb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # tables, ctx_len
        grid=(b, kv, maxb),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd),
                         lambda bi, ci, ki, tables, ctx: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd),
                         lambda bi, ci, ki, tables, ctx:
                         (tables[bi, ki], ci, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd),
                         lambda bi, ci, ki, tables, ctx:
                         (tables[bi, ki], ci, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda bi, ci, ki, tables, ctx: (bi, ci, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
    )(tables, ctx_len, qg, kf, vf)
    return out.reshape(b, h, hd)
