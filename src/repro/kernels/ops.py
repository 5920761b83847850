"""Jit'd public wrappers for the Pallas kernels.

Dispatch policy: compiled Pallas on TPU, interpret-mode (Python-executed
kernel body) elsewhere — so the SAME kernel code is validated on CPU CI and
deployed on pods.  There is no override: on a TPU the compiled kernel runs.
"""
from __future__ import annotations

from functools import partial

import jax

from .borda_count import borda_count as _borda
from .decode_attention import decode_attention as _decode
from .flash_attention import flash_attention as _flash
from .mlstm_scan import mlstm_scan as _mlstm
from .moe_gating import moe_gating as _moe_gate
from .paged_attention import paged_attention as _paged
from .ssm_scan import ssm_scan as _ssm
from .topk_scores import topk_scores as _topk


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_interpret() -> bool:
    return not on_tpu()


@partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                   "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, block_q: int = 128, block_k: int = 128):
    """``q_offset`` > 0 runs suffix-only (chunked) prefill over prepended
    KV — the kernel-level counterpart of the serving prefix-KV cache."""
    return _flash(q, k, v, causal=causal, window=window, q_offset=q_offset,
                  block_q=block_q, block_k=block_k, interpret=use_interpret())


@partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q, k_cache, v_cache, pos, *, block_k: int = 256):
    return _decode(q, k_cache, v_cache, pos, block_k=block_k,
                   interpret=use_interpret())


@jax.jit
def paged_decode_attention(q, k_pool, v_pool, tables, ctx_len):
    """Flash-decode over the block-paged KV pool: per-sequence block tables
    are scalar-prefetched so the kernel DMAs exactly the blocks a sequence
    owns.  TPU-deployment counterpart of the engine's decode step — like
    every kernel here, the model stack itself runs the XLA-level equivalent
    (layers.paged_decode_attention_dense, which the bit-identity contract
    needs); this is the pod-serving variant validated against the same
    ref oracle."""
    return _paged(q, k_pool, v_pool, tables, ctx_len,
                  interpret=use_interpret())


@partial(jax.jit, static_argnames=("k", "block_n"))
def topk_scores(scores, k: int, *, block_n: int = 1024):
    """Two-stage top-k: blocked Pallas candidates + final jnp reduce."""
    bv, bi = _topk(scores, k, block_n=block_n, interpret=use_interpret())
    cand_v, cand_i = bv.reshape(-1), bi.reshape(-1)
    vals, sel = jax.lax.top_k(cand_v, k)
    return vals, cand_i[sel]


@partial(jax.jit, static_argnames=("n_items", "block_items", "block_ballots"))
def borda_count(ballots, n_items: int, *, block_items: int = 128,
                block_ballots: int = 8):
    return _borda(ballots, n_items, block_items=block_items,
                  block_ballots=block_ballots, interpret=use_interpret())


@partial(jax.jit, static_argnames=("block_d", "chunk"))
def ssm_scan(x, dt, b_t, c_t, a, *, block_d: int = 256, chunk: int = 64):
    return _ssm(x, dt, b_t, c_t, a, block_d=block_d, chunk=chunk,
                interpret=use_interpret())


@partial(jax.jit, static_argnames=("chunk",))
def mlstm_scan(q, k, v, i_g, f_g, *, chunk: int = 64):
    return _mlstm(q, k, v, i_g, f_g, chunk=chunk, interpret=use_interpret())


@partial(jax.jit, static_argnames=("k", "block_t"))
def moe_gating(logits, k: int, *, block_t: int = 256):
    return _moe_gate(logits, k, block_t=block_t, interpret=use_interpret())
