"""Int8 error-feedback gradient compression (distributed-optimization trick).

The data-parallel all-reduce is the dominant training collective; quantizing
gradients to int8 with per-leaf scales cuts its bytes 4x (vs fp32) / 2x (vs
bf16).  Error feedback (Karimireddy et al. '19) keeps the quantization
residual in a local buffer and re-injects it next step, preserving
convergence.

Two entry points:
 * :func:`compress_tree` / :func:`decompress_tree` — pure transforms used by
   the train loop (the all-reduce itself stays implicit in pjit; this models
   the end-to-end numerics and is what the convergence test exercises);
 * :func:`ef_allreduce` — an explicit ``shard_map`` psum over the data axes
   operating on the int32-widened int8 payload: the form that makes the
   compressed collective visible in lowered HLO (used by the dry-run variant
   and the §Perf collective experiments).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

f32 = jnp.float32


def init_error_state(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, f32), params)


def compress_leaf(g, err):
    """Returns (q int8, scale fp32 scalar, new_err)."""
    gf = g.astype(f32) + err
    scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    deq = q.astype(f32) * scale
    return q, scale, gf - deq


def compress_tree(grads, err_state):
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err_state)
    qs, scales, errs = [], [], []
    for g, e in zip(flat_g, flat_e):
        q, s, e2 = compress_leaf(g, e)
        qs.append(q); scales.append(s); errs.append(e2)
    return (treedef.unflatten(qs), treedef.unflatten(scales),
            treedef.unflatten(errs))


def decompress_tree(qs, scales, like=None):
    out = jax.tree.map(lambda q, s: q.astype(f32) * s, qs, scales)
    if like is not None:
        out = jax.tree.map(lambda o, l: o.astype(l.dtype), out, like)
    return out


def compressed_grads(grads, err_state):
    """grads -> (dequantized grads, new error state): the train-loop hook."""
    qs, scales, errs = compress_tree(grads, err_state)
    return decompress_tree(qs, scales, like=grads), errs


def ef_allreduce(mesh, axis_names, x_q, scale):
    """Explicit compressed all-reduce of one leaf over ``axis_names``:
    int8 payload widened to int32, psum'd, then dequantized and averaged.
    The wire format is int8 (the int32 widening models the accumulator)."""
    n = 1
    for a in axis_names:
        n *= mesh.shape[a]

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(axis_names), P(axis_names)), out_specs=P(axis_names),
             check_vma=False)
    def _ar(q, s):
        acc = jax.lax.psum(q.astype(jnp.int32) * 1, axis_name=axis_names)
        s_max = jax.lax.pmax(s, axis_name=axis_names)
        return acc.astype(f32) * s_max / n

    return _ar(x_q, scale)
