"""Top-k MoE FFN (Mixtral-style) with sort-based, capacity-bounded dispatch.

TPU adaptation: instead of the GShard (T, E, C) one-hot dispatch einsum —
whose FLOPs/memory dwarf the expert compute — tokens are routed with an
argsort over expert assignments plus scatter/gather, which XLA costs as data
movement, not FLOPs.  Expert weights are tensor-parallel over ``d_ff`` (the
``model`` mesh axis): with 8 experts on a 16-wide model axis, expert-sharding
would pad 8→16 (2x compute waste), so F-sharding is the clean layout; the
collective pattern matches a dense Megatron FFN (documented in DESIGN.md).
Tokens over capacity are dropped (gates renormalized) — standard for
capacity-bounded routing.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .config import MoESpec
from .layers import f32


def init_moe_params(rng, d_model: int, d_ff: int, spec: MoESpec, dtype):
    ks = jax.random.split(rng, 4)
    e = spec.n_experts
    std_in = 1.0 / math.sqrt(d_model)
    std_out = 1.0 / math.sqrt(d_ff)
    return {
        "router": (jax.random.normal(ks[0], (d_model, e), f32) * std_in).astype(dtype),
        "w_gate": (jax.random.normal(ks[1], (e, d_model, d_ff), f32) * std_in).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d_model, d_ff), f32) * std_in).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, d_ff, d_model), f32) * std_out).astype(dtype),
    }


def moe_ffn(p, x, spec: MoESpec, capacity: Optional[int] = None):
    """x: (B, S, D) -> (B, S, D).  Router in fp32; top-k softmax-of-topk."""
    btype = x.dtype
    b, s, d = x.shape
    e, k = spec.n_experts, spec.top_k
    t = b * s
    xt = x.reshape(t, d)

    logits = (xt @ p["router"]).astype(f32)                 # (T, E)
    top_vals, top_idx = jax.lax.top_k(logits, k)            # (T, k)
    gates = jax.nn.softmax(top_vals, axis=-1)               # (T, k)

    cap = capacity or int(math.ceil(spec.capacity_factor * k * t / e))
    cap = max(cap, 1)

    # flatten assignments and compute each token-slot's rank within its expert
    flat_e = top_idx.reshape(-1)                            # (T*k,)
    order = jnp.argsort(flat_e, stable=True)                # group by expert
    sorted_e = flat_e[order]
    run_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_sorted = jnp.arange(t * k) - run_start               # rank within expert
    pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)  # undo sort
    keep = pos < cap

    tok_of = jnp.arange(t).repeat(k)                        # (T*k,) token index
    safe_pos = jnp.where(keep, pos, cap - 1)

    # dispatch: (E, cap, D)
    disp = jnp.zeros((e, cap, d), btype)
    disp = disp.at[flat_e, safe_pos].add(jnp.where(keep[:, None], xt[tok_of], 0))

    # expert FFN
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", disp, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", disp, p["w_up"])
    out_e = jnp.einsum("ecf,efd->ecd", h, p["w_down"])       # (E, cap, D)

    # combine: gather back and weight by gate
    gathered = out_e[flat_e, safe_pos]                       # (T*k, D)
    gathered = jnp.where(keep[:, None], gathered, 0)
    gk = (gates.reshape(-1) * keep).astype(btype)
    combined = jnp.zeros((t, d), btype).at[tok_of].add(gathered * gk[:, None])

    # renormalize for dropped tokens
    denom = jnp.zeros((t,), f32).at[tok_of].add(gk.astype(f32))
    combined = combined / jnp.maximum(denom, 1e-9)[:, None].astype(btype)
    return combined.reshape(b, s, d)


def moe_ffn_sharded(p, x, spec: MoESpec, mesh, dp_axes, model_axis: str):
    """shard_map-local MoE dispatch (the §Perf collective fix).

    The global-view ``moe_ffn`` builds one (E, C_global, D) dispatch buffer
    with data-dependent scatter indices; GSPMD cannot shard that scatter, so
    it replicates the buffer per data shard and all-reduces it — tens of GB
    per layer at mixtral-8x22b scale.  Here each data shard dispatches its
    OWN tokens into a local (E, C_local, D) buffer (C_local = capacity of the
    local token count — per-shard capacity is what production routers use),
    and only the F-sharded expert contraction is reduced over the model axis.
    """
    from functools import partial as _partial

    from jax.sharding import PartitionSpec as P

    x_spec = P(dp_axes, None, None)
    w_col = P(None, None, model_axis)   # (E, D, F): F sharded
    w_row = P(None, model_axis, None)   # (E, F, D): F sharded

    @_partial(jax.shard_map, mesh=mesh,
              in_specs=(x_spec, P(), w_col, w_col, w_row),
              out_specs=x_spec, check_vma=False)
    def _local(xs, router, w_gate, w_up, w_down):
        params = {"router": router, "w_gate": w_gate, "w_up": w_up,
                  "w_down": w_down}
        out = moe_ffn(params, xs, spec)
        # w_down contracted a model-sharded F: finish the reduction here
        return jax.lax.psum(out, axis_name=model_axis)

    return _local(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def router_aux_loss(p, x, spec: MoESpec) -> jax.Array:
    """Load-balancing auxiliary loss (Switch-style): E * sum(f_e * p_e)."""
    b, s, d = x.shape
    logits = (x.reshape(-1, d) @ p["router"]).astype(f32)
    probs = jax.nn.softmax(logits, axis=-1)
    top1 = jnp.argmax(logits, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, spec.n_experts, dtype=f32), axis=0)
    return spec.n_experts * jnp.sum(frac * jnp.mean(probs, axis=0))
