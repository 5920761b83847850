"""Sharding rules: spec validity, divisibility handling, ZeRO-1 extension,
and a real jit execution under a local mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_reduced, list_archs
from repro.distributed import (ShardingPlan, batch_specs, cache_specs, named,
                               param_specs, zero1_specs)
from repro.launch.mesh import make_local_mesh
from repro.models import LM


def fake_mesh_16x16():
    """AbstractMesh stands in for the production mesh (no devices needed)."""
    from jax.sharding import AbstractMesh
    return AbstractMesh((16, 16), ("data", "model"))


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("fsdp", [False, True])
def test_param_specs_divisible(arch, fsdp):
    """Every sharded dim must be divisible by its axis product (no GSPMD
    padding surprises in the memory accounting)."""
    cfg = get_config(arch)
    lm = LM(cfg)
    params_shape = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    mesh = fake_mesh_16x16()
    specs = param_specs(params_shape, mesh, ShardingPlan(fsdp=fsdp))

    def check(leaf, spec):
        for dim, entry in zip(leaf.shape, tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            total = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % total == 0, (leaf.shape, spec)

    jax.tree.map(check, params_shape, specs,
                 is_leaf=lambda x: isinstance(x, P))
    # at least half the parameter bytes must be model-sharded
    total = sharded = 0
    flat_p = jax.tree.leaves(params_shape)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for leaf, spec in zip(flat_p, flat_s):
        b = leaf.size
        total += b
        if any(e is not None for e in tuple(spec)):
            sharded += b
    assert sharded / total > 0.5, f"{arch}: only {sharded/total:.0%} sharded"


def test_zero1_extends_opt_state_sharding():
    cfg = get_config("llama3-8b")
    lm = LM(cfg)
    params_shape = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    mesh = fake_mesh_16x16()
    pspecs = param_specs(params_shape, mesh, ShardingPlan())
    ospecs = zero1_specs(params_shape, pspecs, mesh, ShardingPlan(zero1=True))
    n_extended = 0
    for ps, os_ in zip(jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P)),
                       jax.tree.leaves(ospecs, is_leaf=lambda x: isinstance(x, P))):
        if tuple(os_) != tuple(ps):
            n_extended += 1
    assert n_extended > 0


def test_batch_specs_shard_batch_dim():
    mesh = fake_mesh_16x16()
    bs = batch_specs({"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32),
                      "positions": jax.ShapeDtypeStruct((3, 256, 128), jnp.int32)},
                     mesh)
    # PartitionSpec normalizes 1-tuples to bare names
    assert bs["tokens"] in (P("data"), P(("data",)))
    assert tuple(bs["positions"])[1] in ("data", ("data",))


def test_cache_specs_context_parallel_fallback():
    """B=1 (long_500k): batch unshardable -> seq dim shards over data."""
    mesh = fake_mesh_16x16()
    cache = jax.ShapeDtypeStruct((4, 1, 524288, 5, 64), jnp.bfloat16)
    spec = jax.tree.leaves(cache_specs(cache, mesh),
                           is_leaf=lambda x: isinstance(x, P))[0]
    entries = tuple(spec)
    assert entries[1] is None           # batch=1 not sharded
    assert entries[2] in ("data", ("data",))  # seq sharded (context parallel)


def test_sharded_moe_matches_global_dispatch():
    """shard_map-local MoE dispatch (the collective fix) is numerically
    identical to the global-view scatter on a 1x1 mesh."""
    import dataclasses
    from repro.distributed.context import shard_context
    rng = jax.random.PRNGKey(0)
    cfg_g = dataclasses.replace(get_reduced("mixtral-8x7b"), moe_impl="global")
    cfg_s = dataclasses.replace(get_reduced("mixtral-8x7b"), moe_impl="sharded")
    lm_g, lm_s = LM(cfg_g), LM(cfg_s)
    params = lm_g.init(rng)
    batch = {"tokens": jax.random.randint(rng, (2, 32), 0, cfg_g.vocab_size)}
    loss_g, _ = jax.jit(lm_g.loss)(params, batch)
    mesh = make_local_mesh(1, 1)
    with mesh, shard_context(mesh, ("data",), "model"):
        loss_s, _ = jax.jit(lm_s.loss)(params, batch)
    assert abs(float(loss_g) - float(loss_s)) < 1e-3


def test_sharded_train_step_runs_on_local_mesh():
    """End-to-end: specs drive a real jit on a 1x1 local mesh."""
    cfg = get_reduced("llama3-8b")
    lm = LM(cfg)
    mesh = make_local_mesh(1, 1)
    params = lm.init(jax.random.PRNGKey(0))
    pspecs = param_specs(params, mesh, ShardingPlan())
    shardings = named(mesh, pspecs)
    params = jax.device_put(params, shardings)
    batch = {"tokens": jnp.zeros((4, 32), jnp.int32)}
    with mesh:
        loss, _ = jax.jit(lm.loss, in_shardings=(shardings, None))(params, batch)
    assert np.isfinite(float(loss))
