"""Distributed extras: explicit compressed all-reduce, elastic-mesh
re-lowering, activation-sharding context."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_reduced
from repro.distributed import (ShardingPlan, activation_spec, named,
                               param_specs, sequence_parallel_spec)
from repro.launch.mesh import make_local_mesh
from repro.models import LM
from repro.training.compression import compress_leaf, ef_allreduce
from repro.training.fault_tolerance import elastic_plan


def test_ef_allreduce_roundtrip_single_shard():
    """shard_map int8 psum path: on a 1-wide axis it must equal dequant."""
    mesh = make_local_mesh(1, 1)
    g = jnp.asarray(np.random.default_rng(0).standard_normal(64), jnp.float32)
    q, scale, err = compress_leaf(g, jnp.zeros_like(g))
    with mesh:
        out = ef_allreduce(mesh, ("data",), q, jnp.full((64,), scale))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(q, np.float32) * float(scale),
                               rtol=1e-6)
    # error feedback bound
    assert float(jnp.max(jnp.abs(err))) <= float(scale) * 1.01


def test_elastic_replan_and_relower():
    """Losing devices: elastic_plan recarves the data axis, the same model
    re-lowers on the smaller mesh (the restart path after a pod loss)."""
    plan = elastic_plan(n_alive=1, model_parallel=1)
    assert plan.n_devices == 1
    cfg = get_reduced("llama3-8b")
    lm = LM(cfg)
    mesh = make_local_mesh(plan.data, plan.model)
    params_shape = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    shardings = named(mesh, param_specs(params_shape, mesh, ShardingPlan()))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32)}
    with mesh:
        compiled = jax.jit(lm.loss, in_shardings=(shardings, None)) \
            .lower(params_shape, batch).compile()
    assert compiled.cost_analysis() is not None


def test_activation_spec_context_applies_constraint():
    cfg = get_reduced("phi4-mini-3.8b")
    lm = LM(cfg)
    mesh = make_local_mesh(1, 1)
    params = lm.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32)}
    with mesh, activation_spec(sequence_parallel_spec(("data",))):
        loss, _ = jax.jit(lm.loss)(params, batch)
    assert np.isfinite(float(loss))


def test_cache_layout_seq_spec():
    from jax.sharding import AbstractMesh
    from repro.distributed import cache_specs
    mesh = AbstractMesh((16, 16), ("data", "model"))
    cache = jax.ShapeDtypeStruct((32, 128, 32768, 8, 128), jnp.bfloat16)
    spec = jax.tree.leaves(
        cache_specs(cache, mesh, ShardingPlan(cache_layout="seq")),
        is_leaf=lambda x: isinstance(x, P))[0]
    entries = tuple(spec)
    assert entries[1] in ("data", ("data",))     # batch over data
    assert entries[2] in ("model", ("model",))   # seq over model (ctx parallel)
