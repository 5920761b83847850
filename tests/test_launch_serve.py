"""The launcher's shared constructors, its one-chip memory reckoning, and the
compile-cache placement rule (``repro.launch``)."""
import re

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import compile_cache
from repro.launch.serve import (FULL_WIDTH_ENGINE, REDUCED_ENGINE, GiB,
                                build_engine, build_lm, serving_memory)
from repro.models import LM


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_env(monkeypatch, cache_dir_restored):
    monkeypatch.setenv(compile_cache.ENV, "/elsewhere/jax-cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == before   # set nothing


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_restored):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT / ".jax_cache")
    assert (compile_cache.CHECKOUT / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == path


def test_full_width_sizing_fits_one_chip_and_default_does_not():
    lm = LM(get_config("stablelm-1.6b"))
    sized = serving_memory(lm, 1024, **FULL_WIDTH_ENGINE)
    assert 3.0 * GiB < sized["params"] < 3.1 * GiB
    assert sized["total"] < 12 * GiB
    # the engine's own defaults (256-row probe submissions, 768 blocks,
    # 64 prefix entries) are sized for nothing at this width
    assert serving_memory(lm, 1024)["total"] > 16 * GiB


def test_constructors_serve_a_probe_round():
    lm, params = build_lm("stablelm-1.6b", full=False)
    engine = build_engine(lm, params, full=False)
    assert engine.max_new == REDUCED_ENGINE["max_new_tokens"]
    assert engine.max_probe_batch == 256             # the engine's default
    sized = build_engine(lm, params, full=False, **FULL_WIDTH_ENGINE)
    assert sized.max_probe_batch == FULL_WIDTH_ENGINE["max_probe_batch"]
    assert sized.pool.num_blocks == FULL_WIDTH_ENGINE["pool_blocks"]
    scores = sized.score([f"item {i}" for i in range(4)], "relevance")
    assert len(scores) == 4 and np.isfinite(scores).all()


def test_engine_programs_are_named():
    """Every program the engine jits has a name of its own, so the XLA
    module lines of a profile name each one (jax.jit calls a partial
    "_unknown")."""
    lm, params = build_lm("stablelm-1.6b", full=False)
    engine = build_engine(lm, params, full=False, **FULL_WIDTH_ENGINE)
    batch = engine._make_batch(np.zeros((8, 16), np.int32))
    rows = np.zeros((8,), np.int32)
    lowered = {
        "_prefill": engine._prefill.lower(params, batch),
        "_prefill_exact": engine._prefill_exact.lower(params, batch),
        "_decode_paged": engine._decode_paged.lower(
            params, engine.pool.arenas, rows[:, None], rows,
            np.zeros((8, 2), np.int32)),
    }
    names = {k: re.search(r"module @(\w+)", v.as_text()).group(1)
             for k, v in lowered.items()}
    assert names == {"_prefill": "jit_prefill",
                     "_prefill_exact": "jit_prefill_exact",
                     "_decode_paged": "jit_decode_paged"}
