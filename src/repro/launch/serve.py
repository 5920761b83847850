"""Serving launcher: hosts a model behind the ORDER BY ModelOracle and runs
semantic ORDER BY queries against it.

``python -m repro.launch.serve --arch stablelm-1.6b --query "positivity" ...``

``--full`` serves the published widths with the engine sized for one 16 GiB
TPU v5e (:data:`FULL_WIDTH_ENGINE`, reckoned by :func:`serving_memory`);
the default is the reduced CPU preset.  The constructors below
(:func:`add_model_args`, :func:`build_lm`, :func:`build_engine`) are what
the launcher, ``examples/order_by_serving.py`` and ``chip_smoke.py`` share.

Sharded serving: ``--mesh DxM`` (e.g. ``--mesh 8x1``) lowers the engine onto
a ("data", "model") mesh — probe rounds split into per-data-shard row
slices, decode runs tensor-parallel over the model axis — and ``--fsdp``
additionally shards the weights over the data axes.  On CPU, force devices
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced, list_archs
from repro.core import as_keys, llm_order_by
from repro.core.oracles.model_oracle import ModelOracle
from repro.distributed.sharding import ShardingPlan
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import parse_mesh
from repro.models import LM
from repro.models.layers import dtype_of
from repro.serving import ServeEngine

GiB = 1 << 30

# Engine sizing at published widths on one 16 GiB TPU v5e (stablelm-1.6b in
# bf16: 3.06 GiB of params, 192 KiB of KV per token).  Probe prefill returns
# the KV of every row (rows x (class + max_new_tokens)), a suffix-window job
# holds its gathered prefix KV beside that output, eager pool writes keep
# two arenas live for a moment, and a prefix entry the pool cannot host is
# held dense — :func:`serving_memory` adds these up.
FULL_WIDTH_ENGINE = dict(max_new_tokens=32, max_probe_batch=8,
                         pool_blocks=512, prefix_cache_size=8)
REDUCED_ENGINE = dict(max_new_tokens=16)


def add_model_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--arch", default="stablelm-1.6b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="published widths, engine sized for one TPU v5e")


def build_lm(arch: str, full: bool, seed: int = 0):
    """(lm, params): the arch at published (``full``) or reduced widths,
    with random weights drawn from ``seed``."""
    lm = LM(get_config(arch) if full else get_reduced(arch))
    return lm, lm.init(jax.random.PRNGKey(seed))


def build_engine(lm: LM, params, full: bool, mesh=None, fsdp: bool = False,
                 **overrides) -> ServeEngine:
    """The engine sized for ``full`` widths; ``overrides`` replace single
    :class:`ServeEngine` arguments."""
    if fsdp and mesh is None:
        raise SystemExit("--fsdp requires --mesh")
    kw = {**(FULL_WIDTH_ENGINE if full else REDUCED_ENGINE), **overrides}
    return ServeEngine(lm, params, mesh=mesh,
                       plan=ShardingPlan(fsdp=fsdp) if mesh else None, **kw)


def serving_memory(lm: LM, cls: int, max_new_tokens: int = 32,
                   max_probe_batch: int = 256, pool_blocks: int = 768,
                   block_size: int = 16,
                   prefix_cache_size: int = 64) -> dict[str, int]:
    """Device bytes an engine built with these arguments may hold at once
    while serving probe prompts of padded class ``cls``: the params, two
    pool arenas (an eager write's input and output), the largest probe
    submission (gathered prefix KV plus the returned KV and logits), and
    the prefix entries held dense when the pool cannot host them.  XLA's
    own temporaries are not modelled; tests/test_chip_compile.py measures
    them for one chip."""
    cfg = lm.cfg
    shapes = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    params = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                 for s in jax.tree.leaves(shapes))
    itemsize = np.dtype(dtype_of(cfg.dtype)).itemsize
    kv_token = cfg.decoder_layers() * 2 * cfg.n_kv_heads * cfg.hd * itemsize
    rows = max_probe_batch
    out = {
        "params": params,
        "two_arenas": 2 * pool_blocks * block_size * kv_token,
        "probe_submission": (rows * (2 * cls + max_new_tokens) * kv_token
                             + rows * cfg.vocab_size * 4),
        "dense_prefix_entries": prefix_cache_size * cls * kv_token,
    }
    out["total"] = sum(out.values())
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    add_model_args(ap)
    ap.add_argument("--query", default="degree of positivity")
    ap.add_argument("--path", default="auto")
    ap.add_argument("--strategy", default="borda")
    ap.add_argument("--limit", type=int, default=5)
    ap.add_argument("--budget", type=float, default=None)
    ap.add_argument("--items", nargs="*", default=None)
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve on a data x model mesh (e.g. 8x1, 4x2)")
    ap.add_argument("--fsdp", action="store_true",
                    help="also shard weights over the data axes")
    args = ap.parse_args()

    enable_compile_cache()
    lm, params = build_lm(args.arch, full=not args.reduced)
    cfg = lm.cfg
    mesh = parse_mesh(args.mesh) if args.mesh else None
    engine = build_engine(lm, params, full=not args.reduced, mesh=mesh,
                          fsdp=args.fsdp)
    oracle = ModelOracle(engine)

    items = args.items or [
        "absolutely loved it, best purchase ever",
        "terrible, broke after one day",
        "it is fine, nothing special",
        "pretty good overall, minor flaws",
        "worst experience of my life",
        "exceeded every expectation",
        "mediocre at best",
        "would recommend with reservations",
    ]
    keys = as_keys(items)
    t0 = time.perf_counter()
    result, report = llm_order_by(
        keys, args.query, oracle, path=args.path, descending=True,
        limit=args.limit, budget=args.budget, strategy=args.strategy,
        sample_size=min(8, len(keys)))
    print(f"arch={cfg.name} path={result.path} calls={result.n_calls} "
          f"cost=${result.cost:.5f}")
    if report is not None:
        print(f"optimizer: chose={report.chosen.label} reason={report.reason} "
              f"membership={report.membership_rate:.2f}")
    dt = time.perf_counter() - t0
    for i, k in enumerate(result.order):
        print(f"  {i+1}. {k.text}")
    tps = engine.stats.decode_tokens / dt if dt > 0 else 0.0
    mesh_note = f" mesh={args.mesh}" if args.mesh else ""
    print(f"engine stats: {engine.stats}")
    print(f"throughput:{mesh_note} decode_tokens={engine.stats.decode_tokens} "
          f"wall={dt:.3f}s decode_tokens_per_s={tps:.1f}")


if __name__ == "__main__":
    main()
