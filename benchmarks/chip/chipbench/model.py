"""Model configurations (``configs/<name>.json``), their architectures, and
the weights.

A configuration file holds the published ``config.json`` keys as they are
run, the architecture module that knows them (``"architecture"``), the
registry name of the program's preset that runs them, any ``ModelConfig``
overrides, the engine sizing, and the check's limits.  :func:`model_config`
builds the program's ``ModelConfig`` and has the architecture refuse to go
on when it disagrees with the file.

An architecture is one module, ``architectures/<name>.py``, found by name
as a metric's reader is.  It holds every piece of the harness that depends
on the architecture; :data:`ARCH_CONTRACT` lists what it provides:

- ``check_program(cfg, spec)``: raise ``SystemExit`` where the program's
  ``ModelConfig`` does not run the file's published keys, or has a shape
  the module does not take;
- ``dims(spec)`` and ``dims_from_program(cfg)``: the sizes its reference
  and work counts read, from the file, or from the program's reduced
  preset in a rehearsal;
- ``Reference(params, dims, precision)`` with ``.logits(rows)``:
  ``precision`` is ``"float32"``, or ``"fp8"`` for the control
  (``reference.RowBlocked`` holds the shared row blocking);
- ``prefill_flops(dims, rows, new, cached)``,
  ``prefill_bytes(dims, rows, new, cached, reserve)`` and
  ``model_flops(dims, live_tokens, live_rows, attn_pairs)``: the work counts
  the metric readers take from ``record.arch``;
- ``cached_positions(prefix_cache)``: the key/value positions the caches
  given to a continued prefill hold;
- ``trunk_marker(cfg)``: text in the HLO of the programs that run the layer
  stack, which picks the prefill programs out of a trace;
- optionally ``draw(name, shape, key)``: the leaf ``name`` of the weights
  (``shape`` its ``jax.ShapeDtypeStruct``) drawn from ``key`` in its dtype,
  for a leaf that the generic rule of :func:`init_params` cannot draw; or
  None, which leaves the leaf to that rule.

The weights are the benchmark's, not the program's: one jitted call draws
every leaf of the program's parameter layout from ``--seed`` on the device,
in the dtype it is served in.  The plain reference reads the same arrays.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import math
import re
import sys
from pathlib import Path

from . import BENCH_DIR, ROOT, load_json
from .traffic import seed_words

CONFIG_DIR = BENCH_DIR / "configs"
ARCH_DIR = BENCH_DIR / "architectures"
ARCH_CONTRACT = ("check_program", "dims", "dims_from_program", "Reference",
                 "prefill_flops", "prefill_bytes", "model_flops",
                 "cached_positions", "trunk_marker")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")

# scale of the random RMSNorm gains (the program applies 1 + gain), so the
# check sees the gains used
NORM_GAIN_STD = 0.05


def load_config(name: str) -> dict:
    return load_json(CONFIG_DIR / f"{name}.json")


def load_architecture(spec: dict):
    """The module of the architecture a configuration file names under
    ``"architecture"``: ``architectures/<name>.py``.  A file without the
    key, or one whose name is no module there, is refused; there is no
    default."""
    where = (CONFIG_DIR / f"{spec.get('name')}.json").relative_to(ROOT)
    name = spec.get("architecture")
    path = ARCH_DIR / f"{name}.py"
    if not isinstance(name, str) or not NAME.fullmatch(name) \
            or not path.is_file():
        known = ", ".join(sorted(p.stem for p in ARCH_DIR.glob("*.py")))
        raise SystemExit(f"{where}: \"architecture\" is {name!r}, which "
                         f"names no module in {ARCH_DIR.relative_to(ROOT)}/ "
                         f"({known})")
    return load_architecture_file(path)


def load_architecture_file(path: Path):
    """Load an architecture module from its file, as ``chipbench_arch_<stem>``,
    and refuse one that lacks a name of :data:`ARCH_CONTRACT`."""
    name = "chipbench_arch_" + re.sub(r"\W", "_", path.stem)
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod
    mod_spec.loader.exec_module(mod)
    missing = [f for f in ARCH_CONTRACT if not callable(getattr(mod, f, None))]
    if missing:
        del sys.modules[name]
        raise SystemExit(f"architecture {path.name} lacks "
                         f"{', '.join(missing)} of the contract")
    return mod


def model_config(spec: dict, rehearsal: bool = False):
    """The program's ``ModelConfig`` for a configuration file: the registry
    preset with the file's overrides.  ``rehearsal`` takes the registry's
    reduced CPU preset instead and skips the architecture's check."""
    from repro.configs import get_config, get_reduced
    if rehearsal:
        return dataclasses.replace(get_reduced(spec["registry"]),
                                   **spec["rehearsal"].get("overrides", {}))
    cfg = dataclasses.replace(get_config(spec["registry"]),
                              **spec.get("overrides", {}))
    load_architecture(spec).check_program(cfg, spec)
    return cfg


def _leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def init_params(lm, seed: int, arch):
    """Random weights for ``lm`` in the program's layout, drawn from
    ``seed`` in one jitted call on the default device.  Where the
    architecture module has a ``draw``, a leaf it returns is taken as it
    is; the generic rule draws the rest: matrices normal with standard
    deviation 1/sqrt(fan-in), the output projections ``wo`` and ``w_down``
    scaled by 1/sqrt(2 * layers) as the program's own init does, norm gains
    small normals.  A leaf that neither draws (not a norm gain, and under
    two axes) is refused by name."""
    import jax
    import jax.numpy as jnp

    cfg = lm.cfg
    shapes = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    depth = 1.0 / math.sqrt(2.0 * cfg.n_layers)
    arch_draw = getattr(arch, "draw", None)

    def draw(key, name, s):
        if arch_draw is not None:
            leaf = arch_draw(name, s, key)
            if leaf is not None:
                return leaf
        last = name.rsplit("/", 1)[-1]
        if "norm" in last:
            return (NORM_GAIN_STD * jax.random.normal(key, s.shape,
                                                      jnp.float32)
                    ).astype(s.dtype)
        if len(s.shape) < 2:
            raise ValueError(
                f"init_params: no rule draws the leaf {name} of shape "
                f"{tuple(s.shape)}: not a norm gain nor a matrix, and "
                f"{getattr(arch, '__name__', arch)} gives no draw for it")
        fan_in = s.shape[-1] if last == "embed" else s.shape[-2]
        std = 1.0 / math.sqrt(fan_in)
        if last in ("wo", "w_down"):
            std *= depth
        return (jax.random.normal(key, s.shape, jnp.float32) * std
                ).astype(s.dtype)

    names = [_leaf_name(p) for p, _ in flat]

    def make(key):
        keys = jax.random.split(key, len(flat))
        return treedef.unflatten([draw(k, n, s) for k, n, (_, s)
                                  in zip(keys, names, flat)])

    w = seed_words(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(w[0]), w[1])
    return jax.jit(make)(key)
