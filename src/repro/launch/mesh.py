"""Production mesh definition (TPU v5e, 256 chips/pod).

Defined as a FUNCTION so importing this module never touches jax device
state — device count is locked on first jax init, and only dryrun.py (which
sets XLA_FLAGS before any import) may build the 256/512-device meshes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def parse_mesh(spec: str):
    """Build a ("data", "model") mesh from a ``DxM`` flag string (e.g.
    ``8x1``, ``4x2``) — the serving launcher's ``--mesh``.  The product
    must not exceed the visible device count (force extra CPU devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
    parts = spec.lower().split("x")
    if len(parts) != 2:
        raise ValueError(f"--mesh expects DxM (e.g. 8x1), got {spec!r}")
    data, model = (int(p) for p in parts)
    have = jax.device_count()
    if data * model > have:
        raise ValueError(
            f"mesh {data}x{model} needs {data * model} devices, "
            f"{have} visible (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N "
            f"before jax initializes)")
    return make_local_mesh(data, model)


# TPU v5e hardware constants (per chip) — the roofline denominators.
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_LINK_BW = 50e9              # bytes/s per link
