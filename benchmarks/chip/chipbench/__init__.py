"""The chip benchmark's yardstick: spec loading, traffic generation, the
closed-loop driver, the window arithmetic, trace reduction, the roofline,
peaks and what every architecture's plain float32 reference shares.

Everything a cell needs is found by name from ``BENCHMARK.json``:
``configs/<config>.json``, the architecture module that configuration
names (``architectures/<architecture>.py``: its program check, sizes,
reference, work counts and cache reading; ``model.py`` lists the
contract), ``traffic/<traffic>.json`` and one reader per metric in
``metrics/<metric>.py``.  Adding a cell, a configuration, an architecture
or a metric adds files and entries; it edits nothing here.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]      # benchmarks/chip
ROOT = BENCH_DIR.parents[1]                          # the checkout
SPEC_FILE = ROOT / "BENCHMARK.json"


def use_program() -> None:
    """Make the system under test (``<checkout>/src``) importable."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec() -> dict:
    return load_json(SPEC_FILE)


def find_cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in {SPEC_FILE.name}")


def cell_metrics(spec: dict, cell: str, group: str) -> list[dict]:
    """The metrics of ``group`` ("end_to_end" or "per_layer") this cell
    reports: those without a ``workloads`` key, and those that list it."""
    return [m for m in spec[group]
            if "workloads" not in m or cell in m["workloads"]]
