#!/usr/bin/env python3
"""rehearse.py's small CPU run of a cell, with the configuration's
architecture replaced by a module of another directory, traced, and with a
peaks table that names the CPU, so that the per-layer readers run too.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/tests/rehearse_architecture.py \
        --workload stablelm-1.6b.pointwise-tweets --seed 5 \
        --arch-dir benchmarks/chip/tests/architectures \
        --architecture recording --peaks PEAKS.json

The configuration file is the cell's own with only ``"architecture"``
changed, and the module is found by that name in ``--arch-dir``.  Prints
one JSON line: ``correct``, the checks, the per-layer metrics, the reading,
and the module's ``CALLS`` where it counts them.  The peaks table is for
this rehearsal only: no number it gives is a device's.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import find_cell, load_spec  # noqa: E402
from chipbench import driver, model  # noqa: E402
from chipbench.traffic import load_traffic  # noqa: E402
from rehearse import SECONDS, SMALL_MIX  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--arch-dir", required=True, type=Path)
    ap.add_argument("--architecture", required=True)
    ap.add_argument("--peaks", required=True, type=Path)
    args = ap.parse_args()
    spec = load_spec()
    cell = find_cell(spec, args.workload)
    cfg_spec = {**model.load_config(cell["config"]),
                "architecture": args.architecture}
    model.ARCH_DIR = args.arch_dir.resolve()
    driver.PEAKS_FILE = args.peaks
    mix = {**load_traffic(cell["traffic"]), **SMALL_MIX}
    result, details = driver.run_spec(
        cell, cfg_spec, mix, spec, args.seed, SECONDS, True,
        time.perf_counter(), rehearsal=True)
    arch = sys.modules["chipbench_arch_" + args.architecture]
    print(json.dumps({"correct": result["correct"],
                      "checks": result["checks"],
                      "metrics": result["metrics"],
                      "reading": details["reading"],
                      "window": details["window"],
                      "calls": dict(getattr(arch, "CALLS", {}))},
                     default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
