#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, in one process.

    python3 benchmarks/chip/run.py --workload <config>.<traffic> \
        --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (``configs/``), the architecture module that
configuration names (``architectures/``), its traffic mix (``traffic/``)
and its metrics (one reader each in ``metrics/``) are found by name from
``BENCHMARK.json``.  With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics read from a
profiler trace of the window's last seconds.  The last line of standard
output is the result as one JSON object; the numbers the check compared,
each beside its limit, are the last lines of standard error and the
result's last key.

Before them, standard error carries the run's set-up split, its window
summary and its readings, one ``key=value`` line each.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.  ``tests/rehearse.py`` drives the same run on
the CPU at the registry's reduced preset.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import ROOT, find_cell, load_spec  # noqa: E402


def fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = find_cell(load_spec(), args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the system under test is not in {ROOT / 'src'}")
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return fail(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < cell["chips"]:
        return fail(f"{cell['name']} needs {cell['chips']} chips, "
                    f"{len(devs)} visible")

    from chipbench.driver import run_cell
    result, details = run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), T_START)
    err = sys.stderr
    print("setup: " + " ".join(f"{k}={v}" for k, v in
                               details["setup"].items()), file=err)
    print("window: " + " ".join(f"{k}={v}" for k, v in
                                details["window"].items()), file=err)
    print("reading: " + " ".join(f"{k}={v}" for k, v in
                                 details["reading"].items()), file=err)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}", file=err,
              flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
