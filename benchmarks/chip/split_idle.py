#!/usr/bin/env python3
"""Run one cell traced, as ``run.py --trace 1`` does, and split the
device's idle time over the program's own spans.

    python3 benchmarks/chip/split_idle.py --workload <config>.<traffic> \
        --seed <n> --seconds <s>

The trace reduction (``chipbench/trace_reduce.py``) keeps the harness's
``bench.*`` host spans only.  For the run this script makes, it also keeps
the program's ``repro.*`` spans and reduces them with
``chipbench/program_trace.py``: before run.py's own lines, standard error
carries one ``program: {...}`` JSON line (``program_spans``,
``program_idle`` and the readings) and the ten program spans that hold
the most device idle time.  Standard output is run.py's result line.
"""
from __future__ import annotations

import json
import sys

import run  # noqa: F401  (its clock starts at this import)
from chipbench import program_trace, trace_reduce


def report(split: dict, out) -> None:
    red = dict(split, readings=program_trace.readings(split))
    print("program: " + json.dumps(red), file=out)
    for name, s in split["program_idle"][:program_trace.TOP]:
        print(f"program_idle {name}: {s:.6f} s of "
              f"{split['window_s']:.6f} s", file=out)


def main(argv=None) -> int:
    base_extract, base_reduce = trace_reduce.extract, trace_reduce.reduce

    def extract(pd) -> dict:
        trace = base_extract(pd)
        trace["program"] = program_trace.extract(pd)
        return trace

    def reduce(trace: dict, marker=None):
        split = program_trace.reduce(trace, trace.get("program", []))
        if split is not None:
            report(split, sys.stderr)
        return base_reduce(trace, marker)

    trace_reduce.extract, trace_reduce.reduce = extract, reduce
    args = list(sys.argv[1:] if argv is None else argv)
    return run.main(args + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
