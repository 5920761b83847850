"""The program's own host spans (``repro.*``) in a profiler trace.

The serving path wraps its host work in ``jax.profiler.TraceAnnotation``
spans named ``repro.<layer>.<what>`` (``src/repro/trace.py``).
:func:`extract` reads them from a ``ProfileData`` as (name, start,
duration, thread).  :func:`reduce` takes them with the lists
:func:`trace_reduce.extract` makes and returns, over the traced window:

- ``program_spans``: for each name the count of spans that meet the
  window, their total seconds and their self seconds (the total less what
  their child spans on the same thread cover), both clipped to the window;
- ``program_idle``: device 0's idle time split over the innermost program
  span covering each part of each gap, by a sweep over the spans'
  boundaries; idle time under no program span goes to ``NO_SPAN``.

:func:`readings` turns these into per-layer numbers.  Times are
nanoseconds on the trace's clock in, seconds out.
"""
from __future__ import annotations

from typing import Optional

from . import trace_reduce

PREFIX = "repro."
NO_SPAN = "(no program span)"
ENGINE = ("repro.engine.", "repro.pool.")
PLAN = ("repro.executor.", "repro.scheduler.")
TOP = 10


def extract(pd) -> list:
    """The ``repro.*`` host events of a ``ProfileData``, as (name, start,
    duration, thread) with the thread named by its plane and line."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            thread = f"{plane.name}#{k}"
            out.extend((e.name, float(e.start_ns), float(e.duration_ns),
                        thread)
                       for e in line.events if e.name.startswith(PREFIX))
    return out


def span_table(spans, lo: float, hi: float) -> dict:
    """{name: {"count", "total_s", "self_s"}} over the window [lo, hi]."""
    table: dict[str, dict] = {}
    clipped = []
    for name, s, d, thread in spans:
        a, b = max(s, lo), min(s + d, hi)
        if b < a:
            continue
        row = table.setdefault(name, {"count": 0, "total_s": 0.0,
                                      "self_s": 0.0})
        row["count"] += 1
        row["total_s"] += b - a
        row["self_s"] += b - a
        clipped.append((thread, s, -(s + d), a, b, name))
    # parents before their children: by thread, start, then the longer
    clipped.sort()
    stack: list = []           # open spans of the current thread
    for thread, s, neg_end, a, b, name in clipped:
        while stack and (stack[-1][0] != thread or stack[-1][1] <= s):
            stack.pop()
        if stack:
            table[stack[-1][2]]["self_s"] -= b - a
        stack.append((thread, -neg_end, name))
    ns = 1e-9
    return {name: {"count": r["count"], "total_s": r["total_s"] * ns,
                   "self_s": r["self_s"] * ns} for name, r in table.items()}


def segments(spans, lo: float, hi: float) -> list:
    """[lo, hi] cut at every span boundary into (start, end, name) of the
    innermost span open over each piece: the one opened last."""
    events = []
    for i, (_, s, d, _) in enumerate(spans):
        a, b = max(s, lo), min(s + d, hi)
        if a < b:
            # at one instant closes come first, and a parent opens before
            # the child that starts with it
            events.append((a, 1, -b, i))
            events.append((b, 0, 0.0, i))
    events.sort()
    out, open_, t = [], [], lo
    for when, opens, _, i in events:
        if when > t:
            out.append((t, when, spans[open_[-1]][0] if open_ else NO_SPAN))
            t = when
        if opens:
            open_.append(i)
        else:
            open_.remove(i)
    if t < hi:
        out.append((t, hi, NO_SPAN))
    return out


def split_idle(idle, pieces) -> dict:
    """Seconds of the gaps ``idle`` ([(start, end)], sorted) under each
    name of ``pieces`` (:func:`segments`)."""
    by_name: dict[str, float] = {}
    j = 0
    for gs, ge in idle:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            a, b = max(gs, pieces[k][0]), min(ge, pieces[k][1])
            if b > a:
                name = pieces[k][2]
                by_name[name] = by_name.get(name, 0.0) + (b - a) * 1e-9
            k += 1
    return by_name


def reduce(trace: dict, program: list) -> Optional[dict]:
    """``program_spans`` and ``program_idle`` (every name, the largest
    first) over the traced window of ``trace`` (the lists of
    :func:`trace_reduce.extract`), from the program's spans ``program``
    (:func:`extract`); None where the trace has no window or no device."""
    win = trace_reduce.window_of(trace)
    if win is None or not trace["devices"]:
        return None
    lo, hi = win
    dev0 = sorted(trace["devices"].items())[0][1]
    idle = trace_reduce.gaps(trace_reduce.union(dev0["ops"], lo, hi), lo, hi)
    by_name = split_idle(idle, segments(program, lo, hi))
    return {
        "window_s": (hi - lo) * 1e-9,
        "program_spans": span_table(program, lo, hi),
        "program_idle": [[k, v] for k, v in
                         sorted(by_name.items(), key=lambda kv: -kv[1])],
    }


def readings(red: dict) -> dict:
    """The per-layer numbers of one reduction, None where the program had
    no spans to read:

    - ``prep_ms``: self time of ``engine.encode``, ``engine.plan`` and
      ``engine.pad`` per ``engine.dispatch`` (a padded submission, probe
      or fill), in ms;
    - ``to_host_ms``: time in ``engine.to_host`` per read-back, in ms;
    - ``idle_engine_share`` and ``idle_plan_share``: device 0's idle time
      under ``repro.engine.*`` and ``repro.pool.*`` spans, and under
      ``repro.executor.*`` and ``repro.scheduler.*`` spans, over the
      window, in %;
    - ``no_span_idle_share``: idle time under no program span over all
      idle time, in %."""
    spans = red["program_spans"]
    idle = dict(red["program_idle"])
    out = dict.fromkeys(("prep_ms", "to_host_ms", "idle_engine_share",
                         "idle_plan_share", "no_span_idle_share"))
    if not spans:
        return out
    dispatch = spans.get("repro.engine.dispatch", {}).get("count", 0)
    if dispatch:
        prep = sum(spans.get(f"repro.engine.{k}", {}).get("self_s", 0.0)
                   for k in ("encode", "plan", "pad"))
        out["prep_ms"] = 1e3 * prep / dispatch
    to_host = spans.get("repro.engine.to_host")
    if to_host:
        out["to_host_ms"] = 1e3 * to_host["total_s"] / to_host["count"]
    w = red["window_s"]
    out["idle_engine_share"] = 100.0 * sum(
        v for k, v in idle.items() if k.startswith(ENGINE)) / w
    out["idle_plan_share"] = 100.0 * sum(
        v for k, v in idle.items() if k.startswith(PLAN)) / w
    total = sum(idle.values())
    if total:
        out["no_span_idle_share"] = 100.0 * idle.get(NO_SPAN, 0.0) / total
    return out
