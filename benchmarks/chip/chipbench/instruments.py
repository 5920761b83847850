"""The harness's spans and counters around the system under test.

Spans are ``jax.profiler.TraceAnnotation``s named ``bench.*`` while a
traced run's profiler is on, so the trace reduction can attribute device
idle gaps to them; otherwise they are plain host-clock timings.  Counters
read the program's own ``ServeStats`` and JAX's compile events.

The engine is observed, not changed: ``submit_probes``, the three prefill
programs and ``_make_batch`` are wrapped on the instance to time calls,
record each program call's shape, and offer each served row to the
correctness sampler.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from .reference import PAD

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Process-wide counts of JAX program traces, backend compiles (a
    compile, or a load from the persistent cache) and cache hits."""

    _instance: Optional["CompileCounter"] = None

    def __init__(self):
        import jax
        self.traces = 0
        self.programs = 0
        self.program_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _duration(self, event, duration, **_kw):
        if event == COMPILE_EVENT:
            self.programs += 1
            self.program_s += duration
        elif event == TRACE_EVENT:
            self.traces += 1

    def _event(self, event, **_kw):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return (self.traces, self.programs, self.program_s, self.cache_hits)


@dataclass
class ProgramCall:
    """One call of a prefill program, as the harness saw it."""
    kind: str                 # prefill | prefill_exact | prefill_cont
    t: float                  # host clock at the call
    rows_p: int               # padded rows
    new: int                  # tokens per row computed
    cached: int               # key/value positions read from a cache
    reserve: int
    live_rows: int            # rows that hold a prompt
    live_tokens: int          # non-PAD tokens computed in live rows
    attn_pairs: int           # causal (query, key) pairs among live tokens
    traced: bool


@dataclass
class Instruments:
    engine: object
    sched: object
    arch: object                       # the configuration's architecture
    sampler: object = None
    clock: object = time.perf_counter
    tracing: bool = False
    phase: str = "setup"               # setup | warmup | window | drain
    in_trace: bool = False
    submissions: list = field(default_factory=list)   # (t0, t1) in window
    calls: list = field(default_factory=list)         # ProgramCall
    _tokens: Optional[np.ndarray] = None

    def span(self, name: str):
        if self.tracing:
            import jax
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def attach(self) -> None:
        eng, sched = self.engine, self.sched
        inner_submit = eng.submit_probes

        def submit_probes(prompts, max_batch=None):
            t0 = self.clock()
            with self.span("bench.submit_probes"):
                out = inner_submit(prompts, max_batch)
            if self.phase == "window":
                self.submissions.append((t0, self.clock()))
                if self.sampler is not None:
                    self.sampler.offer(prompts, out)
            return out

        eng.submit_probes = submit_probes
        inner_batch = eng._make_batch

        def make_batch(tokens):
            self._tokens = np.asarray(tokens)
            return inner_batch(tokens)

        eng._make_batch = make_batch
        reserve = eng.max_new
        for attr, kind, res in (("_prefill", "prefill", reserve),
                                ("_prefill_exact", "prefill_exact", 0),
                                ("_prefill_cont", "prefill_cont", 0)):
            setattr(eng, attr, self._recorded(getattr(eng, attr), kind, res))
        inner_pump = sched.pump

        def pump():
            with self.span("bench.pump"):
                return inner_pump()

        sched.pump = pump

    def _recorded(self, fn, kind: str, reserve: int):
        def call(*args):
            toks = self._tokens
            self._tokens = None
            cached = 0
            if kind == "prefill_cont":
                cached = self.arch.cached_positions(args[1])
            if toks is not None and self.phase == "window":
                self.calls.append(program_call(kind, self.clock(), toks,
                                               cached, reserve,
                                               self.in_trace))
            return fn(*args)
        return call

    def stats(self):
        return dataclasses.replace(self.engine.stats)


def program_call(kind: str, t: float, toks: np.ndarray, cached: int,
                 reserve: int, traced: bool) -> ProgramCall:
    live = toks != PAD
    per_row = live.sum(axis=1)
    live_rows = int((per_row > 0).sum())
    # causal pairs among the live tokens of each row's computed segment
    pairs = int((per_row * (per_row + 1) // 2).sum())
    return ProgramCall(kind=kind, t=t, rows_p=int(toks.shape[0]),
                       new=int(toks.shape[1]), cached=cached, reserve=reserve,
                       live_rows=live_rows, live_tokens=int(per_row.sum()),
                       attn_pairs=pairs, traced=traced)
