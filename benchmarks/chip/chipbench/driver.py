"""One run of one cell: set-up, the closed-loop window, the drain, the check.

Each client owns a ``ModelOracle`` on the one engine and submits its next
ORDER BY plan (``ProbePlanExecutor.submit_path``) as soon as its last one
finishes.  The loop is ``executor.tick()`` plus the clients' hook, with
``bench.*`` spans around the tick, the hook, the scheduler's pump and the
engine's ``submit_probes``.  Client ``i`` starts at tick ``floor(i*T/C)``.

Set-up compiles (or loads) every shape the mix can put on the engine
before the loop starts (``traffic.prewarm_rounds``).  Warm-up is then this
same loop for the mix's fixed ``warmup_ticks``, and on until every client
has finished a query; the window opens there and closes ``--seconds``
later.  Queries still in flight then run to their end while the clients
keep the load up; queries submitted after the close are abandoned.  Only
each finished query's (submit, finish, ticks, error) is kept, and the
objects set-up made are frozen out of the garbage collector's passes, so
no collection over the run's history stalls the loop.  A traced run traces
the window's last ``TRACE_SECONDS``.
"""
from __future__ import annotations

import gc
import importlib.util
import shutil
import tempfile
import time
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Callable, Optional

from . import (BENCH_DIR, cell_metrics, find_cell, load_json, load_spec,
               use_program)
from .check import RowSampler, compare
from .instruments import CompileCounter, Instruments
from .model import init_params, load_architecture, load_config, model_config
from .traffic import ClientStream, load_traffic, prewarm_rounds
from .window import stagger_ticks

TRACE_SECONDS = 10.0
# the engine's jitted programs, which one process may share between runs
PROGRAMS = ("_prefill", "_prefill_exact", "_prefill_cont", "_decode",
            "_decode_paged")
# access paths whose probes are all score prompts (engine.score_parts)
SCORE_PATHS = ("pointwise", "ext_pointwise", "ext_merge")
# a run must end within 360 s: a warm-up this long opens the window as it
# stands, and a run this long stops and counts as not correct
WARMUP_LIMIT_S = 200.0
RUN_LIMIT_S = 330.0
PEAKS_FILE = BENCH_DIR / "peaks.json"
METRICS_DIR = BENCH_DIR / "metrics"


@dataclass
class Query:
    """A query in flight; once it finishes only its tuple is kept."""
    run: object
    submit: float


@dataclass
class Client:
    stream: ClientStream
    oracle: object
    start_tick: int
    current: Optional[Query] = None
    finished: int = 0


def load_reader(name: str) -> Callable:
    path = METRICS_DIR / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def engine_kwargs(cfg_spec: dict, rehearsal: bool) -> dict:
    return dict(cfg_spec["rehearsal"]["engine"] if rehearsal
                else cfg_spec["engine"])


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, rehearsal: bool = False,
             fault: Optional[Callable] = None, control: bool = False,
             shared: Optional[dict] = None) -> tuple[dict, dict]:
    """Run the cell ``name`` of ``BENCHMARK.json`` once; returns (result
    line, details)."""
    spec = load_spec()
    cell = find_cell(spec, name)
    return run_spec(cell, load_config(cell["config"]),
                    load_traffic(cell["traffic"]), spec, seed, seconds,
                    trace, t_start, rehearsal, fault, control, shared)


def run_spec(cell: dict, cfg_spec: dict, mix: dict, spec: dict, seed: int,
             seconds: float, trace: bool, t_start: float,
             rehearsal: bool = False, fault: Optional[Callable] = None,
             control: bool = False,
             shared: Optional[dict] = None) -> tuple[dict, dict]:
    """Run a cell given as its parts.  ``fault``, given the engine, breaks
    the timed path (tests); ``control`` puts the fp8 control in the
    program's place for the check, so that ``correct`` is decided on the
    control's logits of the kept rows (the program's own reading is kept
    beside it); ``shared`` carries the engine's jitted programs and the
    model from one run to the next in one process (calibration)."""
    name = cell["name"]
    t_enter = time.perf_counter()
    arch = load_architecture(cfg_spec)
    use_program()
    gc.collect()            # a run before this one in the same process
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    # every program, however quick to compile, comes from the cache after
    # the first run of a checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter.get()
    c_start = counter.snapshot()

    from repro.models import LM
    cfg = model_config(cfg_spec, rehearsal)
    lm = shared.setdefault("lm", LM(cfg)) if shared is not None else LM(cfg)
    params = init_params(lm, seed, arch)
    jax.block_until_ready(params)
    t_init = time.perf_counter()
    c_init = counter.snapshot()

    state = _serve(cell, cfg_spec, mix, arch, lm, params, seed, seconds,
                   trace, rehearsal, fault, counter, shared)
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in jax.local_devices()[:cell["chips"]]) or None
    gc.collect()

    dims = (arch.dims(cfg_spec) if not rehearsal
            else arch.dims_from_program(cfg))
    t_ref = time.perf_counter()
    reading = compare(state.rows, arch.Reference(params, dims, "float32"),
                      arch.Reference(params, dims, "fp8") if control
                      else None)
    reading["reference_s"] = time.perf_counter() - t_ref

    limits = cfg_spec["rehearsal" if rehearsal else "check"]["limits"]
    checks = {k: {"value": reading[k], "limit": lim}
              for k, lim in limits.items()}
    failed = sum(1 for q in state.queries if q[3] is not None)
    correct = (failed == 0 and not state.timed_out and reading["rows"] > 0
               and all(c["limit"] is not None and c["value"] <= c["limit"]
                       for c in checks.values()))

    peaks = load_json(PEAKS_FILE)["devices"]
    kind = jax.devices()[0].device_kind
    # what a metric reader (metrics/<name>.py) is given
    record = SimpleNamespace(
        cell=cell, mix=mix, arch=arch, dims=dims, seconds=seconds,
        state=state, peak=peaks.get(kind), setup_s=state.t_open - t_start)
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(spec, name, group):
        value = load_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": len(state.queries),
              "failed": failed, "metrics": metrics, "device": device}
    if trace and state.trace is not None:
        device["busy_s"] = state.trace["busy_s"]
        device["window_s"] = state.trace["window_s"]
        result["breakdown"] = {"device_ops": state.trace["device_ops"],
                               "idle_gaps": state.trace["idle_gaps"]}
    result["checks"] = checks

    setup = {
        "setup_s": record.setup_s,
        "start_s": t_enter - t_start,
        "init_s": t_init - t_enter,
        "init_programs": c_init[1] - c_start[1],
        "engine_s": state.t_engine - t_init,
        "prewarm_s": state.prewarm_s,
        "prewarm_programs": state.c_prewarm[1] - c_init[1],
        "loop_warmup_s": state.t_open - state.t_engine - state.prewarm_s,
        "warmup_ticks": state.warmup_ticks,
        "warmup_programs": state.c_open[1] - state.c_prewarm[1],
        "setup_programs": state.c_open[1] - c_start[1],
        "setup_compiled": (state.c_open[1] - c_start[1])
        - (state.c_open[3] - c_start[3]),
        "setup_program_s": state.c_open[2] - c_start[2],
        "compile_cache": cache_dir,
    }
    details = {"setup": setup, "reading": reading,
               "window": state.summary, "trace": state.trace}
    return result, details


def _serve(cell, cfg_spec, mix, arch, lm, params, seed, seconds, trace,
           rehearsal, fault, counter, shared) -> SimpleNamespace:
    """Build the engine, drive warm-up, window and drain, and return what
    the metrics and the check read; the program's state is dropped when
    this returns."""
    from repro.core.access_paths.base import PathParams, make_path
    from repro.core.executor import ProbePlanExecutor
    from repro.core.oracles.model_oracle import ModelOracle
    from repro.core.types import Key, SortSpec
    from repro.serving import BatchScheduler, ServeEngine

    clock = time.perf_counter
    engine = ServeEngine(lm, params, **engine_kwargs(cfg_spec, rehearsal))
    if shared is not None:
        for attr in PROGRAMS:
            if attr in shared:
                setattr(engine, attr, shared[attr])
            elif hasattr(engine, attr):
                shared[attr] = getattr(engine, attr)
    sched = BatchScheduler(engine)
    ex = ProbePlanExecutor(scheduler=sched)
    t_engine = clock()
    if fault is not None:
        fault(engine)
    t_prewarm = clock()
    if mix["path"] in SCORE_PATHS:
        for fill, rounds in prewarm_rounds(mix, engine.score_parts,
                                           engine.max_probe_batch):
            engine.clear_prefix_cache()
            engine.prefetch_prefixes(fill)
            for prompts in rounds:
                engine.submit_probes(prompts)
        engine.clear_prefix_cache()
    prewarm_s = clock() - t_prewarm
    c_prewarm = counter.snapshot()
    check = cfg_spec["check"]
    sampler = RowSampler(seed, check["rows_per_class"])
    ins = Instruments(engine, sched, arch, sampler, clock=clock,
                      tracing=trace)
    ins.attach()

    n = mix["clients"]
    starts = stagger_ticks(n, mix["ticks_per_query"])
    clients = [Client(ClientStream(mix, seed, i), ModelOracle(engine),
                      starts[i]) for i in range(n)]
    done: list[tuple] = []          # (submit, finish, ticks, error)

    def submit(c: Client, now: float) -> None:
        q = c.stream.next()
        path = make_path(q.path, PathParams(**dict(q.path_params)))
        keys = [Key(uid=i, text=t) for i, t in enumerate(q.texts)]
        run = ex.submit_path(path, keys, c.oracle,
                             SortSpec(q.criteria, q.descending, q.limit),
                             name=f"c{q.client}.{q.index}")
        c.current = Query(run, now)

    phase, tick = "warmup", 0
    warmup_ticks = None
    t_open = t_close = None
    stats_open = stats_close = c_open = c_close = None
    tracer = None
    trace_stop_s = None
    ins.phase = "warmup"
    timed_out = False
    while True:
        now = clock()
        with ins.span("bench.on_tick"):
            for c in clients:
                if c.current is None and tick >= c.start_tick:
                    submit(c, now)
        with ins.span("bench.tick"):
            ex.tick()
        done_at = clock()
        with ins.span("bench.on_tick"):
            for c in clients:
                q = c.current
                if q is not None and q.run.done:
                    err = q.run.error
                    done.append((q.submit, done_at, q.run.ticks,
                                 None if err is None else repr(err)))
                    c.current = None
                    c.finished += 1
            ex.runs = [r for r in ex.runs if not r.done]
        tick += 1
        now = clock()
        if phase == "warmup":
            ready = (tick >= mix["warmup_ticks"]
                     and all(c.finished for c in clients))
            if ready or now - t_engine > WARMUP_LIMIT_S:
                phase = ins.phase = "window"
                warmup_ticks = tick
                # what set-up made lives to the end of the run: keep it
                # out of the collector's passes
                gc.collect()
                gc.freeze()
                t_open = clock()
                t_close = t_open + seconds
                stats_open, c_open = ins.stats(), counter.snapshot()
        elif phase == "window":
            if trace and tracer is None and now >= t_close - TRACE_SECONDS:
                tracer = _Tracer(ins, arch.trunk_marker(lm.cfg))
            if now >= t_close:
                phase = ins.phase = "drain"
                stats_close, c_close = ins.stats(), counter.snapshot()
                if tracer is not None:
                    t_stop = clock()
                    tracer.stop()
                    trace_stop_s = clock() - t_stop
        if phase == "drain":
            # the clients' load is kept up by queries submitted before the
            # close; the run ends when the last of them has finished
            if all(c.current is None or c.current.submit > t_close
                   for c in clients):
                break
        if now - t_engine > RUN_LIMIT_S:
            timed_out = True
            break
    for c in clients:
        if c.current is not None and not c.current.run.done:
            c.current.run.cancel("abandoned after the window")
    gc.unfreeze()
    # the queries whose span meets the window: everything credited
    counted = [q for q in done if t_close is not None
               and q[0] <= t_close and q[1] >= t_open]
    drain_end = clock()
    reduced = None
    if tracer is not None:
        reduced = tracer.reduce()
    summary = {
        "ticks": tick, "warmup_ticks": warmup_ticks,
        "queries_counted": len(counted),
        "mean_ticks": (sum(q[2] for q in counted) / len(counted)
                       if counted else None),
        "drain_s": drain_end - t_close if t_close else None,
        "window_programs": (c_close[1] - c_open[1]) if c_close else None,
        "trace_stop_s": trace_stop_s,
        "samples_offered": sampler.offered,
    }
    state = SimpleNamespace(
        t_engine=t_engine, prewarm_s=prewarm_s, c_prewarm=c_prewarm,
        t_open=t_open if t_open else clock(),
        t_close=t_close, warmup_ticks=warmup_ticks, queries=counted,
        stats_open=stats_open, stats_close=stats_close,
        c_open=c_open or counter.snapshot(), c_close=c_close,
        submissions=list(ins.submissions), calls=list(ins.calls),
        trace=reduced, rows=sampler.rows(), timed_out=timed_out,
        summary=summary)
    # the plans hold the oracles and so the engine: drop every path to the
    # program's device state before the reference runs
    for c in clients:
        c.oracle = c.current = None
    del clients, done, counted, ex, sched, engine, ins, submit, tracer
    gc.collect()
    return state


class _Tracer:
    """The profiler over the window's last seconds, with the
    ``bench.window`` span marking the traced window on the trace's clock."""

    def __init__(self, ins: Instruments, marker: str):
        import jax
        self.ins = ins
        self.marker = marker
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(self.dir)
        self.window = jax.profiler.TraceAnnotation("bench.window")
        self.window.__enter__()
        ins.in_trace = True

    def stop(self) -> None:
        import jax
        self.window.__exit__(None, None, None)
        self.ins.in_trace = False
        jax.profiler.stop_trace()

    def reduce(self) -> Optional[dict]:
        from . import trace_reduce
        try:
            return trace_reduce.reduce(trace_reduce.load(self.dir),
                                       self.marker)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
