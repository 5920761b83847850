"""program_trace on the program's own spans: a hand-made trace with known
answers, and a reduced engine's probe rounds profiled on the CPU."""
import glob
import os
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import program_trace as pt  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402


def hand_trace():
    # window 0..100 ns; the device is busy 10-30 and 60-80, so idle 0-10,
    # 30-60 and 80-100; the harness's spans as in test_chipbench_trace.py
    return {
        "devices": {"/device:TPU:0": {
            "ops": [("fusion.1 = bf16[8,128]{1,0} fusion(%w)", 10.0, 20.0),
                    ("fusion.3 = bf16[8,128]{1,0} fusion(%w)", 60.0, 20.0)],
            "modules": []}},
        "host": [("bench.window", 0.0, 100.0),
                 ("bench.tick", 0.0, 100.0),
                 ("bench.submit_probes", 20.0, 50.0)],
    }


# one host thread: a tick that outlasts the window, holding a step that
# holds a submission, and an advance cut by the window's end; a dispatch
# after the window
T = "/host:CPU#0"
PROGRAM = [("repro.executor.tick", 0.0, 130.0, T),
           ("repro.scheduler.step", 5.0, 90.0, T),
           ("repro.engine.submit_probes", 20.0, 50.0, T),
           ("repro.engine.pad", 20.0, 20.0, T),
           ("repro.engine.dispatch", 40.0, 5.0, T),
           ("repro.engine.to_host", 50.0, 20.0, T),
           ("repro.executor.advance", 96.0, 24.0, T),
           ("repro.engine.dispatch", 150.0, 10.0, T)]


def ns(x):
    return pytest.approx(x * 1e-9)


def test_span_counts_totals_and_self_times():
    spans = pt.reduce(hand_trace(), PROGRAM)["program_spans"]
    want = {"repro.executor.tick": (1, 100, 6),
            "repro.scheduler.step": (1, 90, 40),
            "repro.engine.submit_probes": (1, 50, 5),
            "repro.engine.pad": (1, 20, 20),
            "repro.engine.dispatch": (1, 5, 5),
            "repro.engine.to_host": (1, 20, 20),
            "repro.executor.advance": (1, 4, 4)}
    assert set(spans) == set(want)
    for name, (count, total, self_) in want.items():
        assert spans[name] == {"count": count, "total_s": ns(total),
                               "self_s": ns(self_)}, name


def test_idle_gaps_are_split_at_span_boundaries():
    red = pt.reduce(hand_trace(), PROGRAM)
    idle = dict(red["program_idle"])
    # the gap 30-60 crosses pad, dispatch, the submission's own time and
    # to_host; 0-10 the tick then the step; 80-100 the step, the tick and
    # the advance
    assert idle == {"repro.engine.pad": ns(10),
                    "repro.engine.dispatch": ns(5),
                    "repro.engine.submit_probes": ns(5),
                    "repro.engine.to_host": ns(10),
                    "repro.executor.tick": ns(6),
                    "repro.scheduler.step": ns(20),
                    "repro.executor.advance": ns(4)}
    assert red["program_idle"][0][0] == "repro.scheduler.step"
    assert sum(idle.values()) == ns(60)


def test_harness_idle_gaps_unchanged():
    trace = hand_trace()
    before = tr.reduce(trace)
    trace["program"] = PROGRAM
    pt.reduce(trace, PROGRAM)
    after = tr.reduce(trace)
    assert after == before
    assert dict(after["idle_gaps"]) == {"bench.submit_probes": ns(30),
                                        "bench.tick": ns(30)}


def test_readings():
    got = pt.readings(pt.reduce(hand_trace(), PROGRAM))
    assert got["prep_ms"] == pytest.approx(20e-9 * 1e3)      # pad self / 1
    assert got["to_host_ms"] == pytest.approx(20e-9 * 1e3)
    assert got["idle_engine_share"] == pytest.approx(30.0)
    assert got["idle_plan_share"] == pytest.approx(30.0)
    assert got["no_span_idle_share"] == pytest.approx(0.0)


def test_idle_under_no_span_and_a_program_without_spans():
    red = pt.reduce(hand_trace(), [])
    assert red["program_idle"] == [[pt.NO_SPAN, ns(60)]]
    assert all(v is None for v in pt.readings(red).values())
    # an idle stretch the spans leave uncovered
    red = pt.reduce(hand_trace(), [("repro.engine.pad", 30.0, 10.0, T)])
    assert dict(red["program_idle"]) == {"repro.engine.pad": ns(10),
                                         pt.NO_SPAN: ns(50)}
    assert pt.readings(red)["no_span_idle_share"] == pytest.approx(500 / 6)
    assert pt.reduce({"devices": {}, "host": []}, PROGRAM) is None


def test_spans_on_another_thread_are_not_children():
    spans = pt.span_table([("repro.executor.tick", 0.0, 50.0, "a"),
                           ("repro.engine.pad", 10.0, 10.0, "b")], 0.0, 100.0)
    assert spans["repro.executor.tick"]["self_s"] == ns(50)


# the innermost enclosing span each program span may have in a probe
# round driven by the executor
PARENTS = {
    "repro.executor.tick": {None},
    "repro.executor.advance": {"repro.executor.tick"},
    "repro.executor.prefetch": {"repro.executor.tick"},
    "repro.scheduler.step": {"repro.executor.tick"},
    "repro.scheduler.fills": {"repro.scheduler.step"},
    "repro.scheduler.probes": {"repro.scheduler.step"},
    "repro.engine.submit_probes": {"repro.scheduler.probes"},
    "repro.engine.encode": {"repro.engine.submit_probes"},
    "repro.engine.plan": {"repro.engine.submit_probes"},
    "repro.engine.fill": {"repro.engine.submit_probes",
                          "repro.scheduler.fills"},
    "repro.engine.pad": {"repro.engine.submit_probes", "repro.engine.fill"},
    "repro.engine.dispatch": {"repro.engine.submit_probes",
                              "repro.engine.fill"},
    "repro.engine.wait": {"repro.engine.submit_probes"},
    "repro.engine.to_host": {"repro.engine.submit_probes"},
    "repro.pool.write": {"repro.engine.fill"},
    "repro.engine.gather": {"repro.engine.submit_probes"},
    "repro.engine.assemble": {"repro.engine.submit_probes"},
    "repro.engine.paged_step": {"repro.scheduler.step"},
}


def parents(spans) -> list:
    """(name, innermost enclosing span's name or None) for each span."""
    out, stack = [], []
    for name, s, d, thread in sorted(spans,
                                     key=lambda e: (e[3], e[1], -e[2])):
        while stack and (stack[-1][2] != thread or stack[-1][1] <= s):
            stack.pop()
        out.append((name, stack[-1][0] if stack else None))
        stack.append((name, s + d, thread))
    return out


@pytest.fixture(scope="module")
def profiled_round(tmp_path_factory):
    """An ext_merge query over 12 keys on the reduced stablelm engine at
    the chip's engine sizing, profiled after a warm-up query and with the
    prefix cache emptied, so it fills, writes the pool and gathers."""
    import jax
    from jax.profiler import ProfileData

    from chipbench import use_program
    use_program()
    from repro.core.access_paths.base import PathParams, make_path
    from repro.core.executor import ProbePlanExecutor
    from repro.core.oracles.model_oracle import ModelOracle
    from repro.core.types import Key, SortSpec
    from repro.launch.serve import FULL_WIDTH_ENGINE, build_engine, build_lm
    from repro.serving import BatchScheduler

    lm, params = build_lm("stablelm-1.6b", full=False)
    eng = build_engine(lm, params, full=False, **FULL_WIDTH_ENGINE)
    ex = ProbePlanExecutor(scheduler=BatchScheduler(eng))
    keys = [Key(uid=i, text=f"post {i} " + "word " * (i % 3))
            for i in range(12)]

    def query():
        ex.submit_path(make_path("ext_merge", PathParams()), keys,
                       ModelOracle(eng), SortSpec("joy", True, 5),
                       name="q")
        while ex.tick():
            pass

    query()
    eng.clear_prefix_cache()
    logdir = tmp_path_factory.mktemp("profile")
    before = eng.stats.calls + eng.stats.prefix_fill_submissions
    jax.profiler.start_trace(str(logdir))
    try:
        query()
    finally:
        jax.profiler.stop_trace()
    ran = eng.stats.calls + eng.stats.prefix_fill_submissions - before
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return pt.extract(ProfileData.from_file(path)), ran


def test_profiled_round_nests_as_the_program_does(profiled_round):
    spans, _ = profiled_round
    seen = parents(spans)
    for name, parent in seen:
        assert parent in PARENTS[name], (name, parent)
    names = {n for n, _ in seen}
    assert names >= set(PARENTS) - {"repro.engine.paged_step"}


def test_profiled_round_dispatches_once_per_submission(profiled_round):
    spans, ran = profiled_round
    dispatches = [e for e in spans if e[0] == "repro.engine.dispatch"]
    assert ran > 0 and len(dispatches) == ran
    table = pt.span_table(spans, min(e[1] for e in spans),
                          max(e[1] + e[2] for e in spans))
    assert table["repro.engine.dispatch"]["count"] == ran
    assert table["repro.engine.to_host"]["count"] == \
        table["repro.engine.wait"]["count"]
