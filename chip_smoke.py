#!/usr/bin/env python3
"""Smoke run of the LLM ORDER BY serving path on a TPU, at full width.

    python chip_smoke.py              # one chip: stablelm-1.6b at published
                                      # widths, random weights from --seed
    python chip_smoke.py --chips 4    # only the mesh phase, on four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal [--chips 4]
                                      # reduced preset on the CPU; never
                                      # prints the ok line

One process drives the launcher's own constructors (``repro.launch.serve``):
engine + scheduler + ModelOracle + the five access paths, ``path="auto"``,
concurrent queries, judge-rationale decode through the paged loop, the
Pallas paged-decode kernel checked against the dense step, and the
identity contracts.  Every phase asserts; none carries on after a failure.
The seconds printed are host-clock smoke timings around blocking reads,
not metrics.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
For ``--chips 4`` run ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
with ``--cpu-rehearsal`` to rehearse on the CPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "stablelm-1.6b"
PATHS = ("pointwise", "ext_pointwise", "quick", "ext_bubble", "ext_merge")
N_KEYS = 24
# the largest padded class a tweets compare prompt lands in (two tweets of
# up to 40 words each under the byte tokenizer)
PROBE_CLASS = 1024
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
GiB = 1 << 30


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    raise SystemExit(2)


class Phases:
    """Per-phase host-clock wall seconds and XLA compile count/seconds."""

    def __init__(self, jax):
        self.jax = jax
        self.compiles = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kw):
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def peak_bytes(self):
        stats = self.jax.devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use")

    @contextmanager
    def phase(self, name: str):
        n0, c0, t0 = self.compiles, self.compile_s, time.perf_counter()
        print(f"[{name}]", flush=True)
        yield
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        print(f"[{name}] smoke timing: wall_s={wall} compile_s={comp} "
              f"run_s={wall - comp} compiles={self.compiles - n0} "
              f"peak_bytes_in_use={self.peak_bytes()}", flush=True)


def check_device(jax, args) -> dict:
    from repro.kernels import ops
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: {dev}", flush=True)
    if len(devs) < args.chips:
        _fail(f"needs {args.chips} devices, {len(devs)} visible")
    if args.cpu_rehearsal:
        return dev
    if dev["platform"] != "tpu":
        _fail(f"no TPU: JAX runs on {dev['platform']!r}")
    if ops.use_interpret():
        _fail("Pallas kernels would run in interpret mode on this device")
    return dev


def check_order(res, keys, limit=None) -> None:
    uids = [k.uid for k in res.order]
    want = len(keys) if limit is None else min(limit, len(keys))
    assert len(uids) == want == len(set(uids)), (res.path, uids)
    assert set(uids) <= {k.uid for k in keys}, (res.path, uids)


def finite_probes(engine, counter: list) -> None:
    """Make every probe submission of ``engine`` assert finite logits."""
    import numpy as np
    inner = engine.submit_probes

    def submit_probes(prompts, max_batch=None):
        out = inner(prompts, max_batch)
        assert np.isfinite(out).all(), "non-finite probe logits"
        counter[0] += out.shape[0]
        return out

    engine.submit_probes = submit_probes


def check_identity(name: str, a, b, bitwise: bool) -> None:
    """Print whether two logit arrays agree bitwise, and by how much not;
    assert bitwise equality, or the row-count tolerance where the chip
    breaks it.

    On a TPU the row count of a prefill changes how XLA tiles the trunk's
    matmuls, so a row's logits move with the size of its submission: by
    at most 0.0674 absolute (about one bf16 ulp of a logit) at
    stablelm-1.6b width on a v5e.  Batched == one-at-a-time, and with it
    data-parallel == one device at another row count per device, is
    bitwise on the CPU only; on the chip it is held to the repo's bf16
    reduction-order bound (TP_PSUM_*)."""
    import numpy as np

    from repro.serving.engine import TP_PSUM_ATOL, TP_PSUM_RTOL
    assert a.shape == b.shape and np.isfinite(a).all() and np.isfinite(b).all()
    same = bool(np.array_equal(a, b))
    diff = float(np.max(np.abs(a - b))) if a.size else 0.0
    print(f"  identity {name}: bitwise={same} max_abs_diff={diff}", flush=True)
    if bitwise:
        assert same, name
    else:
        np.testing.assert_allclose(a, b, rtol=TP_PSUM_RTOL, atol=TP_PSUM_ATOL,
                                   err_msg=name)


def one_chip(jax, args, ph: Phases) -> None:
    import numpy as np

    from repro.configs import get_config
    from repro.core import OrderQuery, datasets, llm_order_by, llm_order_by_many
    from repro.core.oracles.base import PromptParts
    from repro.core.oracles.model_oracle import ModelOracle
    from repro.launch.serve import (FULL_WIDTH_ENGINE, build_engine, build_lm,
                                    serving_memory)
    from repro.serving import BatchScheduler

    full = not args.cpu_rehearsal
    with ph.phase("build"):
        lm, params = build_lm(ARCH, full=full, seed=args.seed)
        jax.block_until_ready(params)
    cfg = lm.cfg
    if full:
        assert cfg == get_config(ARCH)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype} params={n_params}")

    # the rehearsal batches, pools and caches exactly as the chip run does
    kw = FULL_WIDTH_ENGINE
    mem = serving_memory(lm, PROBE_CLASS, **kw)
    print(f"engine {kw}; reckoning at class {PROBE_CLASS}: "
          + " ".join(f"{k}={v / GiB:.3f}GiB" for k, v in mem.items()))
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    if full:
        assert limit and mem["total"] < limit, (mem["total"], limit)
        print(f"  device bytes_limit={limit} ({limit / GiB:.3f}GiB)")

    engine = build_engine(lm, params, full=full, **kw)
    sched = BatchScheduler(engine)
    probed = [0]
    finite_probes(engine, probed)
    task = datasets.tweets(n=N_KEYS, seed=args.seed + 3)
    keys, criteria = task.keys, task.criteria
    oracles = []

    for path in PATHS:
        with ph.phase(f"order_by {path}"):
            oracle = ModelOracle(engine)
            res, _ = llm_order_by(keys, criteria, oracle, path=path,
                                  descending=True)
            check_order(res, keys)
            oracles.append(oracle)
            print(f"  {path}: calls={res.n_calls} submissions="
                  f"{engine.stats.calls} top5={[k.uid for k in res.order[:5]]}")

    with ph.phase("order_by auto"):
        oracle = ModelOracle(engine)
        res, rep = llm_order_by(keys, criteria, oracle, path="auto",
                                descending=True, limit=task.limit,
                                sample_size=8)
        check_order(res, keys, task.limit)
        oracles.append(oracle)
        print(f"  auto: chose={rep.chosen.label} reason={rep.reason} "
              f"calls={res.n_calls} top5={[k.uid for k in res.order[:5]]}")

    with ph.phase("order_by_many"):
        specs = [("quick", task.limit), ("ext_merge", None),
                 ("pointwise", task.limit)]
        queries = [OrderQuery(keys, criteria, ModelOracle(engine),
                              descending=True, path=p, limit=lim)
                   for p, lim in specs]
        results = llm_order_by_many(queries, scheduler=sched)
        for q, r in zip(queries, results):
            check_order(r, keys, q.limit)
            oracles.append(q.oracle)
            print(f"  many {q.path}: calls={r.n_calls} "
                  f"top5={[k.uid for k in r.order[:5]]}")
    assert all(o.ledger.records for o in oracles), "an empty ledger"
    assert engine.stats.prefix_hits > 0, engine.stats
    print(f"  probes={probed[0]} all finite; prefix_hits="
          f"{engine.stats.prefix_hits} misses={engine.stats.prefix_misses}")

    with ph.phase("judge rationale decode"):
        tokens0 = engine.stats.decode_tokens
        judge = ModelOracle(engine, scheduler=sched,
                            judge_rationale_tokens=16)
        best = judge.judge(keys, criteria, [r.order for r in results])
        jax.block_until_ready(engine.pool.arenas)
        decoded = engine.stats.decode_tokens - tokens0
        assert decoded > 0, engine.stats
        engine.clear_prefix_cache()
        assert engine.pool.blocks_in_use == 0, engine.pool.blocks_in_use
        assert engine.paged_active == 0
        print(f"  judge picked candidate {best}; decode_tokens={decoded} "
              f"leaked_blocks=0 pool_peak_blocks={engine.pool.peak_in_use}")

    with ph.phase("identity contracts"):
        on_cpu = jax.devices()[0].platform == "cpu"
        score = [PromptParts(*engine.score_parts(k.text, criteria))
                 for k in keys[:8]]
        plain = [p.prefix + p.suffix for p in score]
        batched = engine.submit_probes(plain)
        single = np.stack([engine.submit_probes([p])[0] for p in plain])
        check_identity("batched == one-at-a-time (monolithic rows)",
                       batched, single, bitwise=on_cpu)
        cached = engine.submit_probes(score)
        check_identity("prefix-cached == monolithic prefill", cached, batched,
                       bitwise=True)
        single_c = np.stack([engine.submit_probes([p])[0] for p in score])
        check_identity("batched == one-at-a-time (prefix-cached rows)",
                       cached, single_c, bitwise=on_cpu)
        engine.clear_prefix_cache()

    prompts = [PromptParts(f"Criteria: {criteria}\nRanking:",
                           f" {k.text[:40]}\nJudge rationale:")
               for k in keys[:4]]
    del sched, engine, judge, oracles, queries
    gc.collect()
    with ph.phase("paged kernel check"):
        eng_k = build_engine(lm, params, full=full, paged_kernel="check",
                             **kw)
        outs = eng_k.generate(prompts, max_new=8)
        jax.block_until_ready(eng_k.pool.arenas)
        assert eng_k.stats.decode_tokens > 0 and len(outs) == len(prompts)
        eng_k.clear_prefix_cache()
        assert eng_k.pool.blocks_in_use == 0
        print(f"  Pallas paged decode allclose to dense on "
              f"{eng_k.stats.decode_tokens} row-steps (rtol/atol "
              f"PAGED_KERNEL_RTOL/ATOL)")


def mesh_phase(jax, args, ph: Phases) -> None:
    """Single-device reference, then the same work on a 4x1 mesh and a 2x2
    mesh.  4x1 is bitwise the reference on the CPU and within the
    row-count tolerance on a TPU (see :func:`check_identity`), and bitwise
    a one-device run that submits each device's share of rows alone; 2x2
    adds the tensor-parallel psums and is held to TP_PSUM_RTOL/ATOL."""
    import numpy as np

    from repro.core import datasets, llm_order_by
    from repro.core.oracles.base import PromptParts
    from repro.core.oracles.model_oracle import ModelOracle
    from repro.launch.mesh import parse_mesh
    from repro.launch.serve import FULL_WIDTH_ENGINE, build_engine, build_lm
    from repro.serving import BatchScheduler

    full = not args.cpu_rehearsal
    lm, params = build_lm(ARCH, full=full, seed=args.seed)
    task = datasets.tweets(n=16, seed=args.seed + 3)
    keys, criteria = task.keys, task.criteria
    # eight plain prompts of one padded class: one 8-row submission runs 2
    # rows on each device of the 4x1 mesh, so the one-device run submits
    # them 2 at a time to run the same shapes
    flat = [f"{criteria}: {k.text}"[:96].ljust(96) for k in keys[:8]]

    def run(mesh, rows_per_call):
        engine = build_engine(lm, params, full=full, mesh=mesh,
                              **FULL_WIDTH_ENGINE)
        calls = engine.stats.calls
        out = {"flat": engine.submit_probes(flat, max_batch=rows_per_call)}
        assert engine.stats.calls - calls == len(flat) // rows_per_call
        probes = [PromptParts(*engine.score_parts(k.text, criteria))
                  for k in keys]
        out["logits"] = engine.submit_probes(probes)
        oracle = ModelOracle(engine)
        res, _ = llm_order_by(keys, criteria, oracle, path="quick",
                              descending=True)
        check_order(res, keys)
        out["quick"] = ([k.uid for k in res.order],
                        list(oracle.ledger.records))
        out["generate"] = BatchScheduler(engine).generate(
            [PromptParts(f"Criteria: {criteria}\nRanking:",
                         f" {k.text[:40]}\nJudge rationale:")
             for k in keys[:4]], max_new=8)
        engine.clear_prefix_cache()
        out["leaked"] = engine.pool.blocks_in_use
        out["stats"] = engine.stats
        assert np.isfinite(out["logits"]).all()
        return out

    runs = {}
    for name, spec, rows in (("one device", None, 2), ("4x1", "4x1", 8),
                             ("2x2", "2x2", 8)):
        with ph.phase(f"mesh {name}"):
            runs[name] = run(spec and parse_mesh(spec), rows)
            gc.collect()
    ref, dp, tp = runs["one device"], runs["4x1"], runs["2x2"]
    on_cpu = jax.devices()[0].platform == "cpu"
    print(f"  4x1 dp_sharded_submissions={dp['stats'].dp_sharded_submissions}"
          f"; leaked blocks 4x1={dp['leaked']} 2x2={tp['leaked']}")
    for name, got in (("4x1", dp), ("2x2", tp)):
        print(f"  {name} == one device: quick order="
              f"{got['quick'][0] == ref['quick'][0]} ledger="
              f"{got['quick'][1] == ref['quick'][1]} generate="
              f"{got['generate'] == ref['generate']}")
    check_identity("4x1 == one device", dp["logits"], ref["logits"],
                   bitwise=on_cpu)
    check_identity("2x2 vs one device", tp["logits"], ref["logits"],
                   bitwise=False)
    assert dp["leaked"] == tp["leaked"] == 0
    if on_cpu:
        assert dp["quick"] == ref["quick"] and dp["generate"] == ref["generate"]
    check_identity("4x1 at 8 rows == one device at 2 rows per call",
                   dp["flat"], ref["flat"], bitwise=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh phase, on four devices")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="reduced preset on the CPU; never prints the ok line")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    dev = check_device(jax, args)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    ph = Phases(jax)
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(jax, args, ph)
    else:
        one_chip(jax, args, ph)
    print(f"smoke total: wall_s={time.perf_counter() - t0} "
          f"compiles={ph.compiles} compile_s={ph.compile_s} "
          f"peak_bytes_in_use={ph.peak_bytes()}", flush=True)
    if args.cpu_rehearsal:
        print("cpu rehearsal passed (no ok line off the chip)")
        return
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
