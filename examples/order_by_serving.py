"""End-to-end serving driver: host a model on the serving engine, submit
batched requests through the scheduler, and execute LLM ORDER BY against the
pod-served model via the ModelOracle — the paper's production deployment
shape (the oracle is OUR model, not an external API).

Run:  PYTHONPATH=src python examples/order_by_serving.py [--arch stablelm-1.6b]
      [--full]   (published widths; the engine is sized for one TPU v5e)
"""
import argparse
import time

import jax

from repro.core import as_keys, llm_order_by
from repro.core.oracles.model_oracle import ModelOracle
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import add_model_args, build_engine, build_lm
from repro.serving import BatchScheduler

PASSAGES = [
    "bmt stands for bone marrow transplant, a medical procedure",
    "the weather in paris is mild in october",
    "bone marrow transplants treat leukemia and lymphoma",
    "bmt is also a subway line in new york city",
    "a transplant replaces damaged marrow with healthy stem cells",
    "stock markets closed higher on tuesday",
    "patients undergoing bmt need immunosuppression",
    "the recipe calls for two cups of flour",
    "marrow donation is coordinated through national registries",
    "football season begins in september",
    "graft-versus-host disease is a bmt complication",
    "the museum opens at nine daily",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    add_model_args(ap)
    ap.add_argument("--limit", type=int, default=5)
    args = ap.parse_args()

    # 1) host the model
    enable_compile_cache()
    lm, params = build_lm(args.arch, full=not args.reduced)
    engine = build_engine(lm, params, full=not args.reduced)
    print(f"serving {lm.cfg.name}: "
          f"{sum(x.size for x in jax.tree.leaves(params)):,} params")

    # 2) batched request path (the scheduler the ORDER BY operators ride on)
    sched = BatchScheduler(engine, max_batch=4)
    rids = [sched.submit(f"summarize: {p}", max_new=6) for p in PASSAGES[:6]]
    t0 = time.perf_counter()
    outs = sched.run()
    print(f"scheduler: {len(outs)} requests in {time.perf_counter()-t0:.2f}s "
          f"({engine.stats.prefill_tokens} prefill tokens, "
          f"{engine.stats.decode_tokens} decode tokens)\n")

    # 3) LLM ORDER BY against the served model
    oracle = ModelOracle(engine)
    keys = as_keys(PASSAGES)
    query = "relevance to query: define bmt medical"
    for path in ("pointwise", "ext_merge", "auto"):
        res, rep = llm_order_by(keys, query, oracle, path=path,
                                descending=True, limit=args.limit,
                                sample_size=8, strategy="borda")
        tag = (f"auto->{rep.chosen.label}" if rep else path)
        print(f"=== {tag}: {res.n_calls} calls, ${res.cost:.5f} ===")
        for i, k in enumerate(res.order):
            print(f"  {i+1}. {k.text[:60]}")
        print()


if __name__ == "__main__":
    main()
