"""Oracle semantics: determinism, antisymmetry, billing, caching."""
import numpy as np

from repro.core import (CachingOracle, ExactOracle, LLAMA405B, LLAMA70B,
                        SimulatedOracle, as_keys)
from repro.core.oracles.simulated import FACTUAL, REASONING
from repro.core.types import InvalidOutputError


def mk(n=10, seed=0):
    rng = np.random.default_rng(seed)
    return as_keys([f"text {i} " + "w" * (i % 7) for i in range(n)],
                   rng.standard_normal(n))


def test_temperature_zero_determinism():
    keys = mk()
    o1, o2 = SimulatedOracle(REASONING), SimulatedOracle(REASONING)
    assert o1.score_batch(keys, "c") == o2.score_batch(keys, "c")
    assert o1.compare(keys[0], keys[1], "c") == o2.compare(keys[0], keys[1], "c")
    r1 = [k.uid for k in o1.rank_batch(keys, "c")]
    r2 = [k.uid for k in o2.rank_batch(keys, "c")]
    assert r1 == r2


def test_compare_antisymmetric():
    keys = mk(20, seed=1)
    o = SimulatedOracle(REASONING)
    for a in keys[:5]:
        for b in keys[5:10]:
            assert o.compare(a, b, "c") == -o.compare(b, a, "c")


def test_compare_far_apart_latents():
    """A latent gap past exp's range decides the compare for the larger
    key (92 vs -22 at the reasoning profile's temperature overflowed)."""
    keys = as_keys(["k0", "k1"], [92.0, -22.0])
    o = SimulatedOracle(REASONING)
    assert o.compare(keys[0], keys[1], "c") == 1
    assert o.compare(keys[1], keys[0], "c") == -1


def test_factual_profile_scores_accurately():
    keys = mk(30, seed=2)
    o = SimulatedOracle(FACTUAL)
    scores = o.score_batch(keys, "height")
    corr = np.corrcoef(scores, [k.latent for k in keys])[0, 1]
    assert corr > 0.95


def test_rank_batch_is_permutation():
    keys = mk(16, seed=3)
    o = SimulatedOracle(REASONING)
    perm = o.rank_batch(keys, "c")
    assert sorted(k.uid for k in perm) == sorted(k.uid for k in keys)


def test_invalid_rate_grows_with_batch():
    o = SimulatedOracle(REASONING)
    fails = {m: 0 for m in (4, 32)}
    for m in fails:
        for seed in range(40):
            keys = mk(m, seed=100 + seed)
            try:
                o.rank_batch(keys, f"crit-{seed}")
            except InvalidOutputError:
                fails[m] += 1
    assert fails[32] >= fails[4]


def test_ledger_token_accounting_and_prices():
    keys = mk(8)
    o = SimulatedOracle(REASONING, prices=LLAMA70B)
    o.score_batch(keys, "c")
    o.compare(keys[0], keys[1], "c")
    led = o.ledger
    assert led.n_calls == 2
    assert led.input_tokens > 0 and led.output_tokens > 0
    c70 = led.cost(LLAMA70B)
    c405 = led.cost(LLAMA405B)
    assert c405 > c70 > 0


def test_cache_hits_are_free():
    keys = mk(6)
    o = CachingOracle(SimulatedOracle(REASONING))
    v1 = o.score_batch(keys, "c")
    calls_after_first = o.ledger.n_calls
    v2 = o.score_batch(keys, "c")
    assert v1 == v2
    assert o.ledger.n_calls == calls_after_first  # no extra billing
    assert o.hits == 1 and o.misses == 1


def test_cache_key_canonicalizes_criteria_whitespace():
    """Regression (ISSUE 6 satellite): memo keys normalize criteria
    whitespace, so logically identical calls spelled with different
    spacing/newlines hit one entry instead of re-billing."""
    keys = mk(4)
    o = CachingOracle(SimulatedOracle(REASONING))
    v1 = o.score_batch(keys, "degree  of\n positivity")
    calls = o.ledger.n_calls
    v2 = o.score_batch(keys, " degree of positivity ")
    assert v1 == v2
    assert o.ledger.n_calls == calls             # second spelling was free
    assert o.hits == 1 and o.misses == 1
    # compare + inquire variants share the same canonicalization
    a, b = keys[0], keys[1]
    r1 = o.compare(a, b, "x\ty")
    r2 = o.compare(a, b, "x y")
    assert r1 == r2 and o.hits == 2
    assert o.inquire(a, "c  c") == o.inquire(a, "c c")
    assert o.hits == 3
    # distinct criteria stay distinct entries
    o.score_batch(keys, "different criteria")
    assert o.misses == 4


def test_cache_key_stable_hash_no_collisions_on_structure():
    """The stable key separates kind / uid tuple / criteria structurally:
    permuted uids or a different verb never alias one entry."""
    from repro.core.oracles.cache import CachingOracle as C
    assert C._ck("score", (1, 2), "c") == C._ck("score", iter((1, 2)), "c")
    assert C._ck("score", (1, 2), "c") != C._ck("score", (2, 1), "c")
    assert C._ck("score", (1, 2), "c") != C._ck("rank", (1, 2), "c")
    assert C._ck("score", (12,), "c") != C._ck("score", (1, 2), "c")


def test_exact_oracle_judge_picks_true_best():
    keys = mk(10, seed=4)
    best = sorted(keys, key=lambda k: k.latent)
    worst = list(reversed(best))
    o = ExactOracle()
    assert o.judge(keys, "c", [worst, best, keys]) == 1
