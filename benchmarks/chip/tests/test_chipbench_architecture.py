"""The seam between the harness and an architecture: a configuration names
its module, the module provides the contract, and the harness takes the
program check, the sizes, the reference, the work counts and the cache
reading from it.  The dense module's readings are held to golden values
recorded with the harness's dense code before it moved into
``architectures/dense.py``: the same integers, and bit-equal float32
logits of the rehearsal reference."""
import dataclasses
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import use_program  # noqa: E402
from chipbench.driver import load_reader  # noqa: E402
from chipbench.model import (ARCH_CONTRACT, ARCH_DIR, CONFIG_DIR,  # noqa: E402
                             init_params, load_architecture,
                             load_architecture_file, load_config,
                             model_config)
from chipbench.reference import padded_row, prompt_ids  # noqa: E402
from chipbench_testlib import BENCH, run_script  # noqa: E402

DATA = BENCH / "tests" / "data"
GOLDEN = json.loads((DATA / "dense_golden.json").read_text())
CONFIGS = sorted(p.stem for p in CONFIG_DIR.glob("*.json"))
DENSE = ["stablelm-1.6b", "phi4-mini-3.8b"]

dense = load_architecture_file(ARCH_DIR / "dense.py")


# ------------------------------------------------------ golden: work counts
@pytest.mark.parametrize("name", DENSE)
@pytest.mark.parametrize("shape", range(4))
def test_dense_prefill_counts_are_the_parents(name, shape):
    rows, new, cached, reserve, ops, nbytes = \
        GOLDEN["counts"][name]["prefill"][shape]
    d = dense.dims(load_config(name))
    assert dense.prefill_flops(d, rows, new, cached) == ops
    assert dense.prefill_bytes(d, rows, new, cached, reserve) == nbytes


@pytest.mark.parametrize("name", DENSE)
@pytest.mark.parametrize("live", range(2))
def test_dense_model_flops_are_the_parents(name, live):
    tokens, rows, pairs, ops = GOLDEN["counts"][name]["model"][live]
    assert dense.model_flops(dense.dims(load_config(name)), tokens, rows,
                             pairs) == ops


# ----------------------------------------------- golden: reference logits
@pytest.fixture(scope="module")
def rehearsal_models():
    """Per dense configuration: the rehearsal preset's weights from the
    golden seed and its sizes."""
    use_program()
    from repro.models import LM
    out = {}
    for name in DENSE:
        cfg = model_config(load_config(name), rehearsal=True)
        out[name] = (init_params(LM(cfg), GOLDEN["seed"], dense),
                     dense.dims_from_program(cfg))
    return out


@pytest.mark.parametrize("name", DENSE)
@pytest.mark.parametrize("precision", ["float32", "fp8"])
def test_dense_reference_logits_are_the_parents_bit_for_bit(
        rehearsal_models, name, precision):
    params, dims = rehearsal_models[name]
    rows = [padded_row(prompt_ids(p if isinstance(p, str) else tuple(p)))
            for p in GOLDEN["prompts"]]
    got = dense.Reference(params, dims, precision).logits(rows)
    want = np.load(DATA / "dense_golden_logits.npz")[f"{name}/{precision}"]
    assert got.dtype == want.dtype == np.float32
    assert np.array_equal(got, want), float(np.abs(got - want).max())


# ------------------------------------------------------ finding the module
@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_names_a_module_with_the_contract(name):
    arch = load_architecture(load_config(name))
    assert all(callable(getattr(arch, f)) for f in ARCH_CONTRACT)


@pytest.mark.parametrize("value", [None, "nope", "../architectures/dense",
                                   "dense.py", 7])
def test_a_configuration_naming_no_module_is_refused_by_file(value):
    spec = dict(load_config("stablelm-1.6b"))
    if value is None:
        del spec["architecture"]
    else:
        spec["architecture"] = value
    with pytest.raises(SystemExit) as e:
        load_architecture(spec)
    msg = str(e.value)
    assert "benchmarks/chip/configs/stablelm-1.6b.json" in msg
    assert '"architecture"' in msg and repr(value) in msg


def test_a_module_lacking_contract_functions_is_refused_with_their_names(
        tmp_path, monkeypatch):
    (tmp_path / "partial.py").write_text(
        "def dims(spec):\n    return {}\n\n"
        "def check_program(cfg, spec):\n    pass\n\n"
        "Reference = None\n")
    monkeypatch.setattr("chipbench.model.ARCH_DIR", tmp_path)
    spec = {**load_config("stablelm-1.6b"), "architecture": "partial"}
    with pytest.raises(SystemExit) as e:
        load_architecture(spec)
    msg = str(e.value)
    assert "partial.py" in msg
    missing = [f for f in ARCH_CONTRACT
               if f not in ("dims", "check_program")]
    assert all(f in msg for f in missing)
    assert "dims," not in msg and "check_program" not in msg


# ------------------------------------------------------- the program check
@pytest.fixture(scope="module")
def stablelm():
    use_program()
    spec = load_config("stablelm-1.6b")
    return spec, model_config(spec)


@pytest.mark.parametrize("change", [
    {"pattern": (("moe", 24),)},
    {"pattern": (("swa", 3), ("attn", 1)) * 6},
    {"head_dim": 128},
])
def test_a_program_shape_dense_does_not_take_is_refused(stablelm, change):
    spec, cfg = stablelm
    with pytest.raises(SystemExit) as e:
        dense.check_program(dataclasses.replace(cfg, **change), spec)
    assert str(e.value) == ("config stablelm-1.6b: not a dense "
                            "full-attention stack of published head size")


def test_a_published_key_the_program_does_not_run_is_refused(stablelm):
    spec, cfg = stablelm
    with pytest.raises(SystemExit) as e:
        dense.check_program(dataclasses.replace(cfg, d_ff=4096), spec)
    assert str(e.value) == ("config stablelm-1.6b: intermediate_size=5632 "
                            "but the program runs d_ff=4096")


def test_model_config_checks_through_the_named_module(stablelm,
                                                       monkeypatch):
    spec, _ = stablelm
    seen = []
    monkeypatch.setattr(dense, "check_program",
                        lambda cfg, s: seen.append((cfg.name, s["name"])))
    monkeypatch.setattr("chipbench.model.load_architecture",
                        lambda s: dense)
    model_config(spec)
    assert seen == [("stablelm-1.6b", "stablelm-1.6b")]


# ----------------------------------------------------------------- weights
class _FakeLM:
    """A parameter layout with a router's correction bias: a 1-D leaf that
    is not a norm gain."""
    cfg = SimpleNamespace(n_layers=2)

    @staticmethod
    def init(key):
        import jax.numpy as jnp
        return {"embed": jnp.zeros((16, 8), jnp.bfloat16),
                "stacks": [{"router_bias": jnp.zeros((2, 4), jnp.float32),
                            "norm1": jnp.zeros((2, 8), jnp.bfloat16)}],
                "e_score_correction_bias": jnp.zeros((4,), jnp.float32)}


def test_init_refuses_a_leaf_no_rule_draws_by_name():
    with pytest.raises(ValueError) as e:
        init_params(_FakeLM, 3, dense)
    assert "e_score_correction_bias" in str(e.value)
    assert "draw" in str(e.value)


def test_init_draws_a_leaf_with_the_architectures_draw():
    import jax.numpy as jnp

    def draw(name, shape, key):
        if name.endswith("_bias"):
            return jnp.full(shape.shape, 0.5, shape.dtype)
        return None

    arch = SimpleNamespace(__name__="with_draw", draw=draw)
    w = init_params(_FakeLM, 3, arch)
    assert np.all(np.asarray(w["e_score_correction_bias"]) == 0.5)
    assert np.all(np.asarray(w["stacks"][0]["router_bias"]) == 0.5)
    # the generic rule draws every other leaf whatever the draw gives
    plain = init_params(_FakeLM, 3, SimpleNamespace(
        draw=lambda n, s, k: jnp.zeros(s.shape, s.dtype)
        if n.endswith("_bias") else None))
    assert np.array_equal(np.asarray(w["embed"]), np.asarray(plain["embed"]))
    assert np.array_equal(np.asarray(w["stacks"][0]["norm1"]),
                          np.asarray(plain["stacks"][0]["norm1"]))


# ----------------------------------------------------- the metric readers
class _Counts:
    """Work counts of round numbers, each call recorded."""

    def __init__(self):
        self.calls = []

    def prefill_flops(self, dims, rows, new, cached):
        self.calls.append(("prefill_flops", rows, new, cached))
        return 10**12

    def prefill_bytes(self, dims, rows, new, cached, reserve):
        self.calls.append(("prefill_bytes", rows, new, cached, reserve))
        return 10**6

    def model_flops(self, dims, live_tokens, live_rows, attn_pairs):
        self.calls.append(("model_flops", live_tokens, live_rows,
                           attn_pairs))
        return 3 * 10**12


def _record(arch):
    call = SimpleNamespace(t=1.0, traced=True, live_rows=8, new=256,
                           cached=0, reserve=32, live_tokens=1536,
                           attn_pairs=150000)
    state = SimpleNamespace(t_open=0.0, t_close=2.0, calls=[call],
                            trace={"module_count": 1, "module_s": 0.02})
    peak = {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12}
    return SimpleNamespace(arch=arch, dims={"d": 1}, state=state, peak=peak)


def test_readers_take_the_work_counts_from_the_records_architecture():
    arch = _Counts()
    rec = _record(arch)
    # 1e12 operations at 1e14/s need 10 ms of the traced 20 ms
    assert load_reader("prefill_roofline")(rec) == pytest.approx(50.0)
    # 3e12 operations in a 2 s window at 1e14/s
    assert load_reader("mfu")(rec) == pytest.approx(1.5)
    assert arch.calls == [("prefill_flops", 8, 256, 0),
                          ("prefill_bytes", 8, 256, 0, 32),
                          ("model_flops", 1536, 8, 150000)]


@pytest.mark.parametrize("name", DENSE)
def test_readers_through_dense_give_the_parents_counts(name):
    rec = _record(dense)
    rec.dims = dense.dims(load_config(name))
    _, _, _, _, ops, nbytes = GOLDEN["counts"][name]["prefill"][0]
    need = max(ops / 1e14, nbytes / 1e12)
    assert load_reader("prefill_roofline")(rec) == 100.0 * need / 0.02
    mops = GOLDEN["counts"][name]["model"][0][3]
    assert load_reader("mfu")(rec) == 100.0 * mops / (2.0 * 1e14)


# ------------------------------------- a whole rehearsal through the module
def test_a_rehearsal_takes_every_piece_from_the_configurations_module(
        tmp_path):
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"devices": {"cpu": {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}}))
    p = run_script(BENCH / "tests" / "rehearse_architecture.py",
                   ["--workload", "stablelm-1.6b.pointwise-tweets",
                    "--seed", 2**31 + 21,
                    "--arch-dir", BENCH / "tests" / "architectures",
                    "--architecture", "recording", "--peaks", peaks],
                   tmp_path)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"], out
    calls = out["calls"]
    # a rehearsal runs the reduced preset: sizes from the program, and no
    # published-key check
    for f in ("dims_from_program", "Reference", "trunk_marker",
              "cached_positions", "model_flops"):
        assert calls.get(f, 0) > 0, (f, calls)
    assert calls["Reference"] == 1 and calls["dims_from_program"] == 1
    assert "dims" not in calls and "check_program" not in calls
    assert out["metrics"]["mfu"]["value"] > 0
    assert out["checks"]["logit_err_max"]["value"] <= \
        out["checks"]["logit_err_max"]["limit"]
