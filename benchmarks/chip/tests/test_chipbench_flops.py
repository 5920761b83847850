"""The dense architecture's work counts (architectures/dense.py) and the
roofline (flops.py) against hand counts."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import flops  # noqa: E402
from chipbench.model import (ARCH_DIR, load_architecture_file,  # noqa: E402
                             load_config)

dense = load_architecture_file(ARCH_DIR / "dense.py")

# d=4, 2 heads of 2 over 1 key/value head, d_ff=6, 3 layers, vocab 10
TINY = dict(d=4, heads=2, kv=1, ff=6, layers=3, vocab=10)


def test_layer_params_by_hand():
    # q 4x4, k and v 4x2 each, o 4x4, gate/up/down 4x6 each
    assert dense.layer_params(TINY) == 16 + 8 + 8 + 16 + 72


def test_prefill_flops_by_hand():
    # 2 rows, 5 new tokens over 3 cached: projections and ffn 2*120 per
    # token per layer; attention 4 * layers * heads * hd * queries * keys;
    # head 2 * d * vocab per row
    assert dense.prefill_flops(TINY, rows=2, new=5, cached=3) == (
        2 * 120 * 3 * 2 * 5 + 4 * 3 * 2 * 2 * (2 * 5) * 8 + 2 * 40 * 2)


def test_prefill_bytes_by_hand():
    # weights once (bf16), 10 gathered embedding rows, key/value of 3
    # cached positions read and 3 + 5 + 1 reserved positions written
    # (3 layers * k,v * 1 head * 2 dims * 2 bytes = 24 per token), bf16
    # logits of 2 rows
    assert dense.prefill_bytes(TINY, rows=2, new=5, cached=3, reserve=1) == (
        (120 * 3 + 40) * 2 + 2 * 5 * 4 * 2 + 24 * 2 * (3 + 9) + 2 * 10 * 2)


def test_model_flops_counts_live_work_only():
    assert dense.model_flops(TINY, live_tokens=7, live_rows=2,
                             attn_pairs=15) == (
        2 * 120 * 3 * 7 + 4 * 3 * 2 * 2 * 15 + 2 * 40 * 2)


def test_roofline_names_the_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(500, 20, peak) == (5.0, "compute")
    assert flops.roofline_seconds(100, 50, peak) == (5.0, "memory")


@pytest.mark.parametrize("name", ["stablelm-1.6b", "phi4-mini-3.8b"])
def test_counts_add_up_to_the_published_parameters(name):
    # the matmul weights plus the embedding and the norm gains are the
    # model's parameters as the program counts them
    sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))
    from chipbench.model import model_config
    spec = load_config(name)
    dims = dense.dims(spec)
    cfg = model_config(spec)
    head = 0 if dims["tie"] else dense.head_params(dims)
    assert (dense.layer_params(dims) * dims["layers"] + head
            + dims["vocab"] * dims["d"]
            + 2 * dims["d"] * dims["layers"] + dims["d"]) == cfg.param_count()
