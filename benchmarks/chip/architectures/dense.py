"""Dense decoder: one stack of identical layers (the program's pattern
``(("attn", n),)``), each full causal attention of head size
``d_model / heads`` over grouped key/value heads and a SwiGLU feed-forward.

Everything the harness knows of this architecture is here, found by the
name a configuration file gives under ``"architecture"`` (the contract is
``chipbench/model.py``'s ``ARCH_CONTRACT``): which published keys the
program must run, the sizes the reference and the work counts read, the
plain reference's layer and head, the operations and bytes of a probe
prefill, where the program's prefix caches keep their length, and the
trunk programs' marker.

The reference follows the architecture the configuration files run, with
their departures from the published models (see each file's
``departures``): RMSNorm with gain ``1 + g``; full rotary embedding on the
whole head, halves rotated (``x1 cos - x2 sin, x2 cos + x1 sin``); grouped
query attention, query head ``j`` reading key/value head ``j // (H / KV)``;
causal attention over every position, the left padding included (the
program's prompts carry no padding mask, so a row's logits are defined at
its padded length); SwiGLU feed-forward; no biases; logits of the last
position from the final RMSNorm and the head (the embedding, transposed,
where the configuration ties them).

The work counts: a probe prefill program runs ``rows`` rows of ``new``
tokens each over ``cached`` tokens of key/value already held (0 for a
plain prefill, the prefix length for a prefill continued from the prefix
cache), returns the key/value of all ``cached + new + reserve`` positions,
and reads out the logits of each row's last position.  Counted operations
are multiply-adds times two: the projections and the feed-forward for
every new token, attention as computed (every new query against every
cached and new key, the causal half included, since the program computes
the whole rectangle), and the head for one position a row.
"""
from __future__ import annotations

import math
from functools import partial

import numpy as np

from chipbench.flops import BF16, LOGIT_BYTES
from chipbench.reference import RowBlocked, _mm, _rms, _rope

# published config.json key -> ModelConfig field
PUBLISHED_FIELDS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "rms_norm_eps": "norm_eps",
    "layer_norm_eps": "norm_eps",
    "torch_dtype": "dtype",
}


# ------------------------------------------------------ the program's check
def check_program(cfg, spec: dict) -> None:
    """Refuse to go on where the program's ``ModelConfig`` disagrees with
    the configuration file's published keys, or is not a dense
    full-attention stack of published head size."""
    for key, field in PUBLISHED_FIELDS.items():
        if key in spec and getattr(cfg, field) != spec[key]:
            raise SystemExit(
                f"config {spec['name']}: {key}={spec[key]!r} but the program "
                f"runs {field}={getattr(cfg, field)!r}")
    if cfg.pattern != (("attn", cfg.n_layers),) or cfg.hd * cfg.n_heads \
            != cfg.d_model:
        raise SystemExit(f"config {spec['name']}: not a dense full-attention "
                         f"stack of published head size")


# ------------------------------------------------------------------ sizes
def dims(spec: dict) -> dict:
    """The sizes the reference needs, from a configuration file's published
    keys."""
    eps = spec.get("rms_norm_eps", spec.get("layer_norm_eps"))
    return dict(d=spec["hidden_size"], layers=spec["num_hidden_layers"],
                heads=spec["num_attention_heads"],
                kv=spec["num_key_value_heads"], ff=spec["intermediate_size"],
                vocab=spec["vocab_size"], theta=float(spec["rope_theta"]),
                eps=float(eps), tie=bool(spec["tie_word_embeddings"]))


def dims_from_program(cfg) -> dict:
    """The same sizes from the program's ``ModelConfig`` (a rehearsal runs
    the registry's reduced preset, which no configuration file states)."""
    return dict(d=cfg.d_model, layers=cfg.n_layers, heads=cfg.n_heads,
                kv=cfg.n_kv_heads, ff=cfg.d_ff, vocab=cfg.vocab_size,
                theta=float(cfg.rope_theta), eps=float(cfg.norm_eps),
                tie=bool(cfg.tie_embeddings))


# ------------------------------------------------------------ the reference
class Reference(RowBlocked):
    def __init__(self, params, dims: dict, precision: str = "float32"):
        import jax
        super().__init__(params, dims, precision)
        self._layer = jax.jit(partial(_layer, dims=dims, fp8=self.fp8))
        self._head = jax.jit(partial(_head, dims=dims, fp8=self.fp8))

    def _block(self, toks: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp
        p = self.params
        stack = p["stacks"][0]
        x = self._embed(p["embed"], jnp.asarray(toks))
        for i in range(self.dims["layers"]):
            x = self._layer(x, stack, jnp.int32(i))
        head = p["embed"] if self.dims["tie"] else p["lm_head"]
        return np.asarray(self._head(x[:, -1], p["final_norm"], head))


def _layer(x, stack, i, *, dims, fp8):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    w = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                     stack)
    b, s, d = x.shape
    h, kv = dims["heads"], dims["kv"]
    hd = d // h
    a = _rms(x, w["norm1"], dims["eps"])
    q = _rope(_mm(a, w["wq"], fp8).reshape(b, s, h, hd), dims["theta"])
    k = _rope(_mm(a, w["wk"], fp8).reshape(b, s, kv, hd), dims["theta"])
    v = _mm(a, w["wv"], fp8).reshape(b, s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v,
                   precision=hi)
    x = x + _mm(o.reshape(b, s, h * hd), w["wo"], fp8)
    a = _rms(x, w["norm2"], dims["eps"])
    f = w["ffn"]
    g = _mm(a, f["w_gate"], fp8)
    u = _mm(a, f["w_up"], fp8)
    return x + _mm(jax.nn.silu(g) * u, f["w_down"], fp8)


def _head(x_last, final_norm, head, *, dims, fp8):
    """x_last (B, d) -> logits (B, V)."""
    a = _rms(x_last, final_norm, dims["eps"])
    return _mm(a, head.T if dims["tie"] else head, fp8)


# ------------------------------------------------------------ work counts
def head_dim(dims: dict) -> int:
    return dims["d"] // dims["heads"]


def layer_params(dims: dict) -> int:
    """Weights of one decoder layer's matmuls."""
    d, h, kv, f = dims["d"], dims["heads"], dims["kv"], dims["ff"]
    hd = head_dim(dims)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def head_params(dims: dict) -> int:
    return dims["d"] * dims["vocab"]


def kv_bytes_per_token(dims: dict) -> int:
    return dims["layers"] * 2 * dims["kv"] * head_dim(dims) * BF16


def prefill_flops(dims: dict, rows: int, new: int, cached: int = 0) -> int:
    """Operations of a prefill of ``rows`` rows as the device computes it."""
    linear = 2 * layer_params(dims) * dims["layers"] * rows * new
    attn = (4 * dims["layers"] * dims["heads"] * head_dim(dims)
            * rows * new * (cached + new))
    return linear + attn + 2 * head_params(dims) * rows


def prefill_bytes(dims: dict, rows: int, new: int, cached: int = 0,
                  reserve: int = 0) -> int:
    """Bytes a prefill must move at least: every weight once (the
    embedding's gathered rows only), the cached key/value read, the
    key/value of every position written, and the logits written."""
    weights = (layer_params(dims) * dims["layers"] + head_params(dims)) * BF16
    embed_rows = rows * new * dims["d"] * BF16
    kv = kv_bytes_per_token(dims) * rows * (cached + (cached + new + reserve))
    logits = rows * dims["vocab"] * LOGIT_BYTES
    return weights + embed_rows + kv + logits


def model_flops(dims: dict, live_tokens: int, live_rows: int,
                attn_pairs: int) -> int:
    """Model operations of the live, unpadded work: the projections and the
    feed-forward of each live token, attention over ``attn_pairs`` (query,
    key) pairs of live tokens, and the head for each live row."""
    return (2 * layer_params(dims) * dims["layers"] * live_tokens
            + 4 * dims["layers"] * dims["heads"] * head_dim(dims) * attn_pairs
            + 2 * head_params(dims) * live_rows)


# ------------------------------------------------- what the harness reads
def cached_positions(prefix_cache) -> int:
    """Key/value positions held in the caches a continued prefill
    (``_prefill_cont``) is given: the program's dense cache of the first
    stack, laid out (layers, rows, positions, ...)."""
    return int(prefix_cache[0].k.shape[2])


def trunk_marker(cfg) -> str:
    """Text that the HLO of a program running the model's layer stack holds:
    the shape of its stacked feed-forward weights.  The probe prefill
    programs are the executions that run such an operation."""
    return f"[{cfg.n_layers},{cfg.d_model},{cfg.d_ff}]"
