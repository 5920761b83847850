"""What every architecture's plain reference shares: a float32 forward pass
written from the architecture's equations with nothing of the program
imported.  Each architecture module (``architectures/<name>.py``) writes
its own layer and head on these pieces; the dense decoder's equations are
in ``architectures/dense.py``.

A reference reads the benchmark's own weights (``model.init_params``) by
their names in the parameter tree, upcasts each layer's bf16 weights to
float32 exactly, and computes every contraction at ``Precision.HIGHEST``,
layer by layer, in fixed blocks of rows (:class:`RowBlocked`), so that it
fits beside the weights once the program's state is freed.

Prompts are byte-tokenised: ``BOS`` then the UTF-8 bytes of the whole
prompt (a structured ``(prefix, suffix)`` prompt is their concatenation),
left-padded with ``PAD`` to the next power of two of at least 16 tokens.

``precision="fp8"`` is the control: every operand of every weight matmul
(``_mm``: the projections, the feed-forward and the head) is rounded to
float8 e4m3, weights with one scale per matrix and activations with one
scale per token, before a float32 product.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

PAD, BOS = 256, 257
MIN_CLASS = 16
ROW_BLOCK = 8
FP8_MAX = 448.0


def prompt_ids(prompt) -> list[int]:
    """Token ids of one probe prompt (a string or a (prefix, suffix) pair)."""
    text = prompt if isinstance(prompt, str) else "".join(prompt)
    return [BOS] + list(text.encode("utf-8"))


def padded_length(n: int) -> int:
    return 1 << (max(n, MIN_CLASS) - 1).bit_length()


def padded_row(ids: Sequence[int]) -> np.ndarray:
    row = np.full(padded_length(len(ids)), PAD, np.int32)
    row[len(row) - len(ids):] = ids
    return row


class RowBlocked:
    """What every architecture's reference shares: the weights, the sizes,
    the precision, the embedding, and :meth:`logits`, which runs the rows
    in fixed blocks.  An architecture's ``Reference`` subclasses it and
    gives ``_block``: the last-position logits (B, V) of ``ROW_BLOCK``
    padded rows of one length."""

    def __init__(self, params, dims: dict, precision: str = "float32"):
        import jax
        if precision not in ("float32", "fp8"):
            raise ValueError(precision)
        self.params = params
        self.dims = dims
        self.fp8 = precision == "fp8"
        self._embed = jax.jit(_embed)

    def logits(self, rows: Sequence[np.ndarray]) -> np.ndarray:
        """Last-position logits (float32, one row per input) of padded token
        rows; rows of one length run together in blocks of ROW_BLOCK."""
        out = np.zeros((len(rows), self.dims["vocab"]), np.float32)
        by_len: dict[int, list[int]] = {}
        for i, r in enumerate(rows):
            by_len.setdefault(len(r), []).append(i)
        for _, idx in sorted(by_len.items()):
            for b in range(0, len(idx), ROW_BLOCK):
                blk = idx[b:b + ROW_BLOCK]
                toks = np.stack([rows[i] for i in blk]
                                + [rows[blk[-1]]] * (ROW_BLOCK - len(blk)))
                out[blk] = self._block(toks)[:len(blk)]
        return out

    def _block(self, toks: np.ndarray) -> np.ndarray:
        raise NotImplementedError


# ------------------------------------------------------------ the equations
def _embed(table, toks):
    import jax.numpy as jnp
    return jnp.take(table, toks, axis=0).astype(jnp.float32)


def _fq8(x, axes):
    """Round ``x`` to float8 e4m3 with one scale over ``axes``."""
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    s = FP8_MAX / jnp.where(amax > 0, amax, 1.0)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(x, w, fp8: bool):
    """x (..., k) @ w (k, n) in float32 at HIGHEST precision."""
    import jax
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    if fp8:
        x = _fq8(x, (-1,))
        w = _fq8(w, (0, 1))
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, gain, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + gain.astype(jnp.float32))


def _rope(x, theta):
    """x (B, S, N, hd): rotate the two halves of each head by position."""
    import jax.numpy as jnp
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv        # (S, half)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
