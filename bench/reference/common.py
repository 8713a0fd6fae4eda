"""Shared pieces of the float32 references.

A reference computes what a configuration defines, in float32 with
every matrix product at ``highest`` precision, from weights it draws
itself from the run seed. It imports nothing of the program under test.
Each weight is drawn in float32, rounded once to the dtype the
configuration states (bf16: the model as it is served) and used in
float32 from then on.

``quant="fp8"`` is the control: every weight matrix product takes its
operands rounded to float8 e4m3, with a scale per row of the left and
per column of the right operand, and accumulates in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

E4M3_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(x: jax.Array, w: jax.Array, quant: str | None) -> jax.Array:
    """x (..., K) @ w (K, N) in float32, or through float8 for the control."""
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def silu(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(x)


def bucket_rows(seqs: list[np.ndarray], bucket: int
                ) -> dict[int, list[int]]:
    """Row indices grouped by length rounded up to ``bucket``. Rows are
    padded on the right to their group's length: every model here is
    causal, so the padding changes no earlier position."""
    groups: dict[int, list[int]] = {}
    for i, s in enumerate(seqs):
        groups.setdefault(-(-len(s) // bucket) * bucket, []).append(i)
    return groups


def run_rows(seqs: list[np.ndarray], want: list[np.ndarray], *, bucket: int,
             block_rows, embed, weights, apply, n_layers: int, head) -> list:
    """Logits (len(want[i]), V) at positions ``want[i]`` of each sequence.

    ``embed(tokens)`` gives (R, T, D) float32; ``weights(i)`` draws
    layer ``i``'s weights and ``apply(w, h)`` applies them to a block of
    rows; ``head(h)`` maps (n, D) to logits. All rows advance one layer
    at a time, in blocks of ``block_rows(T)`` rows, so that only one
    layer's weights and one block's temporaries are live at once.
    """
    blocks = []                          # (row ids, (R, T, D) hidden)
    for T, rows in sorted(bucket_rows(seqs, bucket).items()):
        n = block_rows(T)
        for i in range(0, len(rows), n):
            ids = rows[i:i + n]
            tok = np.zeros((n, T), np.int32)
            for j, r in enumerate(ids):
                tok[j, :len(seqs[r])] = seqs[r]
            blocks.append((ids, embed(jnp.asarray(tok))))
    for li in range(n_layers):
        w = weights(li)
        blocks = [(ids, apply(w, h)) for ids, h in blocks]
    out: list = [None] * len(seqs)
    for ids, h in blocks:
        for j, r in enumerate(ids):
            n = len(want[r])           # positions padded to a multiple of 64
            pos = np.resize(want[r], -(-n // 64) * 64)   # so heads compile once
            out[r] = head(h[j, jnp.asarray(pos)])[:n]
    return out
