"""Float32 reference of a dense decoder (Qwen3 layout), and its FLOPs.

Per layer, with pre-norm residuals:
  h += Wo · attn(RoPE(qk_norm(Wq x)), RoPE(qk_norm(Wk x)), Wv x),  x = rms(h)
  h += Wd · (silu(Wg x) * Wu x),                                   x = rms(h)
Attention is causal with grouped KV heads (query head j reads KV head
j // (heads / kv_heads)); RoPE rotates the two halves of each head
(theta ``rope_theta``). Logits are rms(h) · lm_head, with the head
untied from the embedding where ``tie_word_embeddings`` is false.

Weights follow the served program's seed recipe (``jax.random`` keys
split in its order, normal draws scaled by 1/sqrt(fan-in)), so a seed
names one model.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import mm, rms, run_rows, silu

# configuration key -> the program's ArchConfig field it must equal
PROGRAM_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "qk_norm": "qk_norm", "attention_bias": "qkv_bias",
    "torch_dtype": "compute_dtype",
}


def _sizes(c: dict):
    return (c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"])


def param_count(c: dict) -> int:
    L, d, H, KV, hd, f, V = _sizes(c)
    per = 2 * d + d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f
    per += 2 * hd if c["qk_norm"] else 0
    heads = 1 if c["tie_word_embeddings"] else 2
    return heads * V * d + d + L * per


def request_flops(c: dict, prompt_len: int, n_new: int) -> float:
    """FLOPs to serve one request: the prompt and all but the last
    generated token pass every layer, attention reads each position's
    causal context, and the head runs once per generated token."""
    L, d, H, KV, hd, f, V = _sizes(c)
    tokens = prompt_len + n_new - 1
    linear = 2 * (d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f)
    ctx = tokens * (tokens + 1) // 2          # sum of causal context sizes
    attn = 4 * H * hd * ctx                   # q.k and p.v per position
    return float(L * (linear * tokens + attn) + 2 * d * V * n_new)


@functools.lru_cache(maxsize=4)
def _programs(cfg_json: str, quant: str | None):
    c = json.loads(cfg_json)

    def as_served(x):       # a weight as the configuration's dtype holds it
        return x.astype(c["torch_dtype"]).astype(jnp.float32)

    L, d, H, KV, hd, f, V = _sizes(c)
    eps = c["rms_norm_eps"]

    @jax.jit
    def top(key):
        keys = jax.random.split(key, 4)
        embed = as_served(jax.random.normal(keys[0], (V, d)) * 0.02)
        head = as_served(jax.random.normal(keys[1], (d, V)) / math.sqrt(d))
        return embed, embed.T if c["tie_word_embeddings"] else head

    @jax.jit
    def weights(key, i):
        keys = jax.random.split(key, 4)
        key = jax.random.split(jax.random.fold_in(keys[2], 0), L)[i]
        k_attn, k_mlp, _, _ = jax.random.split(key, 4)
        ka = jax.random.split(k_attn, 4)
        km = jax.random.split(k_mlp, 3)

        def normal(k, shape, fan_in):
            return as_served(jax.random.normal(k, shape, jnp.float32)
                             * (1.0 / math.sqrt(fan_in)))
        return {"wq": normal(ka[0], (d, H * hd), d),
                "wk": normal(ka[1], (d, KV * hd), d),
                "wv": normal(ka[2], (d, KV * hd), d),
                "wo": normal(ka[3], (H * hd, d), H * hd),
                "wg": normal(km[0], (d, f), d),
                "wu": normal(km[1], (d, f), d),
                "wd": normal(km[2], (f, d), f)}

    @jax.jit
    def apply(w, h, cos, sin):
        R, T, _ = h.shape

        def rope(x):                           # (R, T, heads, hd)
            a, b = x[..., :hd // 2], x[..., hd // 2:]
            cs, sn = cos[None, :, None], sin[None, :, None]
            return jnp.concatenate([a * cs - b * sn, b * cs + a * sn], -1)

        x = rms(h, 1.0, eps)                   # every norm scale is 1 at init
        q = mm(x, w["wq"], quant).reshape(R, T, H, hd)
        k = mm(x, w["wk"], quant).reshape(R, T, KV, hd)
        v = mm(x, w["wv"], quant).reshape(R, T, KV, hd)
        if c["qk_norm"]:
            q, k = rms(q, 1.0, eps), rms(k, 1.0, eps)
        q = rope(q).reshape(R, T, KV, H // KV, hd)
        k = rope(k)
        s = jnp.einsum("rtkgd,rskd->rkgts", q, k,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((T, T), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("rkgts,rskd->rtkgd", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        h = h + mm(o.reshape(R, T, H * hd), w["wo"], quant)
        x = rms(h, 1.0, eps)
        return h + mm(silu(mm(x, w["wg"], quant)) * mm(x, w["wu"], quant),
                      w["wd"], quant)

    @jax.jit
    def head(h, lm_head):
        return mm(rms(h, 1.0, eps), lm_head, quant)

    return top, weights, apply, head


def logits_at(c: dict, seed: int, seqs: list[np.ndarray],
              want: list[np.ndarray], quant: str | None = None) -> list:
    """Logits at positions ``want[i]`` of each token sequence ``seqs[i]``
    (each sequence numbered from position 0)."""
    top, weights, apply, head = _programs(json.dumps(c, sort_keys=True),
                                          quant)
    key = jax.random.PRNGKey(seed)
    embed, lm_head = top(key)
    hd, H = c["head_dim"], c["num_attention_heads"]
    inv = 1.0 / c["rope_theta"] ** (np.arange(0, hd, 2) / hd)
    tables = {}

    def rope_tables(T):
        if T not in tables:
            ang = np.arange(T)[:, None] * inv[None]
            tables[T] = (jnp.asarray(np.cos(ang), jnp.float32),
                         jnp.asarray(np.sin(ang), jnp.float32))
        return tables[T]

    return run_rows(
        seqs, want, bucket=256,
        # two (heads, T, T) float32 score tensors per row in ~4 GiB
        block_rows=lambda T: max(1, min(8, 2**32 // (8 * H * T * T))),
        embed=lambda tok: embed[tok],
        weights=lambda i: weights(key, i),
        apply=lambda w, h: apply(w, h, *rope_tables(h.shape[1])),
        n_layers=c["num_hidden_layers"],
        head=lambda h: head(h, lm_head))
