"""Float32 reference of a Mamba-2 language model, and its FLOPs.

As MambaLMHeadModel with Mamba2 layers (arXiv:2405.21060;
github.com/state-spaces/mamba, mamba_ssm/modules/mamba2.py), per layer,
with a pre-norm residual h += Mamba2(rms(h)):
  [z | xBC | dt] = x · in_proj             (no bias)
  xBC = silu(causal depthwise conv(xBC) + conv_bias),  [x | B | C] = xBC
  dt = softplus(dt + dt_bias),  A = -exp(A_log)
  s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_tᵀ,  y_t = s_t C_t + D x_t
  out = rms(y · silu(z)) · out_proj         (gated norm, after the gate)
per head of ``headdim`` channels, heads sharing B and C by group; the
recurrence is computed exactly by blocks of positions (``ssd``). Logits
are rms(h) · embedᵀ (the head tied to the embedding), over the
vocabulary padded to a multiple of ``pad_vocab_size_multiple``. Every
RMSNorm has eps ``norm_epsilon``; every norm weight is 1 at init.

Weights follow the served program's seed recipe (``jax.random`` keys
split in its order, normal draws scaled as it scales them), so a seed
names one model; every weight is rounded once to the served dtype.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import mm, rms, run_rows, silu

HI = jax.lax.Precision.HIGHEST
CHUNK = 128         # positions a block of the SSD decomposition spans

# configuration key -> the program's ArchConfig field it must equal
PROGRAM_FIELDS = {
    "assumed.padded_vocab_size": "vocab_size",
    "n_layer": "n_layers", "d_model": "d_model",
    "assumed.d_state": "ssm_state", "assumed.d_conv": "ssm_conv_width",
    "assumed.expand": "ssm_expand", "assumed.headdim": "ssm_head_dim",
    "assumed.ngroups": "ssm_groups", "assumed.norm_epsilon": "rms_norm_eps",
    "tie_embeddings": "tie_embeddings",
    "residual_in_fp32": "residual_in_fp32",
    "assumed.torch_dtype": "compute_dtype",
}


def _sizes(c: dict):
    """(layers, d_model, inner, heads, headdim, groups, state, conv
    width, vocabulary rows)."""
    a = c["assumed"]
    d, din = c["d_model"], a["expand"] * c["d_model"]
    m = c["pad_vocab_size_multiple"]
    return (c["n_layer"], d, din, din // a["headdim"], a["headdim"],
            a["ngroups"], a["d_state"], a["d_conv"],
            -(-c["vocab_size"] // m) * m)


def param_count(c: dict) -> int:
    L, d, din, H, P, G, N, K, V = _sizes(c)
    conv_dim = din + 2 * G * N
    per = (d                                  # pre-norm
           + d * (2 * din + 2 * G * N + H)    # in_proj
           + (K + 1) * conv_dim               # conv weight and bias
           + 3 * H                            # A_log, D, dt_bias
           + din                              # gated norm
           + din * d)                         # out_proj
    heads = 1 if c["tie_embeddings"] else 2
    return heads * V * d + d + L * per


def request_flops(c: dict, prompt_len: int, n_new: int) -> float:
    """FLOPs to serve one request: the prompt and all but the last
    generated token pass every layer (projections and conv two FLOPs a
    weight; the recurrence three a state element to update it and two to
    read it out), and the head runs once per generated token."""
    L, d, din, H, P, G, N, K, V = _sizes(c)
    tokens = prompt_len + n_new - 1
    proj = 2 * (d * (2 * din + 2 * G * N + H) + din * d)
    conv = 2 * K * (din + 2 * G * N)
    scan = 5 * H * P * N
    return float(L * tokens * (proj + conv + scan) + 2 * d * V * n_new)


def ssd(x, dt, A, b, c, chunk: int = CHUNK):
    """y_t = s_t C_t of the recurrence s_t = exp(dt_t A) s_{t-1} +
    dt_t x_t B_tᵀ from s_0 = 0, by blocks of ``chunk`` positions (the
    SSD paper's block decomposition, exact): inside a block the
    quadratic form y_t = Σ_{s≤t} (C_t·B_s) exp(Σ_{s<r≤t} dt_r A) dt_s x_s,
    and from block to block the recurrence on the state at block ends.
    x: (R, T, H, P); dt: (R, T, H); A: (H,); b, c: (R, T, G, N), head h
    reading group h // (H / G). Returns y (R, T, H, P), no skip term."""
    R, T, H, P = x.shape
    G, N = b.shape[2:]
    n, E = T // chunk, H // G
    assert n * chunk == T, (T, chunk)
    u = (x * dt[..., None]).reshape(R, n, chunk, G, E, P)
    cum = jnp.cumsum((dt * A).reshape(R, n, chunk, G, E), axis=2)
    b = b.reshape(R, n, chunk, G, N)
    c = c.reshape(R, n, chunk, G, N)
    # inside each block: decay from s to t, zero above the diagonal
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
    decay = jnp.exp(jnp.where(causal, cum[:, :, :, None] - cum[:, :, None],
                              -jnp.inf))                  # (R,n,t,s,G,E)
    cb = jnp.einsum("rntgk,rnsgk->rntsg", c, b, precision=HI)
    y = jnp.einsum("rntsge,rntsg,rnsgep->rntgep", decay, cb, u,
                   precision=HI)
    # each block's own contribution to the state at its end, then the
    # state entering each block, carried from block to block
    to_end = jnp.exp(cum[:, :, -1:] - cum)                # (R,n,s,G,E)
    own = jnp.einsum("rnsge,rnsgk,rnsgep->rngepk", to_end, b, u,
                     precision=HI)

    def step(s, inp):
        own_n, decay_n = inp
        return decay_n[..., None, None] * s + own_n, s

    _, entering = jax.lax.scan(
        step, jnp.zeros((R, G, E, P, N), jnp.float32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(jnp.exp(cum[:, :, -1]), 1, 0)))
    y += jnp.einsum("rntgk,nrgepk,rntge->rntgep", c, entering, jnp.exp(cum),
                    precision=HI)
    return y.reshape(R, T, H, P)


@functools.lru_cache(maxsize=4)
def _programs(cfg_json: str, quant: str | None):
    c = json.loads(cfg_json)
    dtype = c["assumed"]["torch_dtype"]

    def as_served(x):       # a weight as the configuration's dtype holds it
        return x.astype(dtype).astype(jnp.float32)

    L, d, din, H, P, G, N, K, V = _sizes(c)
    eps = c["assumed"]["norm_epsilon"]
    conv_dim = din + 2 * G * N

    @jax.jit
    def top(key):
        keys = jax.random.split(key, 4)
        return as_served(jax.random.normal(keys[0], (V, d)) * 0.02)

    @jax.jit
    def weights(key, i):
        keys = jax.random.split(key, 4)
        key = jax.random.split(jax.random.fold_in(keys[2], 0), L)[i]
        k1, k2, k3, _ = jax.random.split(jax.random.split(key, 4)[0], 4)
        return {
            "in_proj": as_served(jax.random.normal(
                k1, (d, 2 * din + 2 * G * N + H)) / math.sqrt(d)),
            "conv_w": as_served(jax.random.normal(k2, (K, conv_dim)) * 0.1),
            "conv_b": jnp.zeros((conv_dim,)),
            "A_log": as_served(jnp.log(jnp.linspace(1.0, 16.0, H))),
            "D": jnp.ones((H,)),
            "dt_bias": as_served(jnp.zeros((H,)) + jnp.log(jnp.expm1(0.01))),
            "out_proj": as_served(jax.random.normal(k3, (din, d))
                                  / math.sqrt(din)),
        }

    @jax.jit
    def apply(w, h):
        R, T, _ = h.shape
        z, xbc, dt = jnp.split(mm(rms(h, 1.0, eps), w["in_proj"], quant),
                               [din, 2 * din + 2 * G * N], axis=-1)
        xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        xbc = silu(sum(xp[:, i:i + T] * w["conv_w"][i] for i in range(K))
                   + w["conv_b"])
        x, b, cc = jnp.split(xbc, [din, din + G * N], axis=-1)
        x = x.reshape(R, T, H, P)
        dt = jax.nn.softplus(dt + w["dt_bias"])
        y = ssd(x, dt, -jnp.exp(w["A_log"]), b.reshape(R, T, G, N),
                cc.reshape(R, T, G, N))
        y = (y + w["D"][:, None] * x).reshape(R, T, din)
        return h + mm(rms(y * silu(z), 1.0, eps), w["out_proj"], quant)

    @jax.jit
    def head(h, embed):
        return mm(rms(h, 1.0, eps), embed.T, quant)

    return top, weights, apply, head


def logits_at(c: dict, seed: int, seqs: list[np.ndarray],
              want: list[np.ndarray], quant: str | None = None) -> list:
    """Logits at positions ``want[i]`` of each token sequence ``seqs[i]``
    (each sequence numbered from position 0)."""
    if not c["tie_embeddings"]:
        raise ValueError("the Mamba-2 reference has a tied head only")
    top, weights, apply, head = _programs(json.dumps(c, sort_keys=True),
                                          quant)
    key = jax.random.PRNGKey(seed)
    embed = top(key)
    _, d, din, H, P, G, N, _, _ = _sizes(c)
    return run_rows(
        seqs, want, bucket=256,
        # a row's float32 temporaries: two (T, CHUNK, H) block decays,
        # one layer's activations and two states a block; ~4 GiB a block
        block_rows=lambda T: max(1, min(16, 2**32 // (4 * (
            T * (2 * CHUNK * H + 3 * din + 2 * G * N + H + d)
            + 2 * (T // CHUNK) * H * P * N)))),
        embed=lambda tok: embed[tok],
        weights=lambda i: weights(key, i),
        apply=apply,
        n_layers=c["n_layer"],
        head=lambda h: head(h, embed))
