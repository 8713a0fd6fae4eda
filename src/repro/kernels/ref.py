"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the allclose test sweeps, the
differentiable implementations used by the training path, and the
numeric references for the DORA runtime's MMU/SFU backends.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------- gemm

def gemm(a, b, bias=None, epilogue: str = "none"):
    out = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    if epilogue.startswith("bias"):
        out = out + bias.astype(jnp.float32)
    if epilogue.endswith("gelu"):
        out = jax.nn.gelu(out)
    elif epilogue.endswith("relu2"):
        r = jnp.maximum(out, 0.0)
        out = r * r
    elif epilogue.endswith("relu"):
        out = jnp.maximum(out, 0.0)
    elif epilogue.endswith("silu"):
        out = jax.nn.silu(out)
    return out.astype(a.dtype)


# ---------------------------------------------------------------------- sfu

def softmax_rows(x):
    x32 = x.astype(jnp.float32)
    return jax.nn.softmax(x32, axis=-1).astype(x.dtype)


def layernorm_rows(x, gamma=None, beta=None, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(axis=-1, keepdims=True)
    var = x32.var(axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    if gamma is not None:
        y = y * gamma.astype(jnp.float32)
    if beta is not None:
        y = y + beta.astype(jnp.float32)
    return y.astype(x.dtype)


def rmsnorm_rows(x, gamma=None, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(axis=-1, keepdims=True) + eps)
    if gamma is not None:
        y = y * gamma.astype(jnp.float32)
    return y.astype(x.dtype)


def gelu_rows(x):
    return jax.nn.gelu(x.astype(jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------- flash attention

def mha_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                  kv_len: jax.Array | None = None):
    """Grouped-query attention oracle, the tests' reference. It upcasts
    K and V to float32 and repeats them to Hq heads. Of the serving
    path only prefill below ``layers.ATTN_CHUNK_THRESHOLD`` calls it; decode
    contracts the cache by query group (``layers.attention_decode``).

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D); Hq % Hkv == 0.
    ``kv_len``: optional (B,) valid KV lengths (decode with a cache).
    Returns (B, Hq, Sq, D).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    qf = q.astype(jnp.float32) * scale
    kf = jnp.repeat(k.astype(jnp.float32), group, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
    if causal and Sq > 1:
        qi = jnp.arange(Sq)[:, None] + (Skv - Sq)
        ki = jnp.arange(Skv)[None, :]
        logits = jnp.where(ki <= qi, logits, -jnp.inf)
    if kv_len is not None:
        ki = jnp.arange(Skv)[None, None, None, :]
        logits = jnp.where(ki < kv_len[:, None, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    return out.astype(q.dtype)


def mha_attention_chunked(q, k, v, *, causal: bool = True,
                          scale: float | None = None,
                          q_chunk: int = 1024):
    """Memory-efficient attention: lax.scan over query chunks with
    online softmax — peak memory O(q_chunk * Skv) instead of O(Sq * Skv).
    GQA handled by grouped einsum (no KV head materialization).

    Numerically identical to ``mha_attention`` (tested); used by the
    long-prefill path where the dense S^2 logits tensor cannot exist.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    q_chunk = min(q_chunk, Sq)
    assert Sq % q_chunk == 0, (Sq, q_chunk)
    nq = Sq // q_chunk
    qg = (q.astype(jnp.float32) * scale).reshape(B, Hkv, g, Sq, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    k_pos = jnp.arange(Skv)

    def chunk_fn(_, qi):
        qc, q0 = qi                       # (B, Hkv, g, qc, D), scalar base
        s = jnp.einsum("bkgqd,bkld->bkgql", qc, kf)
        if causal:
            q_pos = q0 + jnp.arange(q_chunk) + (Skv - Sq)
            mask = k_pos[None, :] <= q_pos[:, None]
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgql,bkld->bkgqd", p, vf)
        return None, out

    q_chunks = qg.reshape(B, Hkv, g, nq, q_chunk, D).transpose(
        3, 0, 1, 2, 4, 5)
    bases = jnp.arange(nq) * q_chunk
    _, outs = jax.lax.scan(chunk_fn, None, (q_chunks, bases))
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(B, Hq, Sq, D)
    return out.astype(q.dtype)


# ----------------------------------------------------------------- mamba2 ssd

def ssd_scan(x, a, b, c, *, initial_state=None):
    """Mamba-2 state-space-duality oracle via the naive recurrence.

    x: (B, S, H, P)   per-head inputs (P = head dim)
    a: (B, S, H)      per-head log-decay (a_t <= 0; decay = exp(a_t))
    b: (B, S, G, Nst) input projection (G state groups, Hq % G == 0)
    c: (B, S, G, Nst) output projection
    state: (B, H, P, Nst)
    y[t] = c[t] . state[t],  state[t] = exp(a[t]) * state[t-1] + x[t] b[t]^T
    Returns (y, final_state), y: (B, S, H, P).
    """
    B, S, H, P = x.shape
    G, Nst = b.shape[2], b.shape[3]
    assert H % G == 0
    rep = H // G
    bf = jnp.repeat(b.astype(jnp.float32), rep, axis=2)   # (B,S,H,N)
    cf = jnp.repeat(c.astype(jnp.float32), rep, axis=2)
    xf = x.astype(jnp.float32)
    af = a.astype(jnp.float32)
    s0 = (jnp.zeros((B, H, P, Nst), jnp.float32)
          if initial_state is None else initial_state.astype(jnp.float32))

    def step(state, inp):
        xt, at, bt, ct = inp
        state = (jnp.exp(at)[:, :, None, None] * state
                 + xt[..., None] * bt[:, :, None, :])
        yt = jnp.einsum("bhpn,bhn->bhp", state, ct)
        return state, yt

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(af, 1, 0),
          jnp.moveaxis(bf, 1, 0), jnp.moveaxis(cf, 1, 0))
    final, ys = jax.lax.scan(step, s0, xs)
    y = jnp.moveaxis(ys, 0, 1)
    return y.astype(x.dtype), final


def ssd_chunked(x, a, b, c, *, chunk: int = 64, initial_state=None):
    """Mamba-2 SSD in the chunked state-space-duality form (Dao & Gu,
    arXiv:2405.21060 §6), the algorithm the Pallas kernel implements;
    the same contract as ``ssd_scan`` and equal to it to float32
    rounding. Positions split into chunks of ``chunk``:

      inside a chunk : y_t = Σ_{s≤t} exp(acs_t − acs_s) (c_t·b_s) x_s,
                       acs the cumulative sum of a in the chunk
      chunk state    : Σ_s exp(acs_last − acs_s) x_s b_sᵀ
      across chunks  : a scan over the chunks only, carrying the
                       (B, H, P, N) state into each chunk
      entering state : y_t += exp(acs_t) c_t · state_inᵀ

    ``c·b`` is formed once per state group and broadcast over the group's
    heads; b and c are never repeated to the heads. The segment sums are
    masked to -inf above the diagonal before the exp, so nothing there
    overflows. Any S: the tail pads to a multiple of ``chunk`` with
    x = b = c = 0 and a = 0 (decay 1), which leaves the final state and
    the real positions as they were, and ``y`` is sliced back to S.
    Operands are float32 and every contraction runs at HIGHEST precision.
    Returns (y in x's dtype, final state (B, H, P, N) float32)."""
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    R = H // G
    pad = -S % chunk
    nc = (S + pad) // chunk
    hi = jax.lax.Precision.HIGHEST

    def chunks(t, *tail):
        t = jnp.pad(t.astype(jnp.float32),
                    [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        return t.reshape(B, nc, chunk, *tail)

    xf = chunks(x, G, R, P)
    bf, cf = chunks(b, G, N), chunks(c, G, N)
    acs = jnp.cumsum(chunks(a, G, R), axis=2)           # (B,nc,Q,G,R)

    # inside each chunk, as (B, nc, G, R, t, s)
    at = jnp.moveaxis(acs, 2, -1)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, at[..., :, None] - at[..., None, :],
                              -jnp.inf))
    cb = jnp.einsum("bntgk,bnsgk->bngts", cf, bf, precision=hi)
    y = jnp.einsum("bngrts,bnsgrp->bntgrp", decay * cb[:, :, :, None], xf,
                   precision=hi)

    # each chunk's own state at its end, then the state entering each chunk
    to_end = jnp.exp(acs[:, :, -1:] - acs)
    own = jnp.einsum("bnsgrp,bnsgk->bngrpk", xf * to_end[..., None], bf,
                     precision=hi)
    s0 = (jnp.zeros((B, H, P, N), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def step(state, inp):
        own_n, decay_n = inp
        return decay_n[..., None, None] * state + own_n, state

    final, entering = jax.lax.scan(
        step, s0.reshape(B, G, R, P, N),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(jnp.exp(acs[:, :, -1]), 1, 0)))
    y += jnp.einsum("bntgk,nbgrpk->bntgrp", cf, entering,
                    precision=hi) * jnp.exp(acs)[..., None]
    y = y.reshape(B, nc * chunk, H, P)[:, :S]
    return y.astype(x.dtype), final.reshape(B, H, P, N)
