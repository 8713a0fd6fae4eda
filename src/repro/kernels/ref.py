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
    path only prefill below ``attn_chunk_threshold`` calls it; decode
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
    """Chunked SSD (the algorithm the Pallas kernel implements):
    intra-chunk quadratic attention-like term + inter-chunk state pass.
    Matches ``ssd_scan`` to fp32 tolerance."""
    B, S, H, P = x.shape
    G, Nst = b.shape[2], b.shape[3]
    rep = H // G
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    xf = x.astype(jnp.float32).reshape(B, nc, chunk, H, P)
    af = a.astype(jnp.float32).reshape(B, nc, chunk, H)
    bf = jnp.repeat(b.astype(jnp.float32), rep, axis=2).reshape(
        B, nc, chunk, H, Nst)
    cf = jnp.repeat(c.astype(jnp.float32), rep, axis=2).reshape(
        B, nc, chunk, H, Nst)

    acs = jnp.cumsum(af, axis=2)                       # (B,nc,L,H)
    # L[t, s] = exp(acs[t] - acs[s]) for s <= t  (segment sum)
    seg = acs[:, :, :, None, :] - acs[:, :, None, :, :]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.where(tri[None, None, :, :, None], jnp.exp(seg), 0.0)

    # intra-chunk: y_diag[t] = sum_s L[t,s] (c_t . b_s) x_s
    cb = jnp.einsum("bnthi,bnshi->bnhts", cf, bf)      # (B,nc,H,L,L)
    Lh = jnp.moveaxis(L, -1, 2)                        # (B,nc,H,L,L)
    y_diag = jnp.einsum("bnhts,bnshp->bnthp", cb * Lh, xf)

    # chunk states: states[n] = sum_s exp(acs[last] - acs[s]) b_s x_s
    decay_out = jnp.exp(acs[:, :, -1:, :] - acs)       # (B,nc,L,H)
    states = jnp.einsum("bnsh,bnshi,bnshp->bnhpi", decay_out, bf, xf)

    # inter-chunk recurrence over n
    chunk_decay = jnp.exp(acs[:, :, -1, :])            # (B,nc,H)
    s0 = (jnp.zeros((B, H, P, Nst), jnp.float32)
          if initial_state is None else initial_state.astype(jnp.float32))

    def step(carry, inp):
        st_n, dec_n = inp
        new = dec_n[:, :, None, None] * carry + st_n
        return new, carry    # emit state *entering* the chunk

    final, prevs = jax.lax.scan(
        step, s0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prevs, 0, 1)            # (B,nc,H,P,N)

    # y_off[t] = (c_t . state_prev) * exp(acs[t])
    y_off = jnp.einsum("bnthi,bnhpi,bnth->bnthp",
                       cf, prev_states, jnp.exp(acs))
    y = (y_diag + y_off).reshape(B, S, H, P)
    return y.astype(x.dtype), final
