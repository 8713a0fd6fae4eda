"""Mamba-2 (SSD) sequence-mixer block (arXiv:2405.21060), used by
mamba2-2.7b and the jamba hybrid's SSM layers.

Structure per block, as ``Mamba2`` in mamba_ssm/modules/mamba2.py:
  in_proj -> [z | x | B | C | dt]
  causal depthwise conv1d (width 4, with bias) over [x | B | C], SiLU
  dt = softplus(dt_raw + dt_bias);  a = -exp(A_log) * dt
  y = SSD(x * dt, a, B, C) + D * x
  y = RMSNorm(y * silu(z));  out = y @ out_proj

The projections, conv and gated norm trace under the ``ssm`` scope and
the recurrence with its skip term under ``ssm_scan``, side by side, so
a profile splits the two. Decode keeps (conv window, SSD state) caches,
both O(1) in sequence length.

Prefill runs the recurrence in the chunked state-space-duality form
(``ref.ssd_chunked``; paper §6): inside a chunk of min(128, max(16, S))
positions, the masked decay times C Bᵀ (formed once per state group)
times X, as matmuls; each chunk's end state as (B ∘ decay)ᵀ X; a scan
over the chunks alone carrying the f32 state; and the entering state's
term exp(cumsum a) ∘ C·Sᵀ. The prompt pads at its end to a whole chunk
with x = b = c = 0 and a = 0, which changes neither the real positions
nor the final state decode starts from. Operands are float32 and every
contraction runs at HIGHEST precision, as in the token recurrence it
replaces. Decode advances the state one token a step. Both entries are
rewritten whole every step, so the layer scan (``lm._cached_layers``)
carries every layer's stacked conv window and state and writes each
layer's new entry back in place, in the donated cache; attention's K/V
entries, which gain one row a step, stay the scan's inputs and outputs.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.models.layers import scoped
from repro.parallel.sharding import constrain


def _splits(cfg):
    din = cfg.ssm_inner
    gn = cfg.ssm_groups * cfg.ssm_state
    nh = cfg.ssm_heads
    return din, gn, nh


def init_ssm(cfg, key):
    d = cfg.d_model
    din, gn, nh = _splits(cfg)
    proj_out = 2 * din + 2 * gn + nh
    conv_dim = din + 2 * gn
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "in_proj": jax.random.normal(k1, (d, proj_out)) / math.sqrt(d),
        "conv_w": jax.random.normal(k2, (cfg.ssm_conv_width, conv_dim))
        * 0.1,
        "conv_b": jnp.zeros((conv_dim,)),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh)),
        "D": jnp.ones((nh,)),
        "dt_bias": jnp.zeros((nh,)) + jnp.log(jnp.expm1(0.01)),
        "norm": jnp.ones((din,)),
        "out_proj": jax.random.normal(k3, (din, d)) / math.sqrt(din),
    }
    s = {
        "in_proj": ("embed", "ssm_inner"),
        "conv_w": (None, "conv_dim"),
        "conv_b": ("conv_dim",),
        "A_log": ("ssm_heads",),
        "D": ("ssm_heads",),
        "dt_bias": ("ssm_heads",),
        "norm": ("ssm_inner",),
        "out_proj": ("ssm_inner", "embed"),
    }
    return p, s


@scoped("ssm")
def _mix_in(cfg, p, x, window=None):
    """in_proj, the causal conv and dt for x: (B, S, D). ``window``:
    (B, K-1, conv_dim) of the conv's inputs before x, or None for zero
    history. Returns (z, x heads (B, S, nh, ph), B and C (B, S, G, N),
    dt (B, S, nh) f32, a (B, S, nh) f32, the conv's next window)."""
    Bt, S, _ = x.shape
    din, gn, nh = _splits(cfg)
    K = cfg.ssm_conv_width
    proj = x @ p["in_proj"].astype(x.dtype)
    z, xbc, dt_raw = jnp.split(proj, [din, 2 * din + 2 * gn], axis=-1)
    if window is None:
        window = jnp.zeros((Bt, K - 1, xbc.shape[-1]), x.dtype)
    xp = jnp.concatenate([window.astype(x.dtype), xbc], axis=1)
    w = p["conv_w"].astype(x.dtype)
    conv = sum(xp[:, i:i + S] * w[i] for i in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(x.dtype))
    xin, bb, cc = jnp.split(xbc, [din, din + gn], axis=-1)
    xin = constrain(xin, "batch", None, "ssm_inner")
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["A_log"].astype(jnp.float32)) * dt
    G, N = cfg.ssm_groups, cfg.ssm_state
    return (z, xin.reshape(Bt, S, nh, cfg.ssm_head_dim),
            bb.reshape(Bt, S, G, N), cc.reshape(Bt, S, G, N), dt, a,
            xp[:, S:])


@scoped("ssm")
def _mix_out(cfg, p, y, z):
    """Gated RMSNorm of y (B, S, nh, ph) and out_proj -> (B, S, D)."""
    y = y.reshape(z.shape)
    y = ref.rmsnorm_rows(y * jax.nn.silu(z), p["norm"], cfg.rms_norm_eps)
    out = y @ p["out_proj"].astype(y.dtype)
    return constrain(out, "batch", None, "embed_act")


def _skip(p, y, xh):
    """The skip term D * x, on x before its dt scaling."""
    return y + p["D"].astype(y.dtype)[:, None] * xh


def _chunk(S):
    """Positions a chunk of the SSD spans, from the sequence length."""
    return min(128, max(16, S))


def ssm_fwd(cfg, p, x):
    """Training path. x: (B, S, D) -> (B, S, D)."""
    z, xh, bg, cg, dt, a, _ = _mix_in(cfg, p, x)
    with jax.named_scope("ssm_scan"):
        y, _ = ops.ssd(xh * dt[..., None].astype(xh.dtype), a, bg, cg,
                       chunk=_chunk(x.shape[1]))
        y = _skip(p, y, xh)
    return _mix_out(cfg, p, y, z)


def ssm_fwd_with_cache(cfg, p, x):
    """Prefill returning (out, SSD state (B, nh, ph, N) f32, conv window
    (B, K-1, conv_dim)), the recurrence in the chunked SSD form
    (``ref.ssd_chunked``) with the chunk taken from the prompt's width."""
    z, xh, bg, cg, dt, a, window = _mix_in(cfg, p, x)
    with jax.named_scope("ssm_scan"):
        y, state = ref.ssd_chunked(xh * dt[..., None].astype(xh.dtype), a,
                                   bg, cg, chunk=_chunk(x.shape[1]))
        y = _skip(p, y, xh)
    return _mix_out(cfg, p, y, z), state, window


def ssm_decode(cfg, p, x, conv_window, state):
    """Single-token decode. x: (B, 1, D); conv_window: (B, K-1, conv_dim);
    state: (B, nh, ph, N). Returns (out, conv_window, state)."""
    z, xh, bg, cg, dt, a, window = _mix_in(cfg, p, x, conv_window)
    with jax.named_scope("ssm_scan"):
        xt = xh[:, 0]
        y, state = ops.ssd_decode_step(
            xt * dt[:, 0, :, None].astype(xt.dtype), a[:, 0], bg[:, 0],
            cg[:, 0], state)
        y = _skip(p, y, xt)[:, None]
    return _mix_out(cfg, p, y, z), window, state
