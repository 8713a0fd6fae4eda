"""Mamba-2 (SSD) sequence-mixer block (arXiv:2405.21060), used by
mamba2-2.7b and the jamba hybrid's SSM layers.

Structure per block:
  in_proj -> [z | x | B | C | dt]
  causal conv1d (width 4) over [x | B | C], SiLU
  dt = softplus(dt_raw + dt_bias);  a = -exp(A_log) * dt
  y = SSD(x * dt, a, B, C) + D * (x * dt)        (kernels.ops.ssd)
  y = RMSNorm(y * silu(z));  out = y @ out_proj

Decode keeps (conv window, SSD state) caches — both O(1) in sequence
length, which is why the long_500k cell runs on this family.
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


def _causal_conv(xbc, conv_w, conv_b, prev=None):
    """Depthwise causal conv1d. xbc: (B, S, Cdim); conv_w: (K, Cdim).
    prev: (B, K-1, Cdim) decode window or None (zero history)."""
    K = conv_w.shape[0]
    if prev is None:
        pad = jnp.zeros((xbc.shape[0], K - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = prev.astype(xbc.dtype)
    xp = jnp.concatenate([pad, xbc], axis=1)
    out = sum(xp[:, i:i + xbc.shape[1], :] * conv_w[i][None, None]
              for i in range(K))
    return out + conv_b[None, None]


@scoped("ssm")
def ssm_fwd(cfg, p, x):
    """Training path. x: (B, S, D) -> (B, S, D)."""
    B, S, D = x.shape
    din, gn, nh = _splits(cfg)
    ph = cfg.ssm_head_dim
    proj = x @ p["in_proj"].astype(x.dtype)
    z, xin, bb, cc, dt_raw = jnp.split(
        proj, [din, 2 * din, 2 * din + gn, 2 * din + 2 * gn], axis=-1)
    xbc = jnp.concatenate([xin, bb, cc], axis=-1)
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"].astype(x.dtype),
                                   p["conv_b"].astype(x.dtype)))
    xin, bb, cc = jnp.split(xbc, [din, din + gn], axis=-1)
    xin = constrain(xin, "batch", None, "ssm_inner")

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None])        # (B,S,nh)
    a = -jnp.exp(p["A_log"])[None, None] * dt               # (B,S,nh)
    xh = xin.reshape(B, S, nh, ph)
    xh = xh * dt[..., None].astype(xh.dtype)
    bg = bb.reshape(B, S, cfg.ssm_groups, cfg.ssm_state)
    cg = cc.reshape(B, S, cfg.ssm_groups, cfg.ssm_state)

    y, _ = ops.ssd(xh, a, bg, cg, chunk=min(128, max(16, S)))
    y = y + p["D"][None, None, :, None].astype(y.dtype) * xh
    y = y.reshape(B, S, din)
    y = ref.rmsnorm_rows(y * jax.nn.silu(z), p["norm"])
    out = y @ p["out_proj"].astype(x.dtype)
    return constrain(out, "batch", None, "embed_act")


@scoped("ssm")
def ssm_fwd_with_cache(cfg, p, x):
    """Prefill returning decode caches (conv window + SSD state)."""
    B, S, D = x.shape
    din, gn, nh = _splits(cfg)
    ph = cfg.ssm_head_dim
    Kw = cfg.ssm_conv_width
    proj = x @ p["in_proj"].astype(x.dtype)
    z, xin, bb, cc, dt_raw = jnp.split(
        proj, [din, 2 * din, 2 * din + gn, 2 * din + 2 * gn], axis=-1)
    xbc_pre = jnp.concatenate([xin, bb, cc], axis=-1)
    xbc = jax.nn.silu(_causal_conv(xbc_pre, p["conv_w"].astype(x.dtype),
                                   p["conv_b"].astype(x.dtype)))
    xin2, bb2, cc2 = jnp.split(xbc, [din, din + gn], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None])
    a = -jnp.exp(p["A_log"])[None, None] * dt
    xh = xin2.reshape(B, S, nh, ph) * dt[..., None].astype(x.dtype)
    bg = bb2.reshape(B, S, cfg.ssm_groups, cfg.ssm_state)
    cg = cc2.reshape(B, S, cfg.ssm_groups, cfg.ssm_state)
    y, state = ref.ssd_scan(xh, a, bg, cg)
    y = y + p["D"][None, None, :, None].astype(y.dtype) * xh
    y = y.reshape(B, S, din)
    y = ref.rmsnorm_rows(y * jax.nn.silu(z), p["norm"])
    out = y @ p["out_proj"].astype(x.dtype)
    conv_window = xbc_pre[:, -(Kw - 1):, :]     # (B, K-1, conv_dim)
    return out, state.astype(jnp.float32), conv_window


@scoped("ssm")
def ssm_decode(cfg, p, x, conv_window, state):
    """Single-token decode. x: (B, 1, D); conv_window: (B, K-1, conv_dim);
    state: (B, nh, ph, N). Returns (out, conv_window, state)."""
    B = x.shape[0]
    din, gn, nh = _splits(cfg)
    ph = cfg.ssm_head_dim
    proj = x @ p["in_proj"].astype(x.dtype)
    z, xin, bb, cc, dt_raw = jnp.split(
        proj, [din, 2 * din, 2 * din + gn, 2 * din + 2 * gn], axis=-1)
    xbc_t = jnp.concatenate([xin, bb, cc], axis=-1)       # (B, 1, conv_dim)
    window = jnp.concatenate([conv_window, xbc_t], axis=1)  # (B, K, cd)
    conv_out = (window * p["conv_w"][None].astype(x.dtype)).sum(axis=1) \
        + p["conv_b"][None].astype(x.dtype)               # (B, cd)
    conv_out = jax.nn.silu(conv_out)
    xin2, bb2, cc2 = jnp.split(conv_out, [din, din + gn], axis=-1)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32)
                         + p["dt_bias"][None])             # (B, nh)
    a = -jnp.exp(p["A_log"])[None] * dt
    xh = xin2.reshape(B, nh, ph) * dt[..., None].astype(x.dtype)
    bg = bb2.reshape(B, cfg.ssm_groups, cfg.ssm_state)
    cg = cc2.reshape(B, cfg.ssm_groups, cfg.ssm_state)
    y, state = ops.ssd_decode_step(xh, a, bg, cg, state)
    y = y + p["D"][None, :, None].astype(y.dtype) * xh
    y = y.reshape(B, 1, din)
    y = ref.rmsnorm_rows(y * jax.nn.silu(z), p["norm"])
    out = y @ p["out_proj"].astype(x.dtype)
    return out, window[:, 1:, :], state
