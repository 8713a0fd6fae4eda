"""Model building blocks: norms, RoPE/M-RoPE, GQA attention, dense MLP,
MoE FFN. Pure-functional JAX; every init returns ``(params, specs)``
where specs mirror the params tree with logical-axis tuples consumed by
repro.parallel.sharding.

Each layer function traces under a ``jax.named_scope`` (``SCOPES``), so
every operation of a compiled step carries its layer in the HLO
``op_name`` metadata ("jit(_decode)/while/body/closed_call/attn/...")
and a profile can be split by layer. Scopes change metadata only.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.parallel.sharding import constrain

Tree = Any

# the named scopes of the model's layers; the embedding gather and the
# LM head (final norm and head matmul) are scoped where ``lm`` runs them.
# ``ssm_scan`` (the SSD recurrence and its skip term) is a sibling of
# ``ssm`` (projections, conv, gated norm), not nested in it.
SCOPES = ("embed", "norm", "attn", "mlp", "moe", "ssm", "ssm_scan",
          "lm_head")

# from this sequence length on, full-sequence attention runs the
# online-softmax chunked form: the (Sq, Skv) logits never materialize
ATTN_CHUNK_THRESHOLD = 8192


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return wrapped
    return deco


def _init(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return jax.random.normal(key, shape, dtype) * scale


# ------------------------------------------------------------------- norms

def init_norm(cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm_kind == "layernorm":
        return ({"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
                {"scale": ("embed_act",), "bias": ("embed_act",)})
    return ({"scale": jnp.ones((d,))}, {"scale": ("embed_act",)})


@scoped("norm")
def apply_norm(cfg, p, x):
    """The block or final norm of ``x``, in the compute dtype (``x`` may
    be the float32 residual stream)."""
    if cfg.norm_kind == "layernorm":
        y = ref.layernorm_rows(x, p["scale"], p["bias"])
    else:
        y = ref.rmsnorm_rows(x, p["scale"], cfg.rms_norm_eps)
    return y.astype(cfg.compute_dtype)


# -------------------------------------------------------------------- rope

def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               m_rope_sections: tuple[int, ...] | None = None) -> jax.Array:
    """x: (B, S, H, D). positions: (B, S) or (3, B, S) for M-RoPE.

    M-RoPE (qwen2-vl): the D/2 rotary frequencies are split into
    temporal/height/width sections, each rotated by its own position id
    stream. For text, all three streams are equal and M-RoPE reduces to
    standard RoPE.
    """
    B, S, H, D = x.shape
    freqs = jnp.asarray(rope_freqs(D, theta), dtype=jnp.float32)  # (D/2,)
    if positions.ndim == 2:
        ang = positions.astype(jnp.float32)[:, :, None] * freqs[None, None]
    else:
        assert m_rope_sections is not None and sum(m_rope_sections) == D // 2
        parts = []
        start = 0
        for si, sec in enumerate(m_rope_sections):
            f = freqs[start:start + sec]
            pos = positions[si].astype(jnp.float32)
            parts.append(pos[:, :, None] * f[None, None])
            start += sec
        ang = jnp.concatenate(parts, axis=-1)                     # (B,S,D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : D // 2], x32[..., D // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------- attention

def init_attention(cfg, key, cross: bool = False):
    ks = jax.random.split(key, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        "wq": _init(ks[0], (d, qd)),
        "wk": _init(ks[1], (d, kvd)),
        "wv": _init(ks[2], (d, kvd)),
        "wo": _init(ks[3], (qd, d), scale=1.0 / math.sqrt(qd)),
    }
    s = {
        "wq": ("embed", "q_dim"),
        "wk": ("embed", "kv_dim"),
        "wv": ("embed", "kv_dim"),
        "wo": ("q_dim", "embed"),
    }
    if cfg.qkv_bias:
        p |= {"bq": jnp.zeros((qd,)), "bk": jnp.zeros((kvd,)),
              "bv": jnp.zeros((kvd,))}
        s |= {"bq": ("q_dim",), "bk": ("kv_dim",), "bv": ("kv_dim",)}
    if cfg.qk_norm:
        p |= {"q_norm": jnp.ones((cfg.head_dim,)),
              "k_norm": jnp.ones((cfg.head_dim,))}
        s |= {"q_norm": ("head_dim",), "k_norm": ("head_dim",)}
    return p, s


def _project_qkv(cfg, p, x, positions, rope: bool):
    B, S, _ = x.shape
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = ref.rmsnorm_rows(q, p["q_norm"], cfg.rms_norm_eps)
        k = ref.rmsnorm_rows(k, p["k_norm"], cfg.rms_norm_eps)
    if rope and positions is not None:
        sections = cfg.m_rope_sections if cfg.m_rope else None
        q = apply_rope(q, positions, cfg.rope_theta, sections)
        k = apply_rope(k, positions, cfg.rope_theta, sections)
    return q, k, v


@scoped("attn")
def attention_fwd(cfg, p, x, positions, *, causal: bool = True,
                  kv_override=None):
    """Full-sequence attention (training / prefill).

    kv_override: (k, v) from an encoder for cross-attention (no rope).
    Returns (out, (k, v)) with k/v in (B, Hkv, S, D) layout for caching.
    """
    B, S, _ = x.shape
    if kv_override is None:
        q, k, v = _project_qkv(cfg, p, x, positions, rope=True)
        k_t = k.transpose(0, 2, 1, 3)
        v_t = v.transpose(0, 2, 1, 3)
    else:
        q = (x @ p["wq"].astype(x.dtype))
        if cfg.qkv_bias:
            q = q + p["bq"].astype(x.dtype)
        q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = ref.rmsnorm_rows(q, p["q_norm"], cfg.rms_norm_eps)
        k_t, v_t = kv_override
    q_t = q.transpose(0, 2, 1, 3)
    q_t = constrain(q_t, "batch_attn", "heads", None, None)
    if S >= ATTN_CHUNK_THRESHOLD:
        out = ref.mha_attention_chunked(q_t, k_t, v_t, causal=causal)
    else:
        out = ref.mha_attention(q_t, k_t, v_t, causal=causal)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, cfg.q_dim)
    out = out @ p["wo"].astype(x.dtype)
    return constrain(out, "batch", None, "embed_act"), (k_t, v_t)


def encode_kv(cfg, p, enc_out):
    """Cross-attention K/V from encoder output: (B, Hkv, Senc, D)."""
    B, S, _ = enc_out.shape
    k = enc_out @ p["wk"].astype(enc_out.dtype)
    v = enc_out @ p["wv"].astype(enc_out.dtype)
    if cfg.qkv_bias:
        k = k + p["bk"].astype(enc_out.dtype)
        v = v + p["bv"].astype(enc_out.dtype)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    return k, v


@scoped("attn")
def attention_decode(cfg, p, x, cache_k, cache_v, pos, *,
                     cross: bool = False, kv_len=None, rope: bool = True):
    """Single-token decode. x: (B, 1, D); cache_k/v: (B, Hc, Smax, D);
    pos: scalar int32 — current position (tokens already in cache).

    q is reshaped into the cache's head groups, (B, Hc, Hq // Hc, D),
    and contracted against the cache as stored: no upcast or repeated
    copy of the cache is made. Hc is read off the cache, so GQA, MQA
    and MHA take the same path. The products accumulate in float32, and
    the scale, mask and softmax run on the float32 logits.

    For cross-attention the cache holds encoder KV and is not updated.
    Returns (out, cache_k, cache_v).
    """
    B = x.shape[0]
    if not cross:
        positions = jnp.full((B, 1), pos, dtype=jnp.int32)
        if cfg.m_rope:
            positions = jnp.broadcast_to(positions[None], (3, B, 1))
        q, k, v = _project_qkv(cfg, p, x, positions, rope=rope)
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.transpose(0, 2, 1, 3).astype(cache_k.dtype),
            (0, 0, pos, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.transpose(0, 2, 1, 3).astype(cache_v.dtype),
            (0, 0, pos, 0))
        valid = pos + 1
    else:
        q = (x @ p["wq"].astype(x.dtype))
        if cfg.qkv_bias:
            q = q + p["bq"].astype(x.dtype)
        q = q.reshape(B, 1, cfg.n_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = ref.rmsnorm_rows(q, p["q_norm"], cfg.rms_norm_eps)
        valid = cache_k.shape[2] if kv_len is None else kv_len
    _, Hc, Smax, D = cache_k.shape
    assert cfg.n_heads % Hc == 0, (cfg.n_heads, Hc)
    qg = q.reshape(B, Hc, cfg.n_heads // Hc, D)
    logits = jnp.einsum("bkgd,bktd->bkgt", qg, cache_k,
                        preferred_element_type=jnp.float32)
    logits = logits * (1.0 / math.sqrt(D))
    lens = jnp.broadcast_to(valid, (B,))[:, None, None, None]
    logits = jnp.where(jnp.arange(Smax) < lens, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", probs, cache_v,
                     preferred_element_type=jnp.float32)
    out = out.astype(x.dtype).reshape(B, 1, cfg.q_dim)
    out = out @ p["wo"].astype(x.dtype)
    return out, cache_k, cache_v


# ---------------------------------------------------------------- dense mlp

def init_mlp(cfg, key):
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp_kind == "swiglu":
        k1, k2, k3 = jax.random.split(key, 3)
        return ({"w_gate": _init(k1, (d, f)), "w_up": _init(k2, (d, f)),
                 "w_down": _init(k3, (f, d), scale=1.0 / math.sqrt(f))},
                {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
                 "w_down": ("mlp", "embed")})
    k1, k2 = jax.random.split(key, 2)
    return ({"w_up": _init(k1, (d, f)),
             "w_down": _init(k2, (f, d), scale=1.0 / math.sqrt(f))},
            {"w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")})


@scoped("mlp")
def mlp_fwd(cfg, p, x):
    if cfg.mlp_kind == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"].astype(x.dtype)) \
            * (x @ p["w_up"].astype(x.dtype))
    elif cfg.mlp_kind == "relu2":
        h = x @ p["w_up"].astype(x.dtype)
        h = jnp.square(jnp.maximum(h, 0.0))
    else:  # gelu
        h = jax.nn.gelu(x @ p["w_up"].astype(x.dtype))
    h = constrain(h, "batch", None, "mlp")
    out = h @ p["w_down"].astype(x.dtype)
    return constrain(out, "batch", None, "embed_act")


# ---------------------------------------------------------------------- moe

def init_moe(cfg, key):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    p = {"router": _init(ks[0], (d, E), scale=0.02)}
    s = {"router": ("embed", "experts")}
    if cfg.mlp_kind == "swiglu":
        p |= {"w_gate": _init(ks[1], (E, d, f)),
              "w_up": _init(ks[2], (E, d, f)),
              "w_down": _init(ks[3], (E, f, d), scale=1.0 / math.sqrt(f))}
        s |= {"w_gate": ("experts", "embed", "mlp"),
              "w_up": ("experts", "embed", "mlp"),
              "w_down": ("experts", "mlp", "embed")}
    else:
        p |= {"w_up": _init(ks[1], (E, d, f)),
              "w_down": _init(ks[2], (E, f, d), scale=1.0 / math.sqrt(f))}
        s |= {"w_up": ("experts", "embed", "mlp"),
              "w_down": ("experts", "mlp", "embed")}
    return p, s


@scoped("moe")
def moe_fwd(cfg, p, x, group_size: int = 1024):
    """Capacity-bounded top-k MoE with deterministic in-group dispatch
    (GShard-style dense einsum dispatch — GSPMD/EP friendly: the
    (g, s, E, C) tensors shard over batch x experts).

    x: (B, S, D) -> (y, aux_loss)
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    Sg = min(group_size, S)
    assert S % Sg == 0, (S, Sg)
    ng = S // Sg
    xg = x.reshape(B * ng, Sg, D)

    logits = (xg.astype(jnp.float32)
              @ p["router"].astype(jnp.float32))          # (g, Sg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, K)                   # (g, Sg, K)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

    cap = max(1, int(math.ceil(Sg * K / E * cfg.capacity_factor)))
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)    # (g, Sg, K, E)
    # priority: slot-major then token order (standard GShard ordering)
    oh_flat = onehot.transpose(0, 2, 1, 3).reshape(-1, K * Sg, E)
    pos = jnp.cumsum(oh_flat, axis=1) - oh_flat           # (g, K*Sg, E)
    keep = (pos < cap) * oh_flat
    pos_idx = jnp.einsum("gte,gte->gt", pos, oh_flat).astype(jnp.int32)
    cap_oh = jax.nn.one_hot(pos_idx, cap, dtype=jnp.float32)
    disp_flat = keep[..., None] * cap_oh[:, :, None, :]   # (g,K*Sg,E,C)
    disp = disp_flat.reshape(-1, K, Sg, E, cap).transpose(0, 2, 1, 3, 4)
    dispatch = disp.sum(2)                                 # (g, Sg, E, C)
    combine = jnp.einsum("gsec,gsk,gske->gsec", dispatch, gate,
                         onehot)                           # weighted

    cd = x.dtype
    xe = jnp.einsum("gsec,gsd->egcd", dispatch.astype(cd), xg)  # (E,g,C,D)
    xe = constrain(xe, "experts", "batch", None, None)
    if cfg.mlp_kind == "swiglu":
        h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", xe,
                                   p["w_gate"].astype(cd))) \
            * jnp.einsum("egcd,edf->egcf", xe, p["w_up"].astype(cd))
    else:
        h = jax.nn.gelu(jnp.einsum("egcd,edf->egcf", xe,
                                   p["w_up"].astype(cd)))
    ye = jnp.einsum("egcf,efd->egcd", h, p["w_down"].astype(cd))
    y = jnp.einsum("gsec,egcd->gsd", combine.astype(cd), ye)
    y = y.reshape(B, S, D)

    # Switch-style load-balance aux loss
    density = dispatch.sum(-1).mean(axis=(0, 1))          # (E,) fraction
    router_mean = probs.mean(axis=(0, 1))
    aux = E * jnp.sum(density * router_mean) * cfg.router_aux_weight
    return constrain(y, "batch", None, "embed_act"), aux
