"""Decoder-only language model over a repeating block pattern.

Covers dense (internlm2/qwen3/qwen1.5/nemotron), MoE (llama4/dbrx),
pure-SSM (mamba2), hybrid (jamba), and VLM-text (qwen2-vl, M-RoPE).
Layers scan over homogeneous super-blocks with optional remat; all
params carry logical-axis specs for repro.parallel.sharding.

Public entry points:
  init(cfg, key)                       -> (params, specs)
  forward(cfg, params, tokens, ...)    -> (logits, aux_loss)
  loss_fn(cfg, params, tokens, labels) -> scalar loss (+z-loss, +moe aux)
  init_cache(cfg, batch, max_len)      -> decode cache pytree
  prefill(cfg, params, tokens)         -> (logits, cache)
  decode_step(cfg, params, cache, tok, pos) -> (logits, cache)
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.parallel.sharding import constrain
from . import layers as L
from . import ssm as S
from .config import ArchConfig

Tree = Any


# ---------------------------------------------------------------------- init

def _init_layer(cfg: ArchConfig, key, pat) -> tuple[Tree, Tree]:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: dict = {}
    s: dict = {}
    p["norm1"], s["norm1"] = L.init_norm(cfg)
    if pat.mixer == "attn":
        p["attn"], s["attn"] = L.init_attention(cfg, k1)
    else:
        p["ssm"], s["ssm"] = S.init_ssm(cfg, k1)
    if pat.ffn == "moe":
        p["norm2"], s["norm2"] = L.init_norm(cfg)
        p["moe"], s["moe"] = L.init_moe(cfg, k2)
    elif pat.ffn == "dense":
        p["norm2"], s["norm2"] = L.init_norm(cfg)
        p["mlp"], s["mlp"] = L.init_mlp(cfg, k2)
    # pat.ffn == "none": pure mixer layer (mamba2)
    return p, s


def _stack_specs(spec: Tree) -> Tree:
    return jax.tree.map(lambda axes: ("layers",) + tuple(axes), spec,
                        is_leaf=lambda x: isinstance(x, tuple))


def init(cfg: ArchConfig, key) -> tuple[Tree, Tree]:
    keys = jax.random.split(key, 4)
    V, D = cfg.vocab_size, cfg.d_model
    params: dict = {"embed": jax.random.normal(keys[0], (V, D)) * 0.02}
    specs: dict = {"embed": ("vocab", "embed")}
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(keys[1], (D, V)) / math.sqrt(D)
        specs["lm_head"] = ("embed", "vocab")
    params["final_norm"], specs["final_norm"] = L.init_norm(cfg)

    blocks_p, blocks_s = {}, {}
    for pi, pat in enumerate(cfg.pattern):
        bkeys = jax.random.split(jax.random.fold_in(keys[2], pi),
                                 cfg.n_blocks)
        holder: dict = {}

        def one(kk, _pat=pat, _holder=holder):
            p, s = _init_layer(cfg, kk, _pat)
            _holder.clear()
            _holder.update(s)   # specs are trace-invariant metadata
            return p

        blocks_p[f"pos{pi}"] = jax.vmap(one)(bkeys)
        blocks_s[f"pos{pi}"] = _stack_specs(dict(holder))
    params["blocks"] = blocks_p
    specs["blocks"] = blocks_s

    if cfg.param_dtype != "float32":
        dt = jnp.dtype(cfg.param_dtype)
        params = jax.tree.map(lambda x: x.astype(dt), params)
    return params, specs


def abstract_init(cfg: ArchConfig) -> tuple[Tree, Tree]:
    """Shapes/specs without allocating (dry-run path)."""
    holder: list = []

    def f(key):
        p, s = init(cfg, key)
        holder.append(s)
        return p

    p_shape = jax.eval_shape(f, jax.random.PRNGKey(0))
    return p_shape, holder[0]


# ------------------------------------------------------------------- blocks

def _layer(cfg: ArchConfig, pat, p, x, mixers, entry=None):
    """One layer: norm1, the pattern position's mixer, the residual,
    norm2, the FFN ``pat.ffn`` names, the residual. ``mixers`` maps
    ``pat.mixer`` to ``fn(params, h, cache entry) -> (out, new entry)``;
    training passes no entry. Returns (x, MoE aux loss, new entry)."""
    h = L.apply_norm(cfg, p["norm1"], x)
    mix, entry = mixers[pat.mixer](p[pat.mixer], h, entry)
    x = x + mix
    if pat.ffn == "none":
        return x, 0.0, entry
    h = L.apply_norm(cfg, p["norm2"], x)
    if pat.ffn == "moe":
        ff, aux = L.moe_fwd(cfg, p["moe"], h)
    else:
        ff, aux = L.mlp_fwd(cfg, p["mlp"], h), 0.0
    return x + ff, aux, entry


def _block_fwd(cfg: ArchConfig, block_params, x, positions):
    mixers = {
        "attn": lambda p, h, _: (
            L.attention_fwd(cfg, p, h, positions, causal=True)[0], None),
        "ssm": lambda p, h, _: (S.ssm_fwd(cfg, p, h), None),
    }
    aux_total = 0.0
    for pi, pat in enumerate(cfg.pattern):
        x, aux, _ = _layer(cfg, pat, block_params[f"pos{pi}"], x, mixers)
        aux_total = aux_total + aux
    return x, aux_total


def _embed(cfg: ArchConfig, params, tokens, *, sharded: bool = True):
    """Token embeddings: the residual stream's first value, in float32
    where ``residual_in_fp32`` holds, else in the compute dtype.
    ``sharded`` constrains them to the activation layout (decode's
    one-token rows are left as the gather makes them)."""
    cd = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("embed"):
        h = params["embed"].astype(cd)[tokens]
        if cfg.residual_in_fp32:
            h = h.astype(jnp.float32)
        return constrain(h, "batch", None, "embed_act") if sharded else h


def _head(cfg: ArchConfig, params, h):
    """Final norm and LM head -> float32 logits; a tied head is the
    embedding, transposed."""
    cd = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("lm_head"):
        h = L.apply_norm(cfg, params["final_norm"], h)
        w = (params["embed"].T if cfg.tie_embeddings
             else params["lm_head"])
        return jnp.matmul(h, w.astype(cd), preferred_element_type=jnp.float32)


def _positions_for(cfg: ArchConfig, tokens, offset: int = 0):
    B, Sq = tokens.shape[0], tokens.shape[1]
    pos = jnp.arange(Sq, dtype=jnp.int32)[None, :] + offset
    pos = jnp.broadcast_to(pos, (B, Sq))
    if cfg.m_rope:
        # text stream: all three position channels equal (vision stub
        # would supply real (t, h, w) ids)
        pos = jnp.broadcast_to(pos[None], (3, B, Sq))
    return pos


def forward(cfg: ArchConfig, params, tokens, positions=None
            ) -> tuple[jax.Array, jax.Array]:
    """tokens: (B, S) int32 -> logits (B, S, V) in f32, aux loss."""
    h = _embed(cfg, params, tokens)
    if positions is None:
        positions = _positions_for(cfg, tokens)

    body = functools.partial(_block_fwd, cfg)
    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)

    def scan_fn(carry, block_params):
        x, aux = carry
        x, aux_b = body(block_params, x, positions)
        return (x, aux + aux_b), None

    (h, aux), _ = jax.lax.scan(scan_fn, (h, jnp.float32(0.0)),
                               params["blocks"],
                               unroll=cfg.n_blocks if cfg.scan_unroll else 1)
    logits = constrain(_head(cfg, params, h), "batch", None, "vocab")
    return logits, aux


def loss_fn(cfg: ArchConfig, params, tokens, labels,
            z_loss: float = 1e-4) -> jax.Array:
    logits, aux = forward(cfg, params, tokens)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (lse - ll).mean()
    zl = z_loss * jnp.square(lse).mean()
    return nll + zl + aux


# -------------------------------------------------------------------- decode

def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=None) -> Tree:
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    cache: dict = {}
    for pi, pat in enumerate(cfg.pattern):
        if pat.mixer == "attn":
            shape = (cfg.n_blocks, batch, cfg.n_kv_heads, max_len,
                     cfg.head_dim)
            cache[f"pos{pi}"] = {"k": jnp.zeros(shape, dtype),
                                 "v": jnp.zeros(shape, dtype)}
        else:
            conv_dim = cfg.ssm_inner + 2 * cfg.ssm_groups * cfg.ssm_state
            cache[f"pos{pi}"] = {
                "conv": jnp.zeros((cfg.n_blocks, batch,
                                   cfg.ssm_conv_width - 1, conv_dim), dtype),
                "state": jnp.zeros((cfg.n_blocks, batch, cfg.ssm_heads,
                                    cfg.ssm_head_dim, cfg.ssm_state),
                                   jnp.float32),
            }
    return cache


def cache_specs(cfg: ArchConfig) -> Tree:
    specs: dict = {}
    for pi, pat in enumerate(cfg.pattern):
        if pat.mixer == "attn":
            ax = ("layers", "batch", "kv_heads", None, None)
            specs[f"pos{pi}"] = {"k": ax, "v": ax}
        else:
            specs[f"pos{pi}"] = {
                "conv": ("layers", "batch", None, "conv_dim"),
                "state": ("layers", "batch", "ssm_heads", None, None),
            }
    return specs


def _cached_layers(cfg: ArchConfig, params, h, cache, mixers):
    """The layer scan of prefill and decode: each block's layers read
    their cache entries and return new ones.

    An SSM position's entries (conv window, SSD state) are rewritten
    whole every step, so they ride in the scan's carry: block i reads
    its slice of the stack and writes the new one back at i, and XLA
    updates the donated cache in place. Scanned as ``xs``/``ys``
    instead, the new entries would be stacked in a temporary and the
    stack copied into the output. An attention position's K/V entries
    stay ``xs``/``ys``: a step adds one row to them, a different update;
    a model with no SSM position carries nothing and scans no index."""
    carried = {f"pos{pi}" for pi, pat in enumerate(cfg.pattern)
               if pat.mixer == "ssm"}
    stack = {k: c for k, c in cache.items() if k in carried}
    xs = (params["blocks"], {k: c for k, c in cache.items()
                             if k not in carried})
    if carried:
        xs += (jnp.arange(cfg.n_blocks),)

    def scan_fn(carry, xs):
        x, stack = carry
        block_params, cache_slice, *index = xs
        new_slice = {}
        for pi, pat in enumerate(cfg.pattern):
            key = f"pos{pi}"
            if key in carried:
                entry = jax.tree.map(
                    lambda c: jax.lax.dynamic_index_in_dim(
                        c, index[0], keepdims=False), stack[key])
            else:
                entry = cache_slice[key]
            x, _, entry = _layer(cfg, pat, block_params[key], x, mixers,
                                 entry)
            if key in carried:
                stack[key] = jax.tree.map(
                    lambda c, e: jax.lax.dynamic_update_index_in_dim(
                        c, e, index[0], 0), stack[key], entry)
            else:
                new_slice[key] = entry
        return (x, stack), new_slice

    (h, stack), new_cache = jax.lax.scan(
        scan_fn, (h, stack), xs,
        unroll=cfg.n_blocks if cfg.scan_unroll else 1)
    return h, {**new_cache, **stack}


def prefill(cfg: ArchConfig, params, tokens, max_len: int | None = None
            ) -> tuple[jax.Array, Tree]:
    """Run the prompt, return last-position logits + a filled cache of
    size max_len (>= prompt length)."""
    B, Sp = tokens.shape
    max_len = max_len or Sp
    cd = jnp.dtype(cfg.compute_dtype)
    h = _embed(cfg, params, tokens)
    positions = _positions_for(cfg, tokens)
    cache = init_cache(cfg, B, max_len)

    def attn(p, hn, c):
        mix, (k, v) = L.attention_fwd(cfg, p, hn, positions, causal=True)
        with jax.named_scope("attn"):
            ck = jax.lax.dynamic_update_slice(c["k"], k.astype(cd),
                                              (0, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(c["v"], v.astype(cd),
                                              (0, 0, 0, 0))
        return mix, {"k": ck, "v": cv}

    def ssm(p, hn, c):
        mix, state, conv = S.ssm_fwd_with_cache(cfg, p, hn)
        return mix, {"conv": conv.astype(cd), "state": state}

    h, new_cache = _cached_layers(cfg, params, h, cache,
                                  {"attn": attn, "ssm": ssm})
    return _head(cfg, params, h[:, -1:])[:, 0], new_cache


def decode_step(cfg: ArchConfig, params, cache, tokens, pos
                ) -> tuple[jax.Array, Tree]:
    """One decode step. tokens: (B, 1) int32; pos: scalar int32 (number
    of tokens already in the cache). Returns (logits (B, V), cache)."""
    h = _embed(cfg, params, tokens, sharded=False)

    def attn(p, hn, c):
        mix, ck, cv = L.attention_decode(cfg, p, hn, c["k"], c["v"], pos)
        return mix, {"k": ck, "v": cv}

    def ssm(p, hn, c):
        mix, conv, state = S.ssm_decode(cfg, p, hn, c["conv"], c["state"])
        return mix, {"conv": conv, "state": state}

    h, new_cache = _cached_layers(cfg, params, h, cache,
                                  {"attn": attn, "ssm": ssm})
    return _head(cfg, params, h)[:, 0], new_cache
