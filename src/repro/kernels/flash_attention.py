"""Flash attention (GQA, causal) as a Pallas TPU kernel.

Used by the serving path (prefill + decode) and by the roofline/perf
work; the training path uses the differentiable jnp oracle in ref.py.

Online-softmax tiling: grid (B, Hq, Sq/bq, Skv/bk) with the KV dimension
innermost ("arbitrary" = sequential) carrying running max / sum / output
accumulators in VMEM scratch. Bounds (true Sq, Skv, causal offset)
arrive via scalar prefetch — the same dynamic-bound discipline as
flex_gemm: one compiled kernel serves every sequence length.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _attn_kernel(bounds_ref, q_ref, k_ref, v_ref, o_ref,
                 m_ref, l_ref, acc_ref, *,
                 block_q: int, block_k: int, causal: bool, scale: float):
    kv_step = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(kv_step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sq = bounds_ref[0]          # true query length
    skv = bounds_ref[1]         # true kv length
    q_idx = pl.program_id(2)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, D)

    # zero padded KV rows: the boundary block may be filled with
    # uninitialized memory and 0 * NaN would poison the p @ v dot
    kv_valid = (kv_step * block_k
                + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)) < skv
    k = jnp.where(kv_valid, k, 0.0)
    v = jnp.where(kv_valid, v, 0.0)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kv_step * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < skv
    if causal:
        # query i attends to kv positions <= i + (skv - sq)
        mask &= k_pos <= q_pos + (skv - sq)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]                                   # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(kv_step == n_kv - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)   # fully-masked rows -> 0 output
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 256, block_k: int = 512,
                           interpret: bool = False):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D); returns (B, Hq, Sq, D).

    GQA: each group of Hq//Hkv query heads reads the same KV head (the
    BlockSpec index map folds the group mapping — no KV materialization).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = 1.0 / float(np.sqrt(D))
    bq = min(block_q, max(8, Sq))
    bk = min(block_k, max(128, Skv))
    grid = (B, Hq, pl.cdiv(Sq, bq), pl.cdiv(Skv, bk))
    bounds = jnp.array([Sq, Skv], dtype=jnp.int32)

    out = pl.pallas_call(
        functools.partial(_attn_kernel, block_q=bq, block_k=bk,
                          causal=causal, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, D),
                             lambda b, h, i, j, bnds: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bk, D),
                             lambda b, h, i, j, bnds, g=group:
                             (b, h // g, j, 0)),
                pl.BlockSpec((1, 1, bk, D),
                             lambda b, h, i, j, bnds, g=group:
                             (b, h // g, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, D),
                                   lambda b, h, i, j, bnds: (b, h, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(bounds, q, k, v)
    return out
