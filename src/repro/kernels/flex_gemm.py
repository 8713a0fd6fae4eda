"""flex_gemm: DORA's dynamic-loop-bound MMU as a Pallas TPU kernel.

The paper's flexible-parallelism mechanism (§3.3, Fig. 4b) keeps ONE
resident kernel program and feeds it runtime loop bounds from the MMU
instruction (`bound_i`, `bound_k`, `bound_j`), so arbitrary MM shapes run
without padding and without per-shape programs. The TPU-native analogue
implemented here:

  * one compiled kernel per *block shape* (not per problem shape);
  * the true operand bounds (M, K, N) arrive as a scalar-prefetch
    operand — the literal instruction word — via
    ``pltpu.PrefetchScalarGridSpec``;
  * remainder tiles are handled by in-kernel masking against the bounds
    (the dynamic-loop-bound equivalent: no HBM padding, boundary blocks
    compute only their valid region);
  * the fused epilogue (bias + GELU / ReLU / squared-ReLU / SiLU)
    mirrors the MMU->SFU tile pipelining of §3.5.

Block shapes (the LMU composition of §3.2) are chosen per problem shape
by the stage-1 DSE (``repro.core.perf_model.plan_tpu_gemm_tiles``) —
VMEM-budgeted, MXU-aligned (multiples of 8x128).

Grid: (m_tiles, n_tiles, k_tiles), k innermost ("arbitrary" semantics)
accumulating into an fp32 VMEM scratch; the epilogue runs on the last k
step before the single store of each (m, n) block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.perf_model import TPU_VMEM_LIMIT_BYTES

EPILOGUES = ("none", "bias", "gelu", "relu", "relu2", "silu",
             "bias_gelu", "bias_relu", "bias_relu2", "bias_silu")


def _apply_epilogue(acc, bias, epilogue: str):
    if epilogue.startswith("bias"):
        acc = acc + bias
    if epilogue.endswith("gelu"):
        acc = jax.nn.gelu(acc)
    elif epilogue.endswith("relu2"):
        r = jnp.maximum(acc, 0.0)
        acc = r * r
    elif epilogue.endswith("relu"):
        acc = jnp.maximum(acc, 0.0)
    elif epilogue.endswith("silu"):
        acc = jax.nn.silu(acc)
    return acc


def _flex_gemm_kernel(bounds_ref,            # scalar prefetch: [M, K, N]
                      a_ref, b_ref, bias_ref, o_ref, acc_ref, *,
                      block_m: int, block_k: int, block_n: int,
                      epilogue: str, out_dtype):
    """One (m, n, k) grid step: acc += mask(a) @ mask(b)."""
    k_idx = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)

    # --- dynamic-bound masking (the bound_i/bound_k/bound_j decode) ----
    k_bound = bounds_ref[1]
    k_base = k_idx * block_k
    k_ids = jax.lax.broadcasted_iota(jnp.int32, (block_m, block_k), 1)
    a = jnp.where(k_base + k_ids < k_bound, a, 0.0)
    # b's K rows: mask rows beyond the bound (columns of a already 0 —
    # masking one side suffices for the dot, but masking both keeps the
    # accumulator free of inf/nan from uninitialized memory)
    kb_ids = jax.lax.broadcasted_iota(jnp.int32, (block_k, block_n), 0)
    b = jnp.where(k_base + kb_ids < k_bound, b, 0.0)

    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _store():
        acc = acc_ref[...]
        bias = (bias_ref[...].astype(jnp.float32)
                if bias_ref is not None else None)
        acc = _apply_epilogue(acc, bias, epilogue)
        o_ref[...] = acc.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "block_n", "epilogue",
                     "out_dtype", "interpret"))
def flex_gemm_pallas(a: jax.Array, b: jax.Array,
                     bias: jax.Array | None = None, *,
                     block_m: int = 256, block_k: int = 512,
                     block_n: int = 256, epilogue: str = "none",
                     out_dtype=None, interpret: bool = False) -> jax.Array:
    """C[M,N] = epilogue(A[M,K] @ B[K,N] (+ bias[N]))."""
    if epilogue not in EPILOGUES:
        raise ValueError(f"unknown epilogue {epilogue!r}")
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    block_m = min(block_m, max(8, M))
    block_n = min(block_n, max(128, N))
    block_k = min(block_k, max(128, K))

    grid = (pl.cdiv(M, block_m), pl.cdiv(N, block_n), pl.cdiv(K, block_k))
    bounds = jnp.array([M, K, N], dtype=jnp.int32)

    has_bias = bias is not None
    if has_bias:
        bias2d = bias.reshape(1, N)
        in_specs = [
            pl.BlockSpec((block_m, block_k), lambda i, j, k, bnds: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k, bnds: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k, bnds: (0, j)),
        ]
        operands = (a, b, bias2d)
        kernel = functools.partial(
            _flex_gemm_kernel, block_m=block_m, block_k=block_k,
            block_n=block_n, epilogue=epilogue, out_dtype=out_dtype)
        wrapped = kernel
    else:
        in_specs = [
            pl.BlockSpec((block_m, block_k), lambda i, j, k, bnds: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k, bnds: (k, j)),
        ]
        operands = (a, b)

        def wrapped(bounds_ref, a_ref, b_ref, o_ref, acc_ref):
            return _flex_gemm_kernel(
                bounds_ref, a_ref, b_ref, None, o_ref, acc_ref,
                block_m=block_m, block_k=block_k, block_n=block_n,
                epilogue=epilogue, out_dtype=out_dtype)

    out = pl.pallas_call(
        wrapped,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda i, j, k, bnds: (i, j)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=TPU_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(bounds, *operands)
    return out
