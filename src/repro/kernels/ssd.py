"""Mamba-2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

The chunked algorithm (Dao & Gu, arXiv:2405.21060):

  intra-chunk : Y_diag = (tril(exp(segsum(a))) * (C B^T)) X   — MXU work
  chunk state : S_n    = decay * S_{n-1} + (B * decay_in)^T X
  inter-chunk : Y_off  = exp(cumsum(a)) * (C S_{n-1}^T)

Grid: (B*H, n_chunks) with the chunk dimension sequential; the (P, N)
state lives in VMEM scratch across chunk steps and resets when a new
(batch, head) row starts. One compiled kernel serves every sequence
length (chunk count is the grid; the tail chunk is masked against the
true length from scalar prefetch).

Inputs arrive flattened/broadcast per head:
  x: (BH, S, P)   a: (BH, S)   b, c: (BH, S, N)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(bounds_ref, x_ref, a_ref, b_ref, c_ref, y_ref, state_ref,
                *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _reset():
        state_ref[...] = jnp.zeros_like(state_ref)

    seq_len = bounds_ref[0]
    base = ci * chunk
    x = x_ref[0].astype(jnp.float32)        # (L, P)
    a = a_ref[0].astype(jnp.float32)        # (1, L): log-decays on lanes
    b = b_ref[0].astype(jnp.float32)        # (L, N)
    c = c_ref[0].astype(jnp.float32)        # (L, N)

    # mask the tail chunk: positions >= seq_len behave as identity
    # (decay 1 would corrupt the state; use a=-inf -> decay 0 for x,b and
    # simply zero x so the state stops changing, y masked on store side)
    valid_row = (base + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
                 ) < seq_len
    valid_col = (base + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
                 ) < seq_len
    a = jnp.where(valid_row, a, 0.0)
    x = jnp.where(valid_col, x, 0.0)
    b = jnp.where(valid_col, b, 0.0)

    # cumulative log-decay as a row and as a column, both by one matmul
    # with the lower-triangular ones matrix (no 1-D cumsum or transpose)
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = li >= lj
    tri = causal.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    acs_col = jax.lax.dot_general(tri, a, (((1,), (1,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)  # (L,1)
    acs_row = jax.lax.dot_general(a, tri, (((1,), (1,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)  # (1,L)
    L = jnp.where(causal, jnp.exp(acs_col - acs_row), 0.0)

    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    y_diag = jax.lax.dot_general(cb * L, x, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    state = state_ref[...]                   # (P, N)
    y_off = jax.lax.dot_general(c, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_off = y_off * jnp.exp(acs_col)

    a_total = jnp.sum(a, axis=1, keepdims=True)      # (1, 1)
    decay_in = jnp.exp(a_total - acs_col)            # (L, 1)
    bx = jax.lax.dot_general(x, b * decay_in,
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (P, N)
    state_ref[...] = jnp.exp(a_total) * state + bx

    y_ref[0] = (y_diag + y_off).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(x, a, b, c, *, chunk: int = 128, interpret: bool = False):
    """x: (BH, S, P), a: (BH, S), b/c: (BH, S, N) -> y: (BH, S, P).

    S is padded to a chunk multiple by the wrapper (ops.py) when needed;
    the true length is masked in-kernel via scalar prefetch. ``a`` is
    carried as (BH, 1, S) so its block's last two dims are (full, chunk).
    """
    BH, S, P = x.shape
    N = b.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    bounds = jnp.array([S], dtype=jnp.int32)

    return pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, nc),
            in_specs=[
                pl.BlockSpec((1, chunk, P), lambda i, j, bnds: (i, j, 0)),
                pl.BlockSpec((1, 1, chunk), lambda i, j, bnds: (i, 0, j)),
                pl.BlockSpec((1, chunk, N), lambda i, j, bnds: (i, j, 0)),
                pl.BlockSpec((1, chunk, N), lambda i, j, bnds: (i, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, chunk, P),
                                   lambda i, j, bnds: (i, j, 0)),
            scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(bounds, x, a.reshape(BH, 1, S), b, c)
