"""Public kernel API: jit'd wrappers that (a) select interpret mode off
the backend (TPU target, CPU validation), (b) ask the stage-1 DSE for
tile plans (DORA's candidate table driving Pallas BlockSpecs), and
(c) fall back to the jnp oracle where a kernel is not profitable
(tiny shapes) or not applicable.

``use_pallas`` can be forced via set_kernel_mode() for tests/benches.
"""

from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention_pallas
from .flex_gemm import flex_gemm_pallas
from .sfu import (gelu_rows_pallas, layernorm_rows_pallas,
                  rmsnorm_rows_pallas, softmax_rows_pallas)
from .ssd import ssd_pallas

_KERNEL_MODE: Literal["auto", "pallas", "ref"] = "auto"


def set_kernel_mode(mode: Literal["auto", "pallas", "ref"]) -> None:
    global _KERNEL_MODE
    assert mode in ("auto", "pallas", "ref")
    _KERNEL_MODE = mode


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _use_pallas(*dims: int) -> bool:
    if _KERNEL_MODE == "pallas":
        return True
    if _KERNEL_MODE == "ref":
        return False
    # auto: pallas on TPU; on CPU the interpreter is far too slow for the
    # training/serving paths, so auto uses the oracle (kernels are still
    # exercised by the test sweeps in interpret mode).
    return jax.default_backend() == "tpu"


def _plan(M: int, K: int, N: int, dtype) -> tuple[int, int, int]:
    from repro.core.perf_model import plan_tpu_gemm_tiles
    t = plan_tpu_gemm_tiles(M, K, N, dtype_bytes=jnp.dtype(dtype).itemsize)
    return t.block_m, t.block_k, t.block_n


def matmul(a: jax.Array, b: jax.Array, bias: jax.Array | None = None,
           epilogue: str = "none") -> jax.Array:
    """2-D GEMM with fused epilogue; DORA-planned tiles on TPU."""
    M, K = a.shape
    N = b.shape[1]
    if not _use_pallas(M, K, N):
        return ref.gemm(a, b, bias, epilogue)
    bm, bk, bn = _plan(M, K, N, a.dtype)
    return flex_gemm_pallas(a, b, bias, block_m=bm, block_k=bk, block_n=bn,
                            epilogue=epilogue, interpret=_interpret())


def linear(x: jax.Array, w: jax.Array, bias: jax.Array | None = None,
           epilogue: str = "none") -> jax.Array:
    """(..., K) @ (K, N) with leading dims flattened through the kernel."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[-1]
    x2 = x.reshape(-1, K)
    out = matmul(x2, w, bias, epilogue)
    return out.reshape(*lead, N)


def softmax(x: jax.Array) -> jax.Array:
    if not _use_pallas(*x.shape):
        return ref.softmax_rows(x)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    return softmax_rows_pallas(x2, interpret=_interpret()).reshape(*lead, -1)


def layernorm(x, gamma=None, beta=None, eps: float = 1e-5):
    if not _use_pallas(*x.shape):
        return ref.layernorm_rows(x, gamma, beta, eps)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    out = layernorm_rows_pallas(x2, gamma, beta, eps=eps,
                                interpret=_interpret())
    return out.reshape(*lead, -1)


def rmsnorm(x, gamma=None, eps: float = 1e-6):
    if not _use_pallas(*x.shape):
        return ref.rmsnorm_rows(x, gamma, eps)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    out = rmsnorm_rows_pallas(x2, gamma, eps=eps, interpret=_interpret())
    return out.reshape(*lead, -1)


def gelu(x):
    if not _use_pallas(*x.shape):
        return ref.gelu_rows(x)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    return gelu_rows_pallas(x2, interpret=_interpret()).reshape(*lead, -1)


def attention(q, k, v, *, causal: bool = True, kv_len=None):
    """GQA attention; pallas flash kernel on TPU, oracle elsewhere.
    The kernel path requires kv_len=None (dense cache)."""
    if kv_len is not None or not _use_pallas(*q.shape):
        return ref.mha_attention(q, k, v, causal=causal, kv_len=kv_len)
    return flash_attention_pallas(q, k, v, causal=causal,
                                  interpret=_interpret())


def ssd(x, a, b, c, *, chunk: int = 128, initial_state=None):
    """Mamba-2 SSD over (B, S, H, P) inputs (see ref.ssd_scan for the
    contract). Pallas chunked kernel on TPU; ``ref.ssd_chunked``
    elsewhere and whenever an initial state is given."""
    B, S, H, P = x.shape
    G = b.shape[2]
    if not _use_pallas(B, S, H, P) or initial_state is not None:
        return ref.ssd_chunked(x, a, b, c, chunk=chunk,
                               initial_state=initial_state)
    rep = H // G
    pad = (-S) % chunk
    xs = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    as_ = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
    bs = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
    cs = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    xf = jnp.moveaxis(xs, 2, 1).reshape(B * H, Sp, P)
    af = jnp.moveaxis(as_, 2, 1).reshape(B * H, Sp)
    bf = jnp.repeat(bs, rep, axis=2).transpose(0, 2, 1, 3).reshape(
        B * H, Sp, -1)
    cf = jnp.repeat(cs, rep, axis=2).transpose(0, 2, 1, 3).reshape(
        B * H, Sp, -1)
    y = ssd_pallas(xf, af, bf, cf, chunk=chunk, interpret=_interpret())
    y = y.reshape(B, H, Sp, P)[:, :, :S].transpose(0, 2, 1, 3)
    # final state from the oracle path when needed (serving uses
    # ssd_decode_step below instead)
    return y, None


def ssd_decode_step(x_t, a_t, b_t, c_t, state):
    """Single-token SSD decode: state update + readout (serving path).
    x_t: (B, H, P), a_t: (B, H), b_t/c_t: (B, G, N), state: (B, H, P, N)."""
    B, H, P = x_t.shape
    G, N = b_t.shape[1], b_t.shape[2]
    rep = H // G
    bf = jnp.repeat(b_t.astype(jnp.float32), rep, axis=1)
    cf = jnp.repeat(c_t.astype(jnp.float32), rep, axis=1)
    decay = jnp.exp(a_t.astype(jnp.float32))[:, :, None, None]
    state = decay * state + x_t.astype(jnp.float32)[..., None] \
        * bf[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", state, cf)
    return y.astype(x_t.dtype), state
