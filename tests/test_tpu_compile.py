"""Compiles for a described TPU v5e (no chip attached): the Pallas
kernels at real widths and the qwen3-4b and mamba2-2.7b serving steps
at their published widths. Nothing runs; the chip's compiler refuses what would not fit
VMEM or HBM, and block shapes it cannot tile.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, so describing it
while the test workers import this file would make them collect
different tests. Keep every such compile in this one file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.perf_model import plan_tpu_gemm_tiles
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flex_gemm import flex_gemm_pallas
from repro.kernels.sfu import rmsnorm_rows_pallas
from repro.kernels.ssd import ssd_pallas

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("M,K,N", [(4096, 2560, 9728), (4096, 9728, 2560)])
def test_flex_gemm_planned_tiles_compile(no_compile_cache, one_chip,
                                         M, K, N):
    t = plan_tpu_gemm_tiles(M, K, N, dtype_bytes=2)
    compiled = _compile(
        lambda a, b: flex_gemm_pallas(a, b, block_m=t.block_m,
                                      block_k=t.block_k, block_n=t.block_n),
        _sds(one_chip, (M, K)), _sds(one_chip, (K, N)))
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_qwen3_prefill(no_compile_cache,
                                                   one_chip):
    compiled = _compile(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
        _sds(one_chip, (2, 32, 2048, 128)),
        _sds(one_chip, (2, 8, 2048, 128)),
        _sds(one_chip, (2, 8, 2048, 128)))
    assert "tpu_custom_call" in compiled.as_text()


def test_rmsnorm_rows_compile_at_2560(no_compile_cache, one_chip):
    compiled = _compile(lambda x, g: rmsnorm_rows_pallas(x, g),
                        _sds(one_chip, (4096, 2560)),
                        _sds(one_chip, (2560,)))
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_compiles_at_mamba2_widths(no_compile_cache, one_chip):
    cfg = get_config("mamba2-2.7b")
    B, S, chunk = 2, 1024, 128
    BH, Pd, N = B * cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    assert (cfg.ssm_heads, Pd, N) == (80, 64, 128)
    compiled = _compile(
        lambda x, a, b, c: ssd_pallas(x, a, b, c, chunk=chunk),
        _sds(one_chip, (BH, S, Pd)), _sds(one_chip, (BH, S), jnp.float32),
        _sds(one_chip, (BH, S, N)), _sds(one_chip, (BH, S, N)))
    assert "tpu_custom_call" in compiled.as_text()


# ------------------------------------------------------- serving steps

B, MAX_LEN, PROMPT = 8, 2048, 512


def _serving(topo, arch):
    """The server's config, rules, steps and sharded parameter shapes
    for ``arch`` on one described chip."""
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import serving_steps
    from repro.models import lm
    from repro.parallel.sharding import make_rules, params_shardings

    cfg = get_config(arch)
    cfg = dataclasses.replace(cfg, param_dtype=cfg.compute_dtype)
    mesh = make_local_mesh(devices=topo.devices[:1])
    rules = make_rules(cfg, mesh)
    shapes, specs = lm.abstract_init(cfg)
    shards = params_shardings(rules, shapes, specs)
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shards)
    prefill, decode = serving_steps(cfg, rules, MAX_LEN)
    return cfg, NamedSharding(mesh, P()), params, prefill, decode


def _decode_compiled(serving, batch):
    from repro.models import lm

    cfg, rep, params, _, decode = serving
    cache_shapes = jax.eval_shape(lambda: lm.init_cache(cfg, batch, MAX_LEN))
    cache = jax.tree.map(lambda s: _sds(rep, s.shape, s.dtype), cache_shapes)
    return decode.lower(params, cache, _sds(rep, (batch, 1), jnp.int32),
                        _sds(rep, (), jnp.int32)).compile()


@pytest.fixture(scope="module")
def qwen3_serving(topo):
    return _serving(topo, "qwen3-4b")


def test_qwen3_params_are_bf16_at_published_size(qwen3_serving):
    cfg, _, params, _, _ = qwen3_serving
    leaves = jax.tree.leaves(params)
    assert {x.dtype for x in leaves} == {jnp.dtype(jnp.bfloat16)}
    assert sum(x.size for x in leaves) == cfg.param_count()
    assert round(cfg.param_count() / 1e9, 2) == 4.41


def test_qwen3_prefill_compiles_and_fits(no_compile_cache, qwen3_serving):
    _, rep, params, prefill, _ = qwen3_serving
    tokens = _sds(rep, (B, PROMPT), jnp.int32)
    compiled = prefill.lower(params, tokens).compile()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.fixture(scope="module")
def qwen3_decode(no_compile_cache, qwen3_serving):
    """The qwen3-4b decode step compiled for one described chip."""
    return _decode_compiled(qwen3_serving, B)


def test_qwen3_decode_compiles_and_fits(qwen3_decode):
    assert _device_bytes(qwen3_decode) < HBM_BYTES


def test_qwen3_decode_never_widens_the_cache(qwen3_decode):
    """Decode attention contracts the bf16 cache by query group: no
    float32 copy of one layer's cache, nor of one repeated to the query
    heads, is made, and the temporaries stay below the 3.02e9 B that
    such copies took (2.42e9 B without them)."""
    text = qwen3_decode.as_text()
    for shape in ("f32[8,8,4,2048,128]", "f32[8,8,2048,128]",
                  "f32[8,32,2048,128]"):
        assert shape not in text
    assert qwen3_decode.memory_analysis().temp_size_in_bytes < 2.6e9


def test_qwen3_decode_fusions_keep_layer_scopes(qwen3_decode):
    """The chip's compiler keeps the model's named scopes on the fused
    operations, where a profile of the chip finds them."""
    scopes = set()
    for line in qwen3_decode.as_text().splitlines():
        m = re.search(r"= \S+ fusion\(.*op_name=\"([^\"]*)\"", line)
        if m:
            scopes |= set(m.group(1).split("/"))
    assert {"attn", "mlp"} <= scopes


# ---------------------------------------------- mamba2-2.7b serving steps

# the chat cell's clients and its widest padded prompt; each step fits
# 90% of the chip (decode 8,126,114,304 B when compiled for a described
# v5e); prefill stays within the 12,924,396,032 B it took when its SSD
# ran token by token (11,515,799,552 B in the chunked form)
CHAT_CLIENTS, CHAT_WIDTH = 16, 1536
FIT = 0.9 * HBM_BYTES
TOKEN_SCAN_PREFILL_BYTES = 12_924_396_032


def _trip_counts(text, scope):
    """The trip counts of the compiled ``while`` loops traced under
    ``scope``: the bound each loop's condition compares its counter
    with."""
    counts = []
    loops = r" while\(.*condition=(%[\w.\-]+).*op_name=\"([^\"]*)"
    for m in re.finditer(loops, text):
        if scope in m.group(2).split("/"):
            cond = text.split(f"\n{m.group(1)} ", 1)[1].split("\n}", 1)[0]
            counts += [int(n) for n in re.findall(r"constant\((\d+)\)",
                                                  cond)]
    return counts


@pytest.fixture(scope="module")
def mamba2_serving(topo):
    return _serving(topo, "mamba2-2.7b")


def test_mamba2_params_are_bf16_at_published_size(mamba2_serving):
    cfg, _, params, _, _ = mamba2_serving
    leaves = jax.tree.leaves(params)
    assert {x.dtype for x in leaves} == {jnp.dtype(jnp.bfloat16)}
    assert sum(x.size for x in leaves) == cfg.param_count() == 2_702_599_680


def test_mamba2_prefill_compiles_and_fits(no_compile_cache, mamba2_serving):
    _, rep, params, prefill, _ = mamba2_serving
    tokens = _sds(rep, (CHAT_CLIENTS, CHAT_WIDTH), jnp.int32)
    compiled = prefill.lower(params, tokens).compile()
    assert _device_bytes(compiled) <= TOKEN_SCAN_PREFILL_BYTES < FIT
    # the SSD loops over its chunks, never over the prompt's positions
    trips = _trip_counts(compiled.as_text(), "ssm_scan")
    assert trips and max(trips) <= CHAT_WIDTH // 16, trips


@pytest.fixture(scope="module")
def mamba2_decode(no_compile_cache, mamba2_serving):
    """The mamba2-2.7b decode step compiled for one described chip."""
    return _decode_compiled(mamba2_serving, CHAT_CLIENTS)


def test_mamba2_decode_compiles_and_fits(mamba2_decode):
    assert _device_bytes(mamba2_decode) < FIT
    # the recurrence and its skip term run under their own scope
    assert 'ssm_scan' in mamba2_decode.as_text()


def test_mamba2_decode_updates_the_state_in_place(mamba2_decode):
    """The layer scan carries the stacked SSD state and writes each
    layer's slice back into the donated cache: no copy of the whole
    f32 state stack is made, and the temporaries stay far below the
    2,717,707,776 B that stacking the new states as the scan's output
    took (290,304 B carried)."""
    stack = "f32[64,16,80,64,128]"
    copies = [line for line in mamba2_decode.as_text().splitlines()
              if re.search(r"= " + re.escape(stack) + r"\S* copy\(", line)]
    assert not copies, copies
    assert mamba2_decode.memory_analysis().temp_size_in_bytes < 1e8
