"""What the program records for a profile, on the CPU at a tiny size: the
layer scopes in the compiled serving steps' ``op_name`` metadata, the
host spans ``BatchServer.serve`` opens, and its work counters."""

import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

OP_NAME = re.compile(r'op_name="([^"]*)"')


def _scopes_in(hlo_text: str) -> set[str]:
    """Every path component of every ``op_name`` in ``hlo_text``."""
    return {part for name in OP_NAME.findall(hlo_text)
            for part in name.split("/")}


@pytest.fixture(scope="module")
def server():
    from repro.configs import get_config
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import BatchServer
    return BatchServer(get_config("qwen3-4b", reduced=True),
                       make_local_mesh(), max_len=32)


def _requests(lens_and_new):
    from repro.launch.serve import Request
    rng = np.random.default_rng(0)
    return [Request(i, rng.integers(1, 100, n).astype(np.int32), max_new=m)
            for i, (n, m) in enumerate(lens_and_new)]


def test_compiled_serving_steps_carry_layer_scopes(server):
    B, S = 2, 8
    tokens = jnp.zeros((B, S), jnp.int32)
    prefill = server.prefill_fn.lower(server.params, tokens).compile()
    _, cache = server.prefill_fn(server.params, tokens)
    decode = server.decode_fn.lower(server.params, cache,
                                    jnp.zeros((B, 1), jnp.int32),
                                    jnp.int32(S)).compile()
    want = {"attn", "mlp", "norm", "embed", "lm_head"}
    assert want <= _scopes_in(prefill.as_text())
    assert want <= _scopes_in(decode.as_text())


@pytest.mark.parametrize("arch,want", [
    ("qwen3-4b", {"embed", "norm", "attn", "mlp", "lm_head"}),
    ("mamba2-2.7b", {"ssm", "ssm_scan"}),
    ("dbrx-132b", {"moe"})])
def test_training_forward_carries_layer_scopes(arch, want):
    from repro.configs import get_config
    from repro.models import lm
    from repro.models.layers import SCOPES
    cfg = get_config(arch, reduced=True)
    params, _ = lm.abstract_init(cfg)
    text = jax.jit(lambda p, t: lm.forward(cfg, p, t)[0]).lower(
        params, jax.ShapeDtypeStruct((1, 8), jnp.int32)).compile().as_text()
    assert want <= _scopes_in(text) and want <= set(SCOPES)


def test_ssm_scan_is_a_sibling_of_ssm():
    """Both mamba2 serving steps carry the recurrence under ``ssm_scan``
    and the rest of the layer under ``ssm``, never one inside the other
    (a profile gives an operation to the outermost scope it knows)."""
    from repro.configs import get_config
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import BatchServer
    server = BatchServer(get_config("mamba2-2.7b", reduced=True),
                         make_local_mesh(), max_len=16)
    tokens = jnp.zeros((2, 8), jnp.int32)
    _, cache = server.prefill_fn(server.params, tokens)
    for compiled in (
            server.prefill_fn.lower(server.params, tokens).compile(),
            server.decode_fn.lower(server.params, cache,
                                   jnp.zeros((2, 1), jnp.int32),
                                   jnp.int32(8)).compile()):
        paths = [set(n.split("/")) for n in OP_NAME.findall(compiled.as_text())]
        assert any("ssm" in p for p in paths)
        assert any("ssm_scan" in p for p in paths)
        assert not any({"ssm", "ssm_scan"} <= p for p in paths)


def test_serve_counts_its_work(server):
    from repro.launch.serve import ServeCounters
    before = ServeCounters(**vars(server.counters))
    # prompts of 3, 5 and 8 tokens pad to 8; replies of 2, 4 and 1
    out = server.serve(_requests([(3, 2), (5, 4), (8, 1)]))
    assert [len(t) for t in out["outputs"].values()] == [2, 4, 1]
    got = {k: v - getattr(before, k) for k, v in vars(server.counters).items()}
    assert got == {"prompt_tokens": 16, "prefill_positions": 24,
                   "decode_steps": 3, "slots": 12, "tokens_kept": 7}
    server.serve(_requests([(4, 3), (2, 3)]))
    assert vars(server.counters) == {
        k: getattr(before, k) + v for k, v in {
            "prompt_tokens": 22, "prefill_positions": 32,
            "decode_steps": 5, "slots": 18, "tokens_kept": 13}.items()}


def test_serve_opens_exactly_its_five_spans(server, monkeypatch):
    from repro.launch import serve
    opened = []

    class Recorder:
        def __init__(self, name, **_):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    out = server.serve(_requests([(3, 2), (5, 4)]))
    assert "decode_tok_per_s" not in out
    assert set(opened) == set(serve.SPANS) and len(serve.SPANS) == 5
    # one prefill; the first sample and collect after it; then per
    # decode step one of each of rng, decode, sample and collect
    assert Counter(opened) == {serve.SPAN_PREFILL: 1, serve.SPAN_RNG: 4,
                               serve.SPAN_DECODE: 3, serve.SPAN_SAMPLE: 4,
                               serve.SPAN_COLLECT: 4}
    assert opened[:4] == [serve.SPAN_PREFILL, serve.SPAN_RNG,
                          serve.SPAN_SAMPLE, serve.SPAN_COLLECT]
