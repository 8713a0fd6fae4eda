"""Per-arch smoke tests (REDUCED configs, CPU): one forward + one train
step, asserting output shapes and no NaNs; decode-path consistency."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, all_configs, get_config
from repro.models import encdec, lm
from repro.optim import adamw

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=16, seed=0):
    rng = np.random.default_rng(seed)
    out = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                                 jnp.int32),
           "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                                 jnp.int32)}
    if cfg.is_encdec:
        out["frames"] = jnp.asarray(rng.standard_normal((B, S, cfg.d_model)),
                                    jnp.float32)
    return out


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_shapes_no_nan(arch):
    cfg = get_config(arch, reduced=True)
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    if cfg.is_encdec:
        params, specs = encdec.init(cfg, KEY)
        logits, aux = encdec.forward(cfg, params, batch["frames"],
                                     batch["tokens"])
    else:
        params, specs = lm.init(cfg, KEY)
        logits, aux = lm.forward(cfg, params, batch["tokens"])
    assert logits.shape == (B, S, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())
    # specs mirror params exactly
    jax.tree.map(lambda p, s: None, params, specs,
                 is_leaf=lambda x: isinstance(x, tuple) and all(
                     isinstance(e, (str, type(None))) for e in x))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = get_config(arch, reduced=True)
    batch = _batch(cfg)
    opt = adamw.OptConfig(total_steps=10, warmup_steps=2)
    if cfg.is_encdec:
        params, _ = encdec.init(cfg, KEY)

        def lf(p):
            return encdec.loss_fn(cfg, p, batch["frames"],
                                  batch["tokens"], batch["labels"])
    else:
        params, _ = lm.init(cfg, KEY)

        def lf(p):
            return lm.loss_fn(cfg, p, batch["tokens"], batch["labels"])

    state = adamw.init_state(params, opt)
    loss, grads = jax.value_and_grad(lf)(params)
    new_params, new_state, metrics = adamw.apply_updates(
        params, grads, state, opt)
    assert np.isfinite(float(loss))
    assert float(metrics["grad_norm"]) > 0
    assert int(new_state["step"]) == 1
    # params actually moved
    delta = sum(float(jnp.abs(a - b).sum()) for a, b in
                zip(jax.tree.leaves(params), jax.tree.leaves(new_params)))
    assert delta > 0


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS
                                  if not get_config(a).is_encdec])
def test_decode_consistency(arch):
    cfg = get_config(arch, reduced=True)
    if cfg.n_experts:
        # capacity drops differ between grouped prefill and per-token
        # decode; disable drops for the equivalence check
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    B, S, Sp = 2, 12, 8
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    params, _ = lm.init(cfg, jax.random.PRNGKey(1))
    full, _ = lm.forward(cfg, params, tokens)
    pre, cache = lm.prefill(cfg, params, tokens[:, :Sp], max_len=S)
    errs = [float(jnp.max(jnp.abs(pre - full[:, Sp - 1])))]
    for t in range(Sp, S):
        step, cache = lm.decode_step(cfg, params, cache,
                                     tokens[:, t:t + 1], jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(step - full[:, t]))))
    assert max(errs) < 2e-3, errs


def test_whisper_decode_consistency():
    cfg = get_config("whisper-medium", reduced=True)
    B, S = 2, 10
    rng = np.random.default_rng(2)
    params, _ = encdec.init(cfg, jax.random.PRNGKey(2))
    frames = jnp.asarray(rng.standard_normal((B, 16, cfg.d_model)),
                         jnp.float32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    full, _ = encdec.forward(cfg, params, frames, tokens)
    pre, cache = encdec.prefill(cfg, params, frames, tokens[:, :6],
                                max_len=S)
    errs = [float(jnp.max(jnp.abs(pre - full[:, 5])))]
    for t in range(6, S):
        sl, cache = encdec.decode_step(cfg, params, cache,
                                       tokens[:, t:t + 1], jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(sl - full[:, t]))))
    assert max(errs) < 2e-3


# (n_heads, n_kv_heads): query group 1 (MHA), 4, 6, and 8 over one cache
# head (MQA)
DECODE_HEADS = [(4, 4), (8, 2), (12, 2), (8, 1)]
DECODE_TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}
MAX_LEN = 16


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("valid", [1, MAX_LEN // 2 + 1, MAX_LEN])
@pytest.mark.parametrize("cross", [False, True])
@pytest.mark.parametrize("heads", DECODE_HEADS)
def test_attention_decode_matches_oracle(heads, cross, valid, dtype):
    """Decode attention contracted by query group over the cache as
    stored equals the oracle, which upcasts and repeats the cache, on
    the same q, cache and valid length (``valid`` tokens attended: the
    position written is ``valid - 1``, or ``kv_len`` on the cross
    path)."""
    from repro.kernels import ref
    from repro.models import layers as L
    n_heads, Hc = heads
    cfg = dataclasses.replace(get_config("qwen3-4b", reduced=True),
                              n_heads=n_heads, n_kv_heads=Hc)
    B, D = 2, cfg.head_dim
    p, _ = L.init_attention(cfg, jax.random.PRNGKey(6))
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((B, 1, cfg.d_model)), dtype)
    cache_k, cache_v = (
        jnp.asarray(rng.standard_normal((B, Hc, MAX_LEN, D)), dtype)
        for _ in range(2))
    pos = jnp.int32(valid - 1)
    out, ck, cv = L.attention_decode(cfg, p, x, cache_k, cache_v, pos,
                                     cross=cross,
                                     kv_len=valid if cross else None)

    if cross:
        q, _, _ = L._project_qkv(cfg, p, x, None, rope=False)
        assert ck is cache_k and cv is cache_v
    else:
        q, _, _ = L._project_qkv(cfg, p, x, jnp.full((B, 1), pos),
                                 rope=True)
        # the new token's K/V landed at ``pos``, nothing else moved
        keep = jnp.arange(MAX_LEN)[:, None] != pos
        assert bool(jnp.all(jnp.where(keep, ck == cache_k, True)))
    want = ref.mha_attention(q.transpose(0, 2, 1, 3), ck, cv, causal=False,
                             kv_len=jnp.full((B,), valid, jnp.int32))
    want = want.transpose(0, 2, 1, 3).reshape(B, 1, cfg.q_dim)
    want = want.astype(dtype) @ p["wo"].astype(dtype)
    assert out.dtype == dtype
    tol = DECODE_TOL[dtype]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_m_rope_reduces_to_rope_for_text():
    """qwen2-vl M-RoPE with equal position channels == standard RoPE."""
    from repro.models.layers import apply_rope
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 8, 4, 16)), jnp.float32)
    pos = jnp.arange(8, dtype=jnp.int32)[None].repeat(2, 0)
    std = apply_rope(x, pos, 1e4)
    mpos = jnp.broadcast_to(pos[None], (3, 2, 8))
    mr = apply_rope(x, mpos, 1e4, m_rope_sections=(2, 3, 3))
    np.testing.assert_allclose(std, mr, rtol=1e-6, atol=1e-6)


def test_m_rope_sections_differ_for_spatial_ids():
    from repro.models.layers import apply_rope
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((1, 4, 2, 16)), jnp.float32)
    pos = jnp.arange(4, dtype=jnp.int32)[None]
    mpos_text = jnp.broadcast_to(pos[None], (3, 1, 4))
    mpos_img = mpos_text.at[1].add(7)   # different h-position ids
    a = apply_rope(x, mpos_text, 1e4, m_rope_sections=(2, 3, 3))
    b = apply_rope(x, mpos_img, 1e4, m_rope_sections=(2, 3, 3))
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3


def test_param_counts_match_names():
    expect = {"internlm2-20b": 20e9, "qwen3-4b": 4.4e9, "qwen1.5-4b": 4e9,
              "nemotron-4-15b": 15.6e9, "whisper-medium": 0.8e9,
              "jamba-1.5-large-398b": 398e9,
              "llama4-maverick-400b-a17b": 395e9, "dbrx-132b": 132e9,
              "mamba2-2.7b": 2.8e9, "qwen2-vl-2b": 1.8e9}
    for arch, cfg in all_configs().items():
        assert abs(cfg.param_count() - expect[arch]) / expect[arch] < 0.08, \
            (arch, cfg.param_count())


def test_moe_capacity_and_balance_loss():
    from repro.models.layers import init_moe, moe_fwd
    cfg = get_config("dbrx-132b", reduced=True)
    p, _ = init_moe(cfg, KEY)
    x = jnp.asarray(np.random.default_rng(5).standard_normal((2, 16, 64)),
                    jnp.float32)
    y, aux = moe_fwd(cfg, p, x)
    assert y.shape == x.shape
    assert float(aux) > 0
    assert not bool(jnp.isnan(y).any())
