"""mamba2 against a plain float32 Mamba-2 written out here (reduced
sizes, CPU): the training forward, and prefill followed by decode steps
through the conv window and SSD state; prefill's chunked SSD against the
token recurrence. Also the parameter counts of the SSM-bearing configs
and the tied head."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.kernels import ref
from repro.launch.serve import Request, left_pad
from repro.models import layers, lm, ssm

HI = jax.lax.Precision.HIGHEST
# float32 on both sides: the program's scan orders its sums unlike the
# token loop below, which moved logits of size ~0.6 by 2.4e-7 on the CPU;
# the old skip term moved them by 0.67
TOL = 1e-5


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def plain_mamba2(cfg, params, tokens, skip="x"):
    """Logits (S, V) of one sequence, token by token: per layer
    h += out_proj(rms(y * silu(z))), y_t = C_t s_t + D x_t with
    s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_tᵀ. ``skip="x_dt"`` adds
    D (x_t dt_t) instead, the form the program once had."""
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    din, K, eps = cfg.ssm_inner, cfg.ssm_conv_width, cfg.rms_norm_eps
    h = params["embed"][tokens]
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["blocks"]["pos0"])
        p = lp["ssm"]
        proj = jnp.dot(_rms(h, lp["norm1"]["scale"], eps), p["in_proj"],
                       precision=HI)
        z, xbc, dt = proj[:, :din], proj[:, din:-H], proj[:, -H:]
        xbc = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
        xbc = jax.nn.silu(sum(xbc[k:k + len(tokens)] * p["conv_w"][k]
                              for k in range(K)) + p["conv_b"])
        x, b, c = xbc[:, :din], xbc[:, din:din + N], xbc[:, din + N:]
        dt = jax.nn.softplus(dt + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
        s = jnp.zeros((H, P, N))
        ys = []
        for t in range(len(tokens)):
            xt = x[t].reshape(H, P)
            s = (jnp.exp(dt[t] * A)[:, None, None] * s
                 + (dt[t][:, None] * xt)[..., None] * b[t])
            y = jnp.einsum("hpn,n->hp", s, c[t], precision=HI)
            y = y + p["D"][:, None] * xt * (
                dt[t][:, None] if skip == "x_dt" else 1.0)
            ys.append(y.reshape(din))
        y = jnp.stack(ys) * jax.nn.silu(z)
        h = h + jnp.dot(_rms(y, p["norm"], eps), p["out_proj"], precision=HI)
    h = _rms(h, params["final_norm"]["scale"], eps)
    return jnp.dot(h, params["embed"].T, precision=HI)


@pytest.fixture(scope="module")
def model():
    cfg = get_config("mamba2-2.7b", reduced=True)
    assert cfg.compute_dtype == "float32" and cfg.tie_embeddings
    params, _ = lm.init(cfg, jax.random.PRNGKey(3))
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (2, 12)), jnp.int32)
    return cfg, params, tokens


def _prefill_then_decode(cfg, params, tokens, n_prompt):
    """Logits at positions n_prompt - 1 .. S - 1, served as the server
    does: prefill of the prompt, then one decode step a token."""
    S = tokens.shape[1]
    logits, cache = lm.prefill(cfg, params, tokens[:, :n_prompt], max_len=S)
    out = [logits]
    for t in range(n_prompt, S):
        logits, cache = lm.decode_step(cfg, params, cache,
                                       tokens[:, t:t + 1], jnp.int32(t))
        out.append(logits)
    return jnp.stack(out, 1)


@pytest.mark.parametrize("skip", ["x", "x_dt"])
@pytest.mark.parametrize("path", ["forward", "prefill_decode"])
def test_program_matches_the_plain_recurrence(model, path, skip):
    """D·x agrees to float32 rounding on both serving paths; the old
    D·(x·dt) is off by far more than the tolerance."""
    cfg, params, tokens = model
    if path == "forward":
        got, n0 = lm.forward(cfg, params, tokens)[0], 0
    else:
        # a 2-token prompt leaves less than the conv's 3-token window
        n0 = 2
        got = _prefill_then_decode(cfg, params, tokens, n0)
    want = jnp.stack([plain_mamba2(cfg, params, t, skip) for t in tokens])
    err = float(jnp.max(jnp.abs(got - want[:, max(n0 - 1, 0):])))
    if skip == "x":
        assert err < TOL, err
    else:
        assert err > 100 * TOL, err


# prompt lengths of a batch of two, padded on the left to the longest;
# the chunk is min(128, max(16, S)) positions
PROMPTS = {
    "under_the_conv_window": (2, 2),
    "whole_chunks": (256, 256),
    "tail_pad": (200, 200),
    "left_padded": (200, 77),
}


@pytest.mark.parametrize("lengths", PROMPTS.values(), ids=PROMPTS.keys())
def test_prefill_ssd_matches_the_token_recurrence(model, lengths):
    """``ssm_fwd_with_cache``'s chunked SSD gives the token-by-token
    recurrence's output, final state and conv window to float32
    rounding, whatever the prompt's length."""
    cfg, params, _ = model
    rng = np.random.default_rng(sum(lengths))
    tokens = jnp.asarray(left_pad([
        Request(i, rng.integers(1, cfg.vocab_size, n).astype(np.int32))
        for i, n in enumerate(lengths)]))
    lp = jax.tree.map(lambda a: a[0], params["blocks"]["pos0"])
    p = lp["ssm"]
    x = layers.apply_norm(cfg, lp["norm1"], params["embed"][tokens])
    got = ssm.ssm_fwd_with_cache(cfg, p, x)

    z, xh, bg, cg, dt, a, window = ssm._mix_in(cfg, p, x)
    y, state = ref.ssd_scan(xh * dt[..., None], a, bg, cg)
    want = ssm._mix_out(cfg, p, ssm._skip(p, y, xh), z), state, window
    for g, w in zip(got, want):
        assert g.shape == w.shape
        scale = float(jnp.max(jnp.abs(w)))
        assert float(jnp.max(jnp.abs(g - w))) < TOL * scale


def test_decode_writes_each_layers_cache_to_its_own_slot(model):
    """Prefilling 5 tokens and decoding the other 7 one at a time leaves,
    layer by layer, the SSD state and conv window that prefilling all 12
    leaves, to float32 rounding: decode's in-place write of each layer's
    entry into the carried stack lands at that layer."""
    cfg, params, tokens = model
    cfg = dataclasses.replace(cfg, n_layers=3)
    params, _ = lm.init(cfg, jax.random.PRNGKey(4))
    S, n_prompt = tokens.shape[1], 5
    _, cache = lm.prefill(cfg, params, tokens[:, :n_prompt], max_len=S)
    for t in range(n_prompt, S):
        _, cache = lm.decode_step(cfg, params, cache, tokens[:, t:t + 1],
                                  jnp.int32(t))
    _, want = lm.prefill(cfg, params, tokens, max_len=S)
    for name in ("state", "conv"):
        got, ref_ = cache["pos0"][name], want["pos0"][name]
        assert got.shape[0] == ref_.shape[0] == 3
        for layer in range(3):
            scale = float(jnp.max(jnp.abs(ref_[layer])))
            err = float(jnp.max(jnp.abs(got[layer] - ref_[layer])))
            assert err < TOL * scale, (name, layer, err, scale)


def test_tied_head_has_no_leaf_of_its_own(model):
    cfg, params, _ = model
    assert "lm_head" not in params
    untied, _ = lm.init(dataclasses.replace(cfg, tie_embeddings=False),
                        jax.random.PRNGKey(3))
    assert untied["lm_head"].shape == (cfg.d_model, cfg.vocab_size)


SSM_ARCHS = [a for a in ARCH_IDS
             if any(p.mixer == "ssm" for p in get_config(a).pattern)]


@pytest.mark.parametrize("reduced", [False, True])
@pytest.mark.parametrize("arch", SSM_ARCHS)
def test_param_count_equals_the_leaves(arch, reduced):
    cfg = get_config(arch, reduced=reduced)
    shapes, _ = lm.abstract_init(cfg)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == cfg.param_count()


def test_published_parameter_counts():
    assert SSM_ARCHS == ["jamba-1.5-large-398b", "mamba2-2.7b"]
    assert get_config("mamba2-2.7b").param_count() == 2_702_599_680
    assert get_config("qwen3-4b").param_count() == 4_411_424_256
