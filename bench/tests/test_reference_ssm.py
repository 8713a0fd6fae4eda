"""The Mamba-2 float32 reference (``reference/ssm.py``) against the
program at a reduced size, its FLOP count, and its float8 control."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import run
import tiny
from reference import ssm

# two Mamba-2 layers of the published layout at d_model 64: 8 heads of
# 16, state 16, one group; 250 tokens padded to 256 vocabulary rows
SSM = {
    "arch": "mamba2-2.7b", "family": "ssm",
    "d_model": 64, "n_layer": 2, "vocab_size": 250,
    "pad_vocab_size_multiple": 16, "tie_embeddings": True,
    "residual_in_fp32": True,
    "assumed": {"d_state": 16, "d_conv": 4, "expand": 2, "headdim": 16,
                "ngroups": 1, "norm_epsilon": 1e-05,
                "padded_vocab_size": 256, "torch_dtype": "bfloat16"},
    "program": {"n_layers": 2, "d_model": 64, "vocab_size": 256,
                "ssm_state": 16, "ssm_head_dim": 16, "remat": False},
}

# the tiny SSM cell's limit: between what its bf16 program reads (under
# 0.006 over seeds 1, 2 and 2**31 + 7) and what its float8 control
# reads over a one-second window (0.068 and up on the same seeds)
LIMIT = 0.03


def f32(config):
    c = dict(config, assumed=dict(config["assumed"], torch_dtype="float32"))
    c["program"] = dict(config["program"], compute_dtype="float32")
    return c


def test_reference_equals_the_programs_forward():
    """Same weights from the same seed, same logits at every position of
    sequences of three lengths (float32 on both sides: the recurrence
    and the program's chunked scan agree to float32 rounding)."""
    from repro.models import lm
    c = f32(SSM)
    cfg = run.program_config(c)
    cfg = cfg.__class__(**{**cfg.__dict__, "param_dtype": "float32"})
    seed = 2 ** 31 + 11
    params = lm.init(cfg, jax.random.PRNGKey(seed))[0]
    assert sum(x.size for x in jax.tree.leaves(params)) == \
        ssm.param_count(c)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, 250, n).astype(np.int32) for n in (9, 30, 47)]
    ref = ssm.logits_at(c, seed, seqs, [np.arange(len(s)) for s in seqs])
    for s, r in zip(seqs, ref):
        got = lm.forward(cfg, params, jnp.asarray(s)[None])[0][0]
        np.testing.assert_allclose(np.asarray(r), np.asarray(got),
                                   rtol=2e-4, atol=2e-4)


def test_served_tokens_match_the_reference_in_float32():
    """Prefill and decode through the conv window and the SSD state,
    waves mixing prompt lengths: in float32 every served token is the
    reference's best, to rounding."""
    cell = tiny.cell(f32(SSM), limit=1e-3)
    out = run.run(cell, 5, 0.5, False, jax.devices()[0], tiny.PEAKS,
                  t_start=time.perf_counter())
    assert out["checks"]["max_logit_gap"]["value"] <= 1e-3
    assert out["correct"]


def test_bf16_run_is_correct_and_reports_its_metrics():
    out = run.run(tiny.cell(SSM, limit=LIMIT), 2 ** 31 + 3, 0.5, False,
                  jax.devices()[0], tiny.PEAKS, t_start=time.perf_counter())
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) >= {"gen_tok_s", "setup_s"}


def test_blocks_equal_the_token_recurrence():
    """The reference's block decomposition of the SSD is the recurrence
    s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_tᵀ, y_t = s_t C_t, token by
    token, to float32 rounding (two groups of four heads, four blocks)."""
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    R, T, H, P, G, N = 2, 4 * ssm.CHUNK, 8, 16, 2, 16
    x = jax.random.normal(k[0], (R, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (R, T, H)) - 2)
    A = -jnp.exp(jnp.linspace(0.0, 2.7, H))
    b = jax.random.normal(k[2], (R, T, G, N))
    c = jax.random.normal(k[3], (R, T, G, N))
    s = jnp.zeros((R, H, P, N))
    want = []
    for t in range(T):
        bt, ct = (jnp.repeat(v[:, t], H // G, axis=1) for v in (b, c))
        s = (jnp.exp(dt[:, t] * A)[..., None, None] * s
             + (x[:, t] * dt[:, t, :, None])[..., None] * bt[:, :, None])
        want.append(jnp.einsum("rhpn,rhn->rhp", s, ct, precision=ssm.HI))
    want = jnp.stack(want, 1)
    got = ssm.ssd(x, dt, A, b, c)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))


def test_ssm_flops_and_parameters_by_hand():
    # d 64, inner 128, 8 heads of 16, state 16, conv 4, vocab 256
    # in_proj 64 x (2*128 + 2*16 + 8) = 18,944 and out_proj 128 x 64 =
    # 8,192 weights: 54,272 FLOPs; conv 2 * 4 * 160 = 1,280; recurrence
    # 5 * 8 * 16 * 16 = 10,240; prompt 5, 3 new -> 7 tokens through the
    # 2 layers; head 2 * 64 * 256 per generated token
    want = 2 * 7 * (54_272 + 1_280 + 10_240) + 3 * 2 * 64 * 256
    assert ssm.request_flops(SSM, 5, 3) == want
    # layer: norm 64, in_proj 18,944, conv 4 * 160 + 160, A_log/D/dt_bias
    # 24, gated norm 128, out_proj 8,192; tied head: one 256 x 64 matrix
    assert ssm.param_count(SSM) == 256 * 64 + 64 + 2 * 28_152


@pytest.mark.parametrize("skip", ["x", "x_dt"])
def test_the_skip_term_is_read(monkeypatch, skip):
    """The reference tells D·x from D·(x·dt): served in float32, the
    program reads within rounding of a reference with D·x and far from
    one with the old D·(x·dt)."""
    if skip == "x_dt":            # D is 1 at init: add (dt - 1)·x to D·x
        plain = ssm.ssd
        monkeypatch.setattr(ssm, "ssd", lambda x, dt, A, b, c: plain(
            x, dt, A, b, c) + (dt[..., None] - 1) * x)
        ssm._programs.cache_clear()
    cell = tiny.cell(f32(SSM), limit=1e-3)
    out = run.run(cell, 7, 0.3, False, jax.devices()[0], tiny.PEAKS,
                  t_start=time.perf_counter())
    ssm._programs.cache_clear()
    assert out["correct"] == (skip == "x")


def test_control_reads_far_above_the_program():
    """The reference with float8 matrix products in place of the bf16
    program reads above the tiny SSM cell's limit on every seed."""
    cell = tiny.cell(SSM, limit=LIMIT)
    prog, ctrl = [], []
    for seed in (1, 2, 2 ** 31 + 7):
        server = run.build_server(cell, seed, jax.devices()[0])
        done, _, _ = run.serve_window(server, cell, seed, 1.0)
        picked = check.sample(done, seed, cell.traffic["clients"], 10 ** 6)
        prog.append(max(check.widest_gaps(ssm, cell.config, seed, picked)))
        ctrl.append(max(check.widest_gaps(ssm, cell.config, seed, picked,
                                          control="fp8")))
    print(f"program {prog} control {ctrl}")
    assert min(ctrl) >= 3 * max(prog)
    assert max(prog) < LIMIT < min(ctrl)
