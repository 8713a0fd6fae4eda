"""A run with its timed path broken underneath comes out not correct.

Each test skips the look for a chip and drives ``run.run`` on a tiny
bf16 model on the CPU, with one fault planted in the serving program:
a decode step that returns its cache unchanged, half of the batch left
out (its rows served from the other half's prompts), and a token
altered where it is sampled. A one-chip cell has no exchange between
chips to leave out. The same run unbroken is correct at the same limit.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
import tiny
from repro.launch import serve

LIMIT = tiny.LIMIT


def _run(config):
    out = run.run(tiny.cell(config, limit=LIMIT), 9, 0.3, False,
                  jax.devices()[0], tiny.PEAKS, t_start=time.perf_counter())
    return out


def _steps_with(prefill_fault=None, decode_fault=None):
    plain = serve.serving_steps

    def steps(cfg, rules, max_len):
        prefill, decode = plain(cfg, rules, max_len)
        return (prefill_fault(prefill) if prefill_fault else prefill,
                decode_fault(decode) if decode_fault else decode)
    return steps


def state_unchanged(decode):
    def step(params, cache, tok, pos):
        logits, _ = decode(params, jax.tree.map(jnp.copy, cache), tok, pos)
        return logits, cache
    return step


def half_batch(prefill):
    def step(params, tokens):
        half = tokens[: tokens.shape[0] // 2]
        logits, cache = prefill(params, jnp.concatenate(
            [half, half, tokens[:tokens.shape[0] % 2]]))
        return logits, cache
    return step


@pytest.mark.parametrize("config", [tiny.DENSE], ids=["dense"])
def test_unbroken_run_is_correct(config):
    assert _run(config)["correct"]


@pytest.mark.parametrize("config", [tiny.DENSE], ids=["dense"])
@pytest.mark.parametrize("fault", [
    {"decode_fault": state_unchanged},
    {"prefill_fault": half_batch},
], ids=["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(monkeypatch, config, fault):
    monkeypatch.setattr(serve, "serving_steps", _steps_with(**fault))
    out = _run(config)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > LIMIT


@pytest.mark.parametrize("config", [tiny.DENSE], ids=["dense"])
def test_altered_token_is_not_correct(monkeypatch, config):
    plain = serve.BatchServer._sample
    calls = []

    def sample(self, logits, temps, key):
        tok = plain(self, logits, temps, key)
        calls.append(1)
        if len(calls) % 3 == 0:            # every third step, every row
            tok = (np.asarray(tok) + 1) % logits.shape[-1]
        return tok
    monkeypatch.setattr(serve.BatchServer, "_sample", sample)
    out = _run(config)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > LIMIT
