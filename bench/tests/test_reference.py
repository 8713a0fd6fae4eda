"""Each float32 reference against the program at reduced sizes."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import run
import tiny


def f32(config):
    c = dict(config, torch_dtype="float32")
    c["program"] = dict(config["program"], compute_dtype="float32")
    return c


@pytest.mark.parametrize("config", [tiny.DENSE], ids=["dense"])
def test_reference_equals_the_programs_forward(config):
    """Same weights from the same seed, same logits, at every position of
    sequences of three lengths (float32 on both sides)."""
    from repro.models import lm
    c = f32(config)
    cfg = run.program_config(c)
    cfg = cfg.__class__(**{**cfg.__dict__, "param_dtype": "float32"})
    seed = 2 ** 31 + 11
    params = lm.init(cfg, jax.random.PRNGKey(seed))[0]
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, 256, n).astype(np.int32) for n in (9, 30, 47)]
    ref = run.family_of(c).logits_at(c, seed, seqs,
                                     [np.arange(len(s)) for s in seqs])
    for s, r in zip(seqs, ref):
        got = lm.forward(cfg, params, jnp.asarray(s)[None])[0][0]
        np.testing.assert_allclose(np.asarray(r), np.asarray(got),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("config", [tiny.DENSE], ids=["dense"])
def test_served_tokens_match_the_reference_in_float32(config):
    """Prefill and decode through the cache, waves mixing prompt lengths
    (so padded rows are read with their padding): in float32 every
    served token is the reference's best, to rounding."""
    cell = tiny.cell(f32(config), limit=1e-3)
    out = run.run(cell, 5, 0.5, False, jax.devices()[0], tiny.PEAKS,
                  t_start=time.perf_counter())
    assert out["checks"]["max_logit_gap"]["value"] <= 1e-3
    assert out["correct"]


@pytest.mark.parametrize("config", [tiny.DENSE], ids=["dense"])
def test_bf16_run_is_correct_and_reports_its_metrics(config):
    cell = tiny.cell(config)
    out = run.run(cell, 2 ** 31 + 3, 0.5, False, jax.devices()[0],
                  tiny.PEAKS, t_start=time.perf_counter())
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) >= {"gen_tok_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_padded_request_is_read_both_ways():
    r = check.Served(0, np.arange(1, 6, dtype=np.int32), 3, 8, [7, 8, 9])
    (seq, pos, tok), (pseq, ppos, _) = check._variants(r)
    assert seq.tolist() == [1, 2, 3, 4, 5, 7, 8]
    assert pos.tolist() == [4, 5, 6] and tok.tolist() == [7, 8, 9]
    assert pseq.tolist() == [0, 0, 0, 1, 2, 3, 4, 5, 7, 8]
    assert ppos.tolist() == [7, 8, 9]
    assert len(check._variants(check.Served(0, r.prompt, 3, 5, [1]))) == 1


def test_sample_holds_the_longest_and_every_batch_row():
    # 4 waves of 3 clients; request i was served in row i % 3
    done = [check.Served(i, np.ones(4, np.int32), n, 4, [1] * n)
            for i, n in enumerate([3, 9, 2, 9, 5, 4, 1, 2, 3, 4, 5, 6])]
    picked = check.sample(done, 3, 3, 2)
    assert picked[0].rid == 1
    assert len(picked) == 7 and len({r.rid for r in picked}) == 7
    rows = [r.rid % 3 for r in picked[1:]]
    assert sorted(rows) == [0, 0, 1, 1, 2, 2]
    assert [r.rid for r in picked] == [r.rid for r in check.sample(done, 3,
                                                                   3, 2)]
    assert [r.rid for r in picked] != [r.rid for r in check.sample(done, 4,
                                                                   3, 2)]
