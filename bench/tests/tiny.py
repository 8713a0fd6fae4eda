"""Tiny same-family cells for rehearsing the harness on the CPU."""

from __future__ import annotations

import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent

DENSE = {
    "arch": "qwen3-4b", "family": "dense",
    "num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rope_theta": 1000000, "rms_norm_eps": 1e-06,
    "attention_bias": False, "qk_norm": True, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "program": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                "head_dim": 16, "d_ff": 128, "vocab_size": 256,
                "remat": False},
}

MIX = {
    "clients": 3, "waves_per_cycle": 2, "max_len": 64,
    "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5,
               "min": 8, "max": 40},
    "output": {"dist": "uniform", "min": 4, "max": 12},
    "greedy": True, "size_seed": 3,
}

# a limit between what the tiny bf16 program reads (under 0.04 over
# seeds 1, 2 and 2**31 + 7) and what its float8 control reads over a
# one-second window (0.49 and up on the same seeds)
LIMIT = 0.1

PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]


def cell(config: dict, limit: float = LIMIT, mix: dict = MIX):
    """A one-chip cell of ``config`` under ``mix``, reporting every
    metric of BENCHMARK.json that applies to any cell."""
    from run import Cell
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return Cell(name="tiny", chips=1, config=copy.deepcopy(config),
                traffic=copy.deepcopy(mix),
                limits={"check_per_slot": 2, "max_logit_gap": limit},
                end_to_end=[m for m in spec["end_to_end"]],
                per_layer=list(spec["per_layer"]))
