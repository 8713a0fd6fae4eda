"""Closed-loop traffic from a mix file (``bench/traffic/<mix>.json``).

A mix is ``clients`` clients that each wait for their reply, so every
wave of ``clients`` requests is one ``BatchServer.serve`` call. The
sizes of one cycle of ``waves_per_cycle`` waves (prompt length and
tokens to generate, per request) are drawn once from the mix's own
``size_seed``: every run seed serves the same set of sizes, so the seed
never changes the amount of work. The run seed draws the token ids, and
the order of the requests inside each wave, anew for every wave of
every cycle, so no prompt repeats within a run.

Distributions, each ``{"dist": ..., "min": lo, "max": hi}`` clipped to
``[lo, hi]`` after rounding:
  ``lognormal``: ``median`` and ``sigma`` of the underlying normal;
  ``uniform``:   integers on ``[min, max]``.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Req:
    """One request as its client sends it."""
    rid: int            # position in the run: cycle * per_cycle + index
    prompt: np.ndarray  # (len,) int32
    max_new: int


def _draw(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    kind = spec["dist"]
    if kind == "lognormal":
        v = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif kind == "uniform":
        v = rng.integers(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(int)


def cycle_sizes(mix: dict) -> list[list[tuple[int, int]]]:
    """(prompt length, max_new) of every request, wave by wave."""
    rng = np.random.default_rng(mix["size_seed"])
    n = mix["clients"] * mix["waves_per_cycle"]
    plen = _draw(rng, mix["prompt"], n)
    gen = _draw(rng, mix["output"], n)
    c = mix["clients"]
    return [list(zip(plen[i:i + c].tolist(), gen[i:i + c].tolist()))
            for i in range(0, n, c)]


def padded_lengths(mix: dict) -> list[int]:
    """The distinct prompt widths the server pads the cycle's waves to."""
    return sorted({max(p for p, _ in w) for w in cycle_sizes(mix)})


class Waves:
    """The endless sequence of waves of one run."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.sizes = cycle_sizes(mix)
        self.vocab = vocab
        self.seed = seed
        self.clients = mix["clients"]

    def wave(self, k: int) -> list[Req]:
        """The ``k``-th wave of the run (cycles repeat their sizes)."""
        cycle, w = divmod(k, len(self.sizes))
        rng = np.random.default_rng([self.seed, cycle, w])
        sizes = self.sizes[w]
        order = rng.permutation(len(sizes))
        base = k * self.clients
        return [Req(base + j, rng.integers(1, self.vocab, sizes[i][0],
                                           dtype=np.int32), sizes[i][1])
                for j, i in enumerate(order)]
