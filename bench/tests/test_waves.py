import json

import numpy as np
import pytest

from run import BENCH
from waves import Waves, cycle_sizes, padded_lengths

MIXES = ["longprompt"]


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_waves(name):
    a, b = Waves(mix(name), 1000, 7), Waves(mix(name), 1000, 7)
    for k in range(6):
        for x, y in zip(a.wave(k), b.wave(k)):
            assert x.rid == y.rid and x.max_new == y.max_new
            assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_other_tokens_same_sizes(name):
    m = mix(name)
    a, b = Waves(m, 1000, 7), Waves(m, 1000, 2 ** 31 + 5)
    for k in range(2 * m["waves_per_cycle"]):
        wa, wb = a.wave(k), b.wave(k)
        assert sorted((len(r.prompt), r.max_new) for r in wa) == \
            sorted((len(r.prompt), r.max_new) for r in wb)
        assert not all(np.array_equal(x.prompt, y.prompt)
                       for x, y in zip(wa, wb))


@pytest.mark.parametrize("name", MIXES)
def test_cycles_repeat_sizes_not_prompts(name):
    m = mix(name)
    w = Waves(m, 1000, 3)
    n = m["waves_per_cycle"]
    first, again = w.wave(0), w.wave(n)
    assert sorted((len(r.prompt), r.max_new) for r in first) == \
        sorted((len(r.prompt), r.max_new) for r in again)
    assert {r.rid for r in first}.isdisjoint(r.rid for r in again)
    assert not any(np.array_equal(x.prompt, y.prompt)
                   for x in first for y in again if len(x.prompt) ==
                   len(y.prompt))


@pytest.mark.parametrize("name", MIXES)
def test_sizes_within_the_mix(name):
    m = mix(name)
    sizes = cycle_sizes(m)
    assert len(sizes) == m["waves_per_cycle"]
    assert all(len(w) == m["clients"] for w in sizes)
    for p, g in (s for w in sizes for s in w):
        assert m["prompt"]["min"] <= p <= m["prompt"]["max"]
        assert m["output"]["min"] <= g <= m["output"]["max"]
        assert p + g <= m["max_len"]
    assert padded_lengths(m) == sorted({max(p for p, _ in w) for w in sizes})
    tokens = [r.prompt for r in Waves(m, 1000, 1).wave(0)]
    assert all(t.dtype == np.int32 and t.min() >= 1 and t.max() < 1000
               for t in tokens)
