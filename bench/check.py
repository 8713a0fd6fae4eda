"""Decides ``correct``: the served tokens against the float32 reference.

Once the window has closed, a sample of the requests it finished is
drawn from the run seed: ``per_slot`` requests served in each row of
the batch, so that a fault in any one row is read, and the request with
the most generated tokens. The
reference runs once over each prompt followed by its served tokens, and
at every generated position reads how far the served token's logit lies
below the reference's best logit there. The widest such gap is compared
with the cell's limit. Served tokens are greedy, so a sound bf16 server
only picks a token the reference ranks second at a near-tie.

The server left-pads each wave to its longest prompt and, with no
padding mask, attends to (or scans over) the padding. A padded request
is therefore read both ways, with the wave's padding before its prompt
and without it, and keeps the closer of the two: the comparison stays
right when the program gains a mask.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np


@dataclass
class Served:
    """One request the window finished."""
    rid: int
    prompt: np.ndarray
    max_new: int
    width: int              # the padded prompt width of its wave
    tokens: list[int]
    latency: float = 0.0    # from its wave's start to the wave's return


def sample(done: list[Served], seed: int, clients: int,
           per_slot: int) -> list[Served]:
    """The longest reply and, for each batch row, ``per_slot`` other
    requests served in that row (a request's row is its place in its
    wave, ``rid % clients``), drawn from ``seed``."""
    longest = max(range(len(done)), key=lambda i: (len(done[i].tokens), -i))
    rng = np.random.default_rng([seed, 0x5EED])
    picked = [longest]
    for row in range(clients):
        ids = [i for i, r in enumerate(done)
               if r.rid % clients == row and i != longest]
        picked += [ids[j] for j in rng.permutation(len(ids))[:per_slot]]
    return [done[i] for i in picked]


def _variants(r: Served):
    """(token sequence, positions read, tokens served) as the request is
    read without its wave's padding and, where it has any, with it."""
    toks = np.asarray(r.tokens, np.int32)
    seq = np.concatenate([r.prompt, toks[:-1]]).astype(np.int32)
    pos = np.arange(len(toks)) + len(r.prompt) - 1
    out = [(seq, pos, toks)]
    pad = r.width - len(r.prompt)
    if pad:
        out.append((np.concatenate([np.zeros(pad, np.int32), seq]),
                    pos + pad, toks))
    return out


def widest_gaps(family, config: dict, seed: int, reqs: list[Served],
                control: str | None = None) -> list[float]:
    """Per request, the widest gap over its generated positions, in the
    closer of its readings. With ``control``, the gaps are those of the
    tokens that the reference computed in that precision ranks first,
    in place of the served ones."""
    rows = [(i, v) for i, r in enumerate(reqs) for v in _variants(r)]
    seqs = [v[0] for _, v in rows]
    want = [v[1] for _, v in rows]
    ref = family.logits_at(config, seed, seqs, want)
    picks = ([np.asarray(jnp.argmax(x, -1)) for x in
              family.logits_at(config, seed, seqs, want, quant=control)]
             if control else [v[2] for _, v in rows])
    gaps = [np.inf] * len(reqs)
    for (i, _), logits, tok in zip(rows, ref, picks):
        at = jnp.take_along_axis(logits, jnp.asarray(tok)[:, None], 1)[:, 0]
        gap = float(jnp.max(jnp.max(logits, -1) - at))
        gaps[i] = min(gaps[i], gap if np.isfinite(gap) else np.inf)
    return gaps
