"""One general generator for every traffic mix.

A mix file (``bench/traffic/<name>.json``) fixes the composition of a wave:

  {"lanes": 8, "max_len": 1280,
   "requests": [[prompt_len, output_len, count], ...]}

Every wave holds exactly that list of (prompt, output) pairs, in file
order, which is the order of arrival. The seed draws only the token ids,
so two seeds do the same work on the same schedule: the lanes a request
shares, the decode blocks and the scrub tables do not move with the seed.
Ids are uniform over ``[1, vocab)`` of the configuration the cell runs (a
sliced vocabulary is a smaller vocabulary).
"""

from __future__ import annotations

import numpy as np

# streams of one seed: weights, the measured waves, the warm-up waves
WEIGHTS, WINDOW, WARMUP = 0, 1, 2


def rng(seed: int, stream: int, index: int = 0) -> np.random.Generator:
    """Generator for one stream of one seed (any whole number >= 0)."""
    return np.random.default_rng([int(seed), int(stream), int(index)])


def pairs(mix: dict) -> list[tuple[int, int]]:
    """The wave's (prompt_len, output_len) list in file order."""
    out = []
    for p, o, n in mix["requests"]:
        if p < 1 or o < 1 or p + o > mix["max_len"]:
            raise ValueError(f"request ({p}, {o}) does not fit max_len {mix['max_len']}")
        out += [(int(p), int(o))] * int(n)
    return out


def wave(mix: dict, vocab: int, gen: np.random.Generator) -> list:
    """One wave: [(prompt int32 (p,), output_len)] with seeded ids."""
    return [
        (gen.integers(1, vocab, size=p).astype(np.int32), o) for p, o in pairs(mix)
    ]

