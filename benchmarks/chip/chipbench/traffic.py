"""One generator for every traffic mix: a mix is a JSON file of parameters.

Closed loop: every request is queued at tick 0 and the engine admits the
next one as soon as a slot frees, so ``max_batch`` slots behave as that
many clients that send again the moment they are answered.

Lengths are lognormal and clipped.  ``QUANTILES`` (prompt, output) pairs
sit at evenly spaced quantiles of the mix (the i-th at quantile
(i + 0.5) / ``QUANTILES`` of each distribution), paired and ordered by
one fixed shuffle and repeated.  The first ``n_first`` requests stand
for sessions already under way: a share of each one's output is
appended to its prompt, and only the rest is left to generate; the
shares are stratified (one in each of ``n_first`` equal strata of
[0, 1)) and fixed too.  The seed draws the token ids and nothing else.
A closed loop turns any change of lengths or order into a different
schedule for the whole window, so every seed serves the same lengths in
the same order, and two seeds differ in their tokens alone.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from statistics import NormalDist
from typing import Dict, List

import numpy as np

PAIRING_SEED = 0      # fixes the pairs, their order and the shares
QUANTILES = 64        # quantile points of each length distribution


@dataclasses.dataclass
class Stream:
    prompts: List[np.ndarray]
    outputs: List[int]
    first: int                     # how many lead requests are under way


def load(path: pathlib.Path) -> Dict:
    return json.loads(pathlib.Path(path).read_text())


def quantile_lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of a clipped lognormal."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z)).astype(np.int64)
    return np.clip(x, spec["min"], spec["max"])


def quantile_pairs(mix: Dict, max_len: int) -> np.ndarray:
    """The fixed (prompt, output) pairs in their fixed order, shape
    (QUANTILES, 2)."""
    fixed = np.random.default_rng(PAIRING_SEED)
    p = quantile_lengths(mix["prompt"], QUANTILES)
    o = quantile_lengths(mix["output"], QUANTILES)[fixed.permutation(
        QUANTILES)]
    if np.any(p + o > max_len):
        raise ValueError(f"mix {mix['name']}: prompt + output exceeds "
                         f"max_len {max_len}")
    return np.stack([p, o], 1)[fixed.permutation(QUANTILES)]


def generate(mix: Dict, seed: int, n_first: int, vocab: int,
             max_len: int) -> Stream:
    pairs = quantile_pairs(mix, max_len)
    order = np.concatenate([pairs] * math.ceil(mix["requests"] / QUANTILES))
    n_first = min(n_first, len(order))
    fixed = np.random.default_rng([PAIRING_SEED, n_first])
    shares = (fixed.permutation(n_first) + fixed.random(n_first)) / max(
        n_first, 1)
    rng = np.random.default_rng(seed)
    prompts, outputs = [], []
    for i, (p, o) in enumerate(order):
        p, o = int(p), int(o)
        if i < n_first:
            done = int(shares[i] * o)           # output already generated
            p, o = p + done, o - done
        prompts.append(rng.integers(0, vocab, p, dtype=np.int32))
        outputs.append(o)
    return Stream(prompts, outputs, n_first)
