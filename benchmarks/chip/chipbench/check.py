"""Decides ``correct``: served greedy tokens against the plain reference.

A sample of finished requests, drawn from the seed with the longest one
always in it, is run through the family's float32 reference over each
prompt followed by its served tokens.  At every served position the
number compared is how far the served token's reference logit lies below
the reference's best logit there; the widest such gap over the sample
has to stay under the cell's limit.  Greedy decoding with exact
arithmetic gives 0; the program's bf16 arithmetic flips near ties only.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np


@dataclasses.dataclass
class Sample:
    ids: List[int]                    # request indices
    seqs: List[np.ndarray]            # prompt + served tokens but the last
    rows: List[np.ndarray]            # positions that predicted each token
    served: List[np.ndarray]          # the served tokens


def draw(results: Sequence, prompts: Sequence[np.ndarray], seed: int,
         min_tokens: int, max_requests: int) -> Sample:
    """Finished requests: the one with most served tokens, then others in
    a seeded order, until ``min_tokens`` served tokens are in."""
    done = [i for i, r in enumerate(results)
            if r.status == "OK" and len(r.tokens)]
    if not done:
        raise RuntimeError("no request finished OK: nothing to compare")
    longest = max(done, key=lambda i: (len(results[i].tokens), -i))
    rest = [i for i in done if i != longest]
    rest = [rest[k] for k in np.random.default_rng(seed).permutation(
        len(rest))]
    ids, n = [], 0
    for i in [longest] + rest:
        if n >= min_tokens or len(ids) >= max_requests:
            break
        ids.append(i)
        n += len(results[i].tokens)
    s = Sample([], [], [], [])
    for i in ids:
        p, t = prompts[i], np.asarray(results[i].tokens, np.int32)
        s.ids.append(i)
        s.seqs.append(np.concatenate([p, t[:-1]]).astype(np.int32))
        s.rows.append(np.arange(len(p) - 1, len(p) + len(t) - 1))
        s.served.append(t)
    return s


def verdict(gap: float, limit: float, failed: int) -> bool:
    """``correct``: the widest gap within the limit and no request failed.
    The program's reading and the control's go through this alike."""
    return bool(gap <= limit and failed == 0)


def served_gap(ref_logits: Sequence[np.ndarray],
               served: Sequence[np.ndarray]) -> float:
    """Widest gap of a served token's reference logit below the best."""
    worst = 0.0
    for lg, t in zip(ref_logits, served):
        got = np.take_along_axis(lg, t[:, None].astype(np.int64), 1)[:, 0]
        worst = max(worst, float(np.max(lg.max(-1) - got)))
    return worst


def picked_gap(ref_logits: Sequence[np.ndarray],
               other_logits: Sequence[np.ndarray]) -> float:
    """The same gap for the tokens another computation puts first at the
    same positions (the control: it need not decode)."""
    return served_gap(ref_logits, [o.argmax(-1) for o in other_logits])
