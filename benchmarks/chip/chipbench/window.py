"""End-to-end numbers of one window, from the engine's own host
timestamps (every tick ends in ``block_until_ready``, so each emit time
follows the device's work).  The arithmetic is that of
``benchmarks/serve_bench.py`` (TTFT from admission, gaps from
``emit_times``, linear-interpolated percentiles), restricted to events
inside the window (open, close].

TTFT covers every request admitted inside the window.  The run goes on
past the close until each of them has emitted its first token, so slow
first tokens are not lost at the close; one still waiting when the run
ends (``end``, a cap the harness sets) counts ``end - admit`` as a lower
bound and is counted in ``censored``.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class WindowStats:
    seconds: float
    tokens: int
    gaps_s: np.ndarray            # consecutive emits of one request
    ttft_s: np.ndarray            # every request admitted in the window
    censored: int                 # of those, first token not seen by end

    @property
    def tokens_per_s(self) -> float:
        return self.tokens / self.seconds


def pct(x: np.ndarray, q: float) -> float:
    return float(np.percentile(x, q)) if len(x) else float("nan")


def measure(results: List, open_t: float, close_t: float,
            end_t: float) -> WindowStats:
    tokens = censored = 0
    gaps: List[float] = []
    ttft: List[float] = []
    for r in results:
        e = np.asarray(r.emit_times, np.float64)
        inside = (e > open_t) & (e <= close_t)
        tokens += int(inside.sum())
        if len(e) > 1:
            both = inside[1:] & inside[:-1]
            gaps.extend((e[1:] - e[:-1])[both].tolist())
        if open_t < r.admit_time <= close_t:
            if len(e):
                ttft.append(e[0] - r.admit_time)
            else:
                ttft.append(end_t - r.admit_time)
                censored += 1
    return WindowStats(close_t - open_t, tokens, np.array(gaps),
                       np.array(ttft), censored)
