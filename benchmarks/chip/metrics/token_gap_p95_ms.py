"""token_gap_p95_ms: p95 of the gaps between consecutive tokens of one
request, both ends inside the traced part of the window.  It is
``itl_p95_ms`` read as a layer's number, for the cells where that tail
is too unsteady to hold to a bound (its p95 falls on the step between
ticks with one prefill chunk and ticks with two).  Layer: scheduler."""

import numpy as np


def read(ctx):
    gaps = []
    for e in ctx.emits:
        e = e[(e >= ctx.lo) & (e < ctx.hi)]
        gaps.append(np.diff(e))
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    if not len(gaps):
        return None
    ctx.note(f"token_gap_p95_ms: {len(gaps)} gaps, median "
             f"{1e3 * float(np.median(gaps)):.3f} ms")
    return 1e3 * float(np.percentile(gaps, 95))
