"""moe_gmm_roofline (%): the least time the chip could take for the
routed experts' grouped matmuls, max(FLOPs / peak, bytes / HBM
bandwidth) summed over the calls, over the ``moe_gmm`` kernels' summed
device time.  The calls are the ``serve.prefill`` and ``serve.decode``
spans of the traced window's whole ticks; each carries ``routed_rows``,
the assignments its held experts computed summed over its expert layers,
and the family's ``moe_gmm_work`` turns that into FLOPs (6 D Fe a routed
row) and bytes (the held experts' weights once a layer, the routed rows
in and out).  Rows the expert buffers pad with are not work, so the
share shows what computing them costs.  The kernels counted are those
that ran between the first tick's start and the last one's end.
A program whose spans carry no ``routed_rows`` reports nothing.
Layer: kernels."""

import numpy as np

from chipbench import spans

KERNEL = r"^%moe_gmm(\.\d+)? = "
COUNTER = "routed_rows"


def read(ctx):
    tr, fam = ctx.trace, ctx.cell.family
    if tr is None or not tr.ops or not hasattr(fam, "moe_gmm_work"):
        return None
    ticks = spans.of(ctx)
    calls = [c for c, _ in spans.calls(ticks)]
    if not calls or any(COUNTER not in c.args for c in calls):
        return None
    ev = tr.ops[0].leaves().within(ticks[0].start, ticks[-1].end)
    k = ev.matching(KERNEL).durations * 1e-9
    if not len(k):
        return None
    rows = np.array([c.args[COUNTER] for c in calls], np.int64)
    flops, byts = fam.moe_gmm_work(ctx.d, rows)
    pk = ctx.peaks
    t_flops = flops / pk["bf16_flops"]
    t_bytes = byts / pk["hbm_bytes_per_s"]
    bound = float(np.sum(np.maximum(t_flops, t_bytes)))
    kind = np.array([c.name == "serve.decode" for c in calls])
    ctx.note(f"moe_gmm: {len(k)} kernel events for {len(calls)} calls "
             f"({int(kind.sum())} decode, {int((~kind).sum())} prefill); "
             f"routed rows a call: decode mean "
             f"{rows[kind].mean() if kind.any() else 0:.1f}, prefill mean "
             f"{rows[~kind].mean() if (~kind).any() else 0:.1f}; kernel "
             f"time {float(np.sum(k)) * 1e3:.3f} ms against "
             f"{bound * 1e3:.3f} ms, bound by memory in "
             f"{int(np.sum(t_bytes >= t_flops))} of {len(calls)} calls")
    return 100.0 * bound / float(np.sum(k))
