"""paged_decode_attention_roofline (%): the least time the chip could
take for the kernel's work, max(FLOPs / peak, bytes / HBM bandwidth),
over the kernel's summed device time.  Work per call comes from the
lengths each decode step was given: every slot attends kv_len = length
+ 1 rows, whose K and V are read once, plus q and the output.  One call
per layer per decode step.  Layer: kernels."""

import numpy as np

from chipbench import trace

KERNEL = r"^%paged_decode_attention(\.\d+)? = "


def read(ctx):
    if ctx.trace is None:
        return None
    k = trace.op_times(ctx.trace, KERNEL)
    dt, lens = ctx.decodes
    m = (dt >= ctx.lo) & (dt < ctx.hi)
    if not len(k) or not m.any():
        return None
    fam, d, pk = ctx.cell.family, ctx.d, ctx.peaks
    flops = byts = 0
    for step in lens[m]:
        f, b = fam.decode_attention_work(d, step + 1)
        flops += f
        byts += b
    calls = int(m.sum()) * d.L
    t_flops = flops / pk["bf16_flops"] / calls
    t_bytes = byts / pk["hbm_bytes_per_s"] / calls
    per_call = float(np.sum(k)) / len(k)
    ctx.note(f"paged_decode_attention: {len(k)} kernel events for {calls} "
             f"calls; per call {per_call * 1e6:.3f} us against "
             f"{max(t_flops, t_bytes) * 1e6:.3f} us, bound by "
             f"{'memory' if t_bytes >= t_flops else 'compute'}")
    return 100.0 * max(t_flops, t_bytes) / per_call
