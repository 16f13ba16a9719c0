"""serve_mfu (%): model FLOPs of the prompt and output tokens processed
in the window over (window seconds x the chip's bf16 peak).  Counts every
layer's matmuls, attention at each token's own context, and the head
only where logits are produced (each decoding slot, each chunk's last
row).  Padding, idle slots and recomputation do not count.  Layer: the
whole model step."""

import numpy as np


def read(ctx):
    d, fam = ctx.d, ctx.cell.family
    pt, ps, pn = ctx.prefills
    dt, lens = ctx.decodes
    pm = (pt >= ctx.lo) & (pt < ctx.hi)
    dm = (dt >= ctx.lo) & (dt < ctx.hi)
    if not (pm.any() or dm.any()):
        return None
    kv = lens[dm]
    kv = kv[kv > 0] + 1
    flops = (float(np.sum(fam.chunk_flops(d, ps[pm], pn[pm])))
             + float(np.sum(fam.token_flops(d, kv, True))))
    ctx.note(f"serve_mfu: {int(pn[pm].sum())} prompt tokens in "
             f"{int(pm.sum())} chunks, {kv.size} decoded tokens, "
             f"{flops:.6e} FLOPs in {ctx.hi - ctx.lo:.3f} s")
    return 100.0 * flops / ((ctx.hi - ctx.lo) * ctx.peaks["bf16_flops"])
