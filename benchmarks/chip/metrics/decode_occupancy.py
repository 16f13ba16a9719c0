"""decode_occupancy (%): mean decoding slots per decode step in the
window, over max_batch.  Read from the lengths each decode step was
given (a slot decodes when its length is above 0).  Layer: scheduler."""


def read(ctx):
    t, lens = ctx.decodes
    m = (t >= ctx.lo) & (t < ctx.hi)
    if not m.any():
        return None
    active = (lens[m] > 0).sum(1)
    ctx.note(f"decode_occupancy: {int(m.sum())} decode steps, mean "
             f"{active.mean():.3f} of {ctx.max_batch} slots")
    return 100.0 * float(active.mean()) / ctx.max_batch
