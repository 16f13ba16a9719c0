"""launch_idle_ms: mean, over the ``serve.prefill`` and ``serve.decode``
calls of the traced window's whole ticks, of the call's wait less device
0's busy time from the call span's start to the wait's end
(``spans.wait_excess_ns``): the device idle time each wait adds beyond
the host's own time (``tick_host_ms``), spent in launch, transfers and
the runtime.  Host and device durations alone enter it, so how well the
profiler synchronises the two clocks does not.  Its notes give the
coverage (the share of the device's idle time, between the first tick's
start and the last one's end, that lies under a ``serve.tick``), the sum
that has to match the idle time a tick, the median over the calls (the
steady part, which dispatching ahead can hide) with the waits over
``LONG_NS`` (runtime hiccups) counted apart, and the idle time a tick
under each innermost ``serve.*`` span (on the clocks as the profiler
synchronised them).  Layer: runtime."""

import numpy as np

from chipbench import spans, trace

# a wait this much longer than its program is a runtime hiccup, not the
# call's launch: the notes count such waits apart
LONG_NS = 10e6


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.ops:
        return None
    ticks = spans.of(ctx)
    pairs = spans.calls(ticks)
    if not pairs:
        return None
    idle = spans.Idle(trace.idle_gaps(tr))
    excess = spans.wait_excess_ns(pairs, idle)
    value = float(excess.mean()) * 1e-6
    n = len(ticks)
    under = float(idle.within([t.start for t in ticks],
                              [t.end for t in ticks]).sum())
    lo, hi = ticks[0].start, ticks[-1].end
    spanned = float(idle.within([lo], [hi])[0])
    ctx.note(f"launch_idle_ms: coverage "
             f"{100 * under / spanned if spanned else 100.0:.3f}% of the "
             f"{spanned * 1e-6:.3f} ms the device idled in the "
             f"{(hi - lo) * 1e-9:.3f} s from the first tick to the last "
             f"lies under a serve.tick ({tr.window_s:.3f} s traced)")
    per_tick = under * 1e-6 / n
    per = len(pairs) / n
    host = spans.tick_host_ms(ticks)
    est = host + value * per
    ctx.note(f"launch_idle_ms: device idle {per_tick:.3f} ms a tick; "
             f"tick_host_ms {host:.3f} + launch_idle_ms {value:.3f} x "
             f"{per:.3f} calls a tick = {est:.3f} ms "
             f"({100 * (est / per_tick - 1) if per_tick else 0.0:+.2f}%)")
    p10, p50, p90 = np.percentile(excess, [10, 50, 90]) * 1e-6
    k = int(np.argmax(excess))
    call, _ = pairs[k]
    tick = next(t for t in ticks if t.start <= call.start <= t.end)
    long = excess > LONG_NS
    ctx.note(f"launch_idle_ms: over {len(pairs)} calls median {p50:.3f} "
             f"ms [10th {p10:.3f}, 90th {p90:.3f}]; {int(long.sum())} over "
             f"{LONG_NS * 1e-6:.0f} ms hold {excess[long].sum() * 1e-6:.3f} "
             f"of the {excess.sum() * 1e-6:.3f} ms; the longest "
             f"{excess[k] * 1e-6:.3f} ms, a {call.name} in tick "
             f"{tick.args['tick']}, {(call.start - tr.t0_ns) * 1e-9:.3f}"
             f" s into the trace")
    own = spans.self_idle_ms_per_tick(ticks, idle)
    ctx.note("launch_idle_ms: device idle ms a tick under each innermost "
             "span: " + ", ".join(f"{name} {v:.3f}" for name, v in
                                  sorted(own.items(), key=lambda x: -x[1])))
    return value
