"""tick_host_ms: mean, over the whole ticks of the traced window, of the
engine's ``serve.tick`` span less the ``serve.*.wait`` spans inside it:
the host time of a tick during which the device had nothing more from
that tick.  Read from the program's own spans (``chipbench/spans.py``).
Its notes give each phase's own host time a tick, the span counters
summed over the ticks, the longest tick with its counts and chunks, the
host time inside each jitted call (where the probe's ``bench.*_dispatch``
spans mark it), and the model FLOPs and decode counts rebuilt from the
counters beside the probe's.  Layer: scheduler."""

import numpy as np

from chipbench import spans

# counters summed over the ticks: (span, argument)
SUMS = (("serve.admit", "admitted"), ("serve.admit", "prefix_blocks"),
        ("serve.control", "cancelled"), ("serve.control", "timed_out"),
        ("serve.control", "shed"), ("serve.grow", "grown"),
        ("serve.grow", "preempted"))


def counter_sums(ticks):
    """{argument: its sum over ``ticks``} for each counter of ``SUMS``."""
    return {arg: sum(s.args[arg] for s in spans.named(ticks, name))
            for name, arg in SUMS}


def _in_ticks(ctx, t, ticks):
    """Which probe times ``t`` (host seconds) fall inside ``ticks``: the
    traced window maps the host clock onto the trace's nanoseconds."""
    return spans.in_ticks(t * 1e9 + (ctx.trace.t0_ns - ctx.lo * 1e9), ticks)


def _decode_counts(lens, B):
    """Occupancy (%) and KV rows a step of the steps given ``lens``."""
    if not len(lens):
        return float("nan"), float("nan")
    return (100.0 * float((lens > 0).sum(1).mean()) / B,
            float(np.where(lens > 0, lens + 1, 0).sum(1).mean()))


def against_probe(ctx, ticks):
    """The decode counters the spans carry beside the probe's, as
    ((occupancy %, kv_rows a step) from the spans, from the probe's steps
    in its window, from the probe's steps that fall in the same ticks),
    or None without decode steps."""
    dec = spans.named(ticks, "serve.decode")
    t, lens = ctx.decodes
    if not dec or not len(t):
        return None
    B = ctx.max_batch
    ours = (100.0 * float(np.mean([d.args["active"] for d in dec])) / B,
            float(np.mean([d.args["kv_rows"] for d in dec])))
    win = lens[(t >= ctx.lo) & (t < ctx.hi)]
    same = lens[_in_ticks(ctx, t, ticks)]
    w, m = _decode_counts(win, B), _decode_counts(same, B)
    ctx.note(f"tick_host_ms: decode occupancy, kv_rows a step: spans "
             f"{ours[0]:.4f}%, {ours[1]:.2f} ({len(dec)} steps); probe in "
             f"its window {w[0]:.4f}%, {w[1]:.2f} ({len(win)} steps; "
             f"{100 * (ours[0] / w[0] - 1):+.3f}%, "
             f"{100 * (ours[1] / w[1] - 1):+.3f}%); probe in the same "
             f"ticks {m[0]:.4f}%, {m[1]:.2f} ({len(same)} steps)")
    return ours, w, m


def model_flops(ctx, ticks):
    """Model FLOPs of the ticks from the counters (each chunk's ``start``
    and ``n_valid``; each step's ``active`` and ``kv_rows``, the decode
    work being affine in the context) and from the probe's calls in the
    same ticks, as (spans, probe)."""
    d, fam = ctx.d, ctx.cell.family
    pre = spans.named(ticks, "serve.prefill")
    dec = spans.named(ticks, "serve.decode")
    f0, f1 = (float(fam.token_flops(d, c, True)) for c in (0, 1))
    ours = (float(np.sum(fam.chunk_flops(
                d, [p.args["start"] for p in pre],
                [p.args["n_valid"] for p in pre]))) if pre else 0.0)
    ours += sum(f0 * s.args["active"] + (f1 - f0) * s.args["kv_rows"]
                for s in dec)
    pt, ps, pn = ctx.prefills
    dt, lens = ctx.decodes
    pm, dm = _in_ticks(ctx, pt, ticks), _in_ticks(ctx, dt, ticks)
    kv = lens[dm]
    kv = kv[kv > 0] + 1
    theirs = (float(np.sum(fam.chunk_flops(d, ps[pm], pn[pm])))
              if pm.any() else 0.0)
    theirs += float(np.sum(fam.token_flops(d, kv, True)))
    ctx.note(f"tick_host_ms: model FLOPs in these ticks from the counters "
             f"{ours:.6e} ({len(pre)} chunks, {len(dec)} steps), from the "
             f"probe's calls {theirs:.6e} ({int(pm.sum())} chunks, "
             f"{int(dm.sum())} steps)")
    return ours, theirs


def in_jitted_call(ctx, ticks):
    """Host ms a call of each kind: inside the jitted call itself (the
    probe's ``bench.<kind>_dispatch`` span around it) and the rest of
    the call's span less its wait, as {kind: (inside, rest)}; a kind
    without the probe's spans is left out."""
    out = {}
    for kind in ("prefill", "decode"):
        calls = spans.named(ticks, f"serve.{kind}")
        ev = ctx.trace.host.matching(rf"^bench\.{kind}_dispatch$")
        at = spans.in_ticks(ev.start, ticks)
        if not calls or not at.any():
            continue
        inside = ev.durations[at].sum()
        own = sum(c.self_ns for c in calls)
        out[kind] = (inside * 1e-6 / len(calls),
                     (own - inside) * 1e-6 / len(calls))
    return out


def _longest(ticks):
    worst = max(ticks, key=lambda t: t.dur)
    w_own = spans.self_ms_per_tick([worst])
    top = max(w_own, key=w_own.get)
    c = counter_sums([worst])
    chunks = ", ".join(f"req {p.args['req']} (slot {p.args['slot']}, "
                       f"{p.args['start']}+{p.args['n_valid']})"
                       for p in spans.named([worst], "serve.prefill"))
    return (f"tick_host_ms: longest tick {worst.args['tick']}: "
            f"{worst.dur * 1e-6:.3f} ms, waits "
            f"{(worst.dur - worst.host_ns) * 1e-6:.3f} ms, host "
            f"{worst.host_ns * 1e-6:.3f} ms, most of it {top} "
            f"({w_own[top]:.3f} ms); {worst.args['queued']} queued, "
            f"{worst.args['busy']} slots busy, {c['admitted']} admitted, "
            f"{c['grown']} grown, {c['preempted']} preempted; chunks: "
            f"{chunks or 'none'}")


def read(ctx):
    ticks = spans.of(ctx)
    if not ticks:
        return None
    n = len(ticks)
    host = spans.tick_host_ms(ticks)
    own = spans.self_ms_per_tick(ticks)
    pre = spans.named(ticks, "serve.prefill")
    steps = len(spans.named(ticks, "serve.decode")) / n
    tick_ms = float(np.mean([t.dur for t in ticks])) * 1e-6
    ctx.note(f"tick_host_ms: {n} ticks of {tick_ms:.3f} ms, host "
             f"{host:.3f} ms a tick; own host ms a tick: "
             + ", ".join(f"{k} {v:.3f}" for k, v in own.items())
             + f"; {len(pre) / n:.3f} chunks and {steps:.3f} decode steps "
             "a tick")
    queued = [t.args["queued"] for t in ticks]
    busy = [t.args["busy"] for t in ticks]
    ctx.note(f"tick_host_ms: over the ticks queued {np.mean(queued):.1f} "
             f"(max {max(queued)}), slots busy {np.mean(busy):.3f} of "
             f"{ctx.max_batch}; "
             + ", ".join(f"{k} {v}" for k, v in counter_sums(ticks).items())
             + f"; chunks of {len({p.args['req'] for p in pre})} requests")
    ctx.note(_longest(ticks))
    calls = in_jitted_call(ctx, ticks)
    ctx.note("tick_host_ms: host ms a call, in the jitted call + around "
             "it: " + (", ".join(f"{k} {a:.3f} + {b:.3f}"
                                 for k, (a, b) in calls.items())
                       or "no bench.*_dispatch spans to split it"))
    model_flops(ctx, ticks)
    against_probe(ctx, ticks)
    return host
