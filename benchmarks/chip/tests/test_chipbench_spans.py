"""The readers of the engine's ``serve.*`` spans: on hand-made spans and
device intervals with exact answers, and on a traced tiny run."""

import numpy as np
import pytest

from chipbench_tiny import harness, tiny_cell, tiny_run
from chipbench import spans
from chipbench import trace as tm

SEED = 2**31 + 91


def _reader(name):
    return harness.load_module(harness.BENCH_DIR / "metrics" / f"{name}.py",
                               f"chipbench_metric_{name}")


def _row(name, t0, t1, **args):
    return (name, float(t0), float(t1 - t0), args)


# one thread's spans in trace nanoseconds; the window is [0, 1000]
ROWS = [
    _row("serve.tick", -50, 90, tick=0, queued=3, busy=2),  # cut at t0
    _row("serve.decode.wait", 20, 60),        # its tick began before t0
    _row("serve.tick", 100, 400, tick=1, queued=3, busy=2),
    _row("serve.control", 110, 130, cancelled=0, timed_out=0, shed=0),
    _row("serve.admit", 130, 140, admitted=1, prefix_blocks=2),
    _row("serve.prefill", 150, 300, req=7, slot=1, start=0, n_valid=64),
    _row("serve.prefill.wait", 200, 280),
    _row("serve.grow", 300, 310, grown=1, preempted=0),
    _row("serve.decode", 310, 390, active=2, kv_rows=70),
    _row("serve.decode.wait", 330, 370),
    _row("serve.tick", 420, 700, tick=2, queued=2, busy=3),
    _row("serve.control", 420, 440, cancelled=1, timed_out=0, shed=0),
    _row("serve.decode", 450, 650, active=3, kv_rows=100),
    _row("serve.decode.wait", 470, 630),
    _row("serve.check", 660, 690),
    _row("serve.tick", 900, 1100, tick=3, queued=2, busy=3),  # cut at t1
    _row("serve.decode", 910, 1050, active=3, kv_rows=103),
    _row("serve.decode.wait", 920, 1040),
]
# device 0 busy in [0,100], [210,280], [335,370], [485,630], [700,1000]:
# idle in [100,210], [280,335], [370,485], [630,700]
OPS = [("%a = f32[] fusion()", s, e - s)
       for s, e in ((0, 100), (210, 280), (335, 370), (485, 630),
                    (700, 1000))]
# the programs the three waits wait for, each ending as its wait does,
# and a small one between
MODULES = [("jit__pstep", 210, 70), ("jit__step", 335, 35),
           ("jit_slice", 396, 3), ("jit__step", 485, 145)]
# the probe's spans around the jitted calls, the last in the cut tick
BENCH = [("bench.prefill_dispatch", 160, 30),
         ("bench.decode_dispatch", 315, 10),
         ("bench.decode_dispatch", 455, 10),
         ("bench.decode_dispatch", 912, 6)]
# what the probe saw: dispatch times (host seconds), each chunk's start
# and length, each step's lens
PREFILLS = (np.array([165e-9]), np.array([0]), np.array([64]))
DECODES = (np.array([320e-9, 460e-9, 950e-9]),
           np.array([[34, 34, 0, 0], [35, 35, 27, 0], [36, 36, 28, 0]]))


CELL = tiny_cell()
DIMS = CELL.family.dims(CELL.conf)


def _ctx(ops=OPS, rows=ROWS, modules=MODULES, bench=BENCH):
    tr = tm.Trace([tm.Events.of(ops)] if ops else [],
                  [tm.Events.of(modules)] if ops else [],
                  tm.Events.of(bench), 0.0, 1000.0)
    ctx = harness.Context(CELL, DIMS, None, 4, 0.0, 1000e-9, PREFILLS,
                          DECODES, [], tr, [])
    ctx.serve_ticks = spans.whole_ticks([rows], tr.t0_ns, tr.t1_ns)
    return ctx


def test_ticks_cut_by_the_window_and_orphans_are_dropped():
    ticks = _ctx().serve_ticks
    assert [t.args["tick"] for t in ticks] == [1, 2]
    assert [c.name for c in ticks[0].children] == [
        "serve.control", "serve.admit", "serve.prefill", "serve.grow",
        "serve.decode"]
    assert [w.name for w in ticks[0].waits()] == ["serve.prefill.wait",
                                                  "serve.decode.wait"]


def test_tick_host_ms_and_phase_times():
    ctx = _ctx()
    # (300 - 80 - 40) and (280 - 160) ns
    assert _reader("tick_host_ms").read(ctx) == pytest.approx(150e-6)
    own = spans.self_ms_per_tick(ctx.serve_ticks)
    assert own == pytest.approx({
        "control": 20e-6, "admit": 5e-6, "prefill host": 35e-6,
        "grow": 5e-6, "decode host": 40e-6, "check": 15e-6, "loop": 30e-6})
    assert sum(own.values()) == pytest.approx(150e-6)
    # prefill: 30 of its 70 ns; decode: 10 of its 40 ns, twice
    calls = _reader("tick_host_ms").in_jitted_call(ctx, ctx.serve_ticks)
    assert calls == {"prefill": pytest.approx((30e-6, 40e-6)),
                     "decode": pytest.approx((10e-6, 30e-6))}


def test_span_counters_against_the_probe():
    """The spans' counters match the probe's steps in the same ticks;
    the probe's window also holds the step of the cut tick."""
    ctx = _ctx()
    mod = _reader("tick_host_ms")
    ours, window, same = mod.against_probe(ctx, ctx.serve_ticks)
    assert ours == pytest.approx((62.5, 85.0))
    assert same == pytest.approx(ours)
    assert window == pytest.approx((100 * 8 / 12, 91.0))


def test_every_counter_is_read_into_the_notes():
    ctx = _ctx()
    mod = _reader("tick_host_ms")
    assert mod.counter_sums(ctx.serve_ticks) == {
        "admitted": 1, "prefix_blocks": 2, "cancelled": 1, "timed_out": 0,
        "shed": 0, "grown": 1, "preempted": 0}
    mod.read(ctx)
    assert any("queued 2.5 (max 3), slots busy 2.500 of 4; admitted 1, "
               "prefix_blocks 2, cancelled 1" in n and
               "chunks of 1 requests" in n for n in ctx.notes)
    assert any("longest tick 1: 0.000 ms" in n and "3 queued, 2 slots busy, "
               "1 admitted, 1 grown, 0 preempted; chunks: req 7 (slot 1, "
               "0+64)" in n for n in ctx.notes)


def test_model_flops_from_the_counters_match_the_probe():
    """The chunk from ``start``/``n_valid``, the steps from ``active``
    and ``kv_rows`` (lens + 1 of 35, 35, then 36, 36, 28), against the
    probe's calls in the same two ticks."""
    ctx = _ctx()
    fam = CELL.family
    want = float(fam.chunk_flops(DIMS, 0, 64)) + float(np.sum(
        fam.token_flops(DIMS, np.array([35, 35, 36, 36, 28]), True)))
    ours, theirs = _reader("tick_host_ms").model_flops(ctx, ctx.serve_ticks)
    assert ours == pytest.approx(want, rel=1e-12)
    assert theirs == pytest.approx(want, rel=1e-12)


def test_without_the_probe_spans_the_call_split_says_so():
    ctx = _ctx(bench=[])
    mod = _reader("tick_host_ms")
    assert mod.in_jitted_call(ctx, ctx.serve_ticks) == {}
    assert mod.read(ctx) == pytest.approx(150e-6)
    assert any("no bench.*_dispatch spans" in n for n in ctx.notes)


def test_launch_idle_ms_coverage_and_attribution():
    ctx = _ctx()
    # idle inside the three waits: 10, 5 and 15 ns
    assert _reader("launch_idle_ms").read(ctx) == pytest.approx(10e-6)
    idle = spans.Idle(tm.idle_gaps(ctx.trace))
    ticks = ctx.serve_ticks
    under = idle.within([t.start for t in ticks], [t.end for t in ticks])
    assert under.tolist() == [195.0, 135.0]
    assert idle.within([100.0], [700.0]).tolist() == [350.0]   # 330 / 350
    assert any("coverage 94.286%" in n for n in ctx.notes)
    # 150 ns host + 10 ns x 1.5 calls = the 165 ns idle a tick
    assert any("= 0.000 ms (+0.00%)" in n for n in ctx.notes)
    assert any("over 3 calls median 0.000 ms" in n and "0 over 10 ms hold "
               "0.000 of the 0.000 ms" in n and "a serve.decode in tick 2"
               in n for n in ctx.notes)
    own = spans.self_idle_ms_per_tick(ticks, idle)
    assert own["serve.prefill.wait"] == pytest.approx(5e-6)
    assert own["serve.decode.wait"] == pytest.approx(10e-6)
    assert own["serve.prefill"] == pytest.approx(35e-6)
    assert sum(own.values()) == pytest.approx(165e-6)


def _moved(dt):
    """The hand-made trace with the device's times ``dt`` ns later."""
    return _ctx(ops=[(n, s + dt, d) for n, s, d in OPS],
                modules=[(n, s + dt, d) for n, s, d in MODULES])


@pytest.mark.parametrize("dt", [-25, -10, 0])
def test_launch_idle_ms_ignores_the_clock_offset(dt):
    """Device times up to 25 ns early (the first decode starts 25 ns
    after its call span) read as one clock does; the idle inside each
    wait would not."""
    assert _reader("launch_idle_ms").read(_moved(dt)) == \
        pytest.approx(10e-6)


def test_wait_excess_leaves_out_what_ran_before_the_wait():
    """A program that starts 10 ns before its wait and ends 10 ns before
    it ends: the wait's 10 ns of idle are the host's 10 ns before it."""
    call = spans.Span("serve.decode", 0.0, 100.0, {})
    wait = spans.Span("serve.decode.wait", 40.0, 100.0, {})
    idle = spans.Idle([(-50.0, 30.0), (90.0, 200.0)])
    assert idle.within([40.0], [100.0]).tolist() == [10.0]
    assert spans.wait_excess_ns([(call, wait)], idle).tolist() == [0.0]
    assert spans.wait_excess_ns([], idle).size == 0


def test_idle_prefix_sums_match_direct_overlap():
    gaps = [(100.0, 210.0), (280.0, 335.0), (370.0, 485.0), (630.0, 700.0)]
    idle = spans.Idle(gaps)
    rng = np.random.default_rng(0)
    a = rng.uniform(-50, 1050, 200)
    b = a + rng.uniform(0, 400, 200)
    direct = [sum(max(0.0, min(e, y) - max(s, x)) for s, e in gaps)
              for x, y in zip(a, b)]
    assert np.allclose(idle.within(a, b), direct)
    assert spans.Idle([]).within([0.0], [5.0]).tolist() == [0.0]


@pytest.mark.parametrize("name", ["tick_host_ms", "launch_idle_ms"])
def test_readers_report_nothing_without_serve_spans(name):
    """A program that records no ``serve.*`` spans: no value, no error."""
    assert _reader(name).read(_ctx(rows=[])) is None


def test_launch_idle_ms_needs_a_device_plane():
    assert _reader("launch_idle_ms").read(_ctx(ops=[])) is None
    assert _reader("tick_host_ms").read(_ctx(ops=[])) is not None


def test_traced_tiny_run_reports_tick_host_ms():
    """The CPU trace has the engine's spans but no device plane."""
    out = tiny_run(tiny_cell(), SEED, trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["tick_host_ms"]["value"] > 0.0
    assert out["metrics"]["tick_host_ms"]["unit"] == "ms"
    assert "launch_idle_ms" not in out["metrics"]
