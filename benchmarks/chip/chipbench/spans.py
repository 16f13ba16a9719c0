"""The engine's own spans, read from the run's profile.

``PagedServeEngine.run`` opens a ``serve.tick`` span around each pass of
its scheduler loop and, inside it, one span per phase: ``serve.control``,
``serve.admit``, ``serve.prefill`` (one per chunk, ``serve.prefill.wait``
inside it), ``serve.grow``, ``serve.decode`` (``serve.decode.wait``
inside it) and ``serve.check``.  The spans carry the tick's counts as
integer arguments.  They land on the host planes of the ``.xplane.pb``
the ``Trace`` is read from, in the same nanoseconds, so they line up
with the device's operations as well as the profiler synchronises the
two clocks; ``wait_excess_ns`` reads durations only.

A tick counts only if it lies wholly inside the traced window: the
profiler starts and stops in the middle of one.  A program without these
spans yields no ticks, and the metrics that read them report nothing.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Sequence, Tuple

import numpy as np

from chipbench import harness, trace

PREFIX = "serve."
TICK = "serve.tick"
WAIT = ".wait"
CALLS = ("serve.prefill", "serve.decode")
# each phase span's own time, as the notes name it
PHASES = {"serve.control": "control", "serve.admit": "admit",
          "serve.prefill": "prefill host", "serve.grow": "grow",
          "serve.decode": "decode host", "serve.check": "check",
          "serve.tick": "loop"}


@dataclasses.dataclass
class Span:
    name: str
    start: float                   # trace nanoseconds
    end: float
    args: Dict[str, int]
    children: List["Span"] = dataclasses.field(default_factory=list)

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def self_ns(self) -> float:
        """Its duration less the part its child spans cover."""
        return self.dur - sum(c.dur for c in self.children)

    def walk(self) -> Iterator["Span"]:
        yield self
        for c in self.children:
            yield from c.walk()

    def waits(self) -> List["Span"]:
        return [s for s in self.walk() if s.name.endswith(WAIT)]

    @property
    def host_ns(self) -> float:
        """The span's time less the waits for the device inside it."""
        return self.dur - sum(w.dur for w in self.waits())


Row = Tuple[str, float, float, Dict[str, int]]     # name, start, dur, args


def events(path: str) -> List[List[Row]]:
    """The ``serve.*`` events of each host line (one line a thread)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            rows = [(e.name, float(e.start_ns), float(e.duration_ns),
                     {k: int(v) for k, v in e.stats})
                    for e in line.events if e.name.startswith(PREFIX)]
            if rows:
                out.append(rows)
    return out


def tree(rows: Sequence[Row]) -> List[Span]:
    """Nest one thread's spans by their intervals; the roots in order."""
    roots: List[Span] = []
    stack: List[Span] = []
    for name, s, d, args in sorted(rows, key=lambda r: (r[1], -r[2])):
        sp = Span(name, s, s + d, args)
        while stack and not (s >= stack[-1].start and sp.end <= stack[-1].end):
            stack.pop()
        (stack[-1].children if stack else roots).append(sp)
        stack.append(sp)
    return roots


def whole_ticks(lines: Sequence[Sequence[Row]], t0_ns: float,
                t1_ns: float) -> List[Span]:
    """The ``serve.tick`` roots wholly inside [t0_ns, t1_ns], by start."""
    ticks = [sp for rows in lines for sp in tree(rows)
             if sp.name == TICK and sp.start >= t0_ns and sp.end <= t1_ns]
    return sorted(ticks, key=lambda sp: sp.start)


def of(ctx) -> List[Span]:
    """The whole ticks of a traced run's window, read once per run and
    kept on the reader's context as ``serve_ticks``."""
    if ctx.trace is None:
        return []
    if not hasattr(ctx, "serve_ticks"):
        path = trace.find_xplane(str(harness.TRACE_DIR))
        ctx.serve_ticks = whole_ticks(events(path), ctx.trace.t0_ns,
                                      ctx.trace.t1_ns)
    return ctx.serve_ticks


def in_ticks(t_ns, ticks: Sequence[Span]) -> np.ndarray:
    """Which of the times ``t_ns`` fall inside one of ``ticks``."""
    t_ns = np.asarray(t_ns, np.float64)
    if not len(ticks):
        return np.zeros(t_ns.shape, bool)
    starts = np.array([k.start for k in ticks])
    ends = np.array([k.end for k in ticks])
    i = np.searchsorted(starts, t_ns, side="right") - 1
    return (i >= 0) & (t_ns <= ends[np.maximum(i, 0)])


def named(ticks: Sequence[Span], name: str) -> List[Span]:
    return [s for t in ticks for s in t.walk() if s.name == name]


def self_ms_per_tick(ticks: Sequence[Span]) -> Dict[str, float]:
    """Each phase's own host time (waits excluded), ms per tick."""
    tot = {label: 0.0 for label in PHASES.values()}
    for t in ticks:
        for s in t.walk():
            if s.name in PHASES:
                tot[PHASES[s.name]] += s.self_ns
    return {k: v * 1e-6 / len(ticks) for k, v in tot.items()}


def tick_host_ms(ticks: Sequence[Span]) -> float:
    return float(np.mean([t.host_ns for t in ticks])) * 1e-6


class Idle:
    """Device idle time inside any interval, from one device's idle
    gaps (sorted, disjoint), by prefix sums."""

    def __init__(self, gaps: Sequence[Tuple[float, float]]):
        g = np.asarray(gaps, np.float64).reshape(-1, 2)
        self.s, self.len = g[:, 0], g[:, 1] - g[:, 0]
        self.before = np.concatenate([[0.0], np.cumsum(self.len)])

    def upto(self, t) -> np.ndarray:
        """Idle nanoseconds before each time in ``t``."""
        t = np.asarray(t, np.float64)
        if not len(self.s):
            return np.zeros_like(t)
        j = np.maximum(np.searchsorted(self.s, t, side="right") - 1, 0)
        return self.before[j] + np.clip(t - self.s[j], 0.0, self.len[j])

    def within(self, start, end) -> np.ndarray:
        return self.upto(end) - self.upto(start)


def calls(ticks: Sequence[Span]) -> List[Tuple[Span, Span]]:
    """Each ``serve.prefill`` / ``serve.decode`` span with its wait."""
    return [(s, w) for t in ticks for s in t.walk()
            if s.name in CALLS for w in s.children if w.name.endswith(WAIT)]


def wait_excess_ns(pairs: Sequence[Tuple[Span, Span]], idle: Idle
                   ) -> np.ndarray:
    """For each (call, wait): the wait less the device's busy time from
    the call span's start to the wait's end.  The call's program runs
    wholly inside that interval and nothing else does (the previous call
    was waited for, and the host builds inputs before launching), so an
    offset between the device's clock and the host's, up to the host
    time on either side of the program, changes nothing.  It is the
    device idle time the wait adds beyond the host's own time: the idle
    inside the wait less what of the program ran before the wait began."""
    if not pairs:
        return np.zeros(0)
    cs = np.array([c.start for c, _ in pairs])
    ws = np.array([w.start for _, w in pairs])
    we = np.array([w.end for _, w in pairs])
    return idle.within(cs, we) - (ws - cs)


def self_idle_ms_per_tick(ticks: Sequence[Span], idle: Idle
                          ) -> Dict[str, float]:
    """Device idle time under each innermost span, by span name, ms per
    tick: a span's idle less what its child spans cover."""
    spans = [s for t in ticks for s in t.walk()]
    inside = idle.within([s.start for s in spans], [s.end for s in spans])
    at = {id(s): v for s, v in zip(spans, inside.tolist())}
    tot: Dict[str, float] = {}
    for s in spans:
        own = at[id(s)] - sum(at[id(c)] for c in s.children)
        tot[s.name] = tot.get(s.name, 0.0) + own
    return {k: v * 1e-6 / len(ticks) for k, v in tot.items()}
