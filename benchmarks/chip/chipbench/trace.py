"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

* device events: every event on the ``XLA Ops`` line (one per executed
  operation) and on the ``XLA Modules`` line (one per executed program)
  of each ``/device:*`` plane;
* host spans: the benchmark's own ``bench.*`` annotations;
* the clock: the ``bench.clock`` annotation was made at a known host
  ``perf_counter`` time, which maps host seconds onto trace nanoseconds.

Busy time is the union of the operation intervals inside the window,
averaged over the devices; idle gaps are the holes in that union, each
labelled by the host span under its midpoint.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Events:
    """Parallel arrays of named intervals, in trace nanoseconds."""
    names: List[str]
    start: np.ndarray
    end: np.ndarray

    @classmethod
    def of(cls, rows: Sequence[Tuple[str, float, float]]) -> "Events":
        rows = sorted(rows, key=lambda r: r[1])
        return cls([r[0] for r in rows],
                   np.array([r[1] for r in rows], np.float64),
                   np.array([r[1] + r[2] for r in rows], np.float64))

    def within(self, lo: float, hi: float) -> "Events":
        keep = (self.start >= lo) & (self.end <= hi)
        return Events([n for n, k in zip(self.names, keep) if k],
                      self.start[keep], self.end[keep])

    def matching(self, pattern: str) -> "Events":
        rx = re.compile(pattern)
        keep = np.array([bool(rx.search(n)) for n in self.names], bool)
        if not len(keep):
            return self
        return Events([n for n, k in zip(self.names, keep) if k],
                      self.start[keep], self.end[keep])

    @property
    def durations(self) -> np.ndarray:
        return self.end - self.start

    def leaves(self) -> "Events":
        """Drop the events that enclose the next one (a ``while`` or
        ``call`` spans the ops of its body on the same line)."""
        if len(self.names) < 2:
            return self
        keep = np.append(self.start[1:] >= self.end[:-1], True)
        return Events([n for n, k in zip(self.names, keep) if k],
                      self.start[keep], self.end[keep])


@dataclasses.dataclass
class Trace:
    ops: List[Events]              # one per device
    modules: List[Events]          # one per device
    host: Events                   # bench.* spans
    t0_ns: float                   # traced window, trace nanoseconds
    t1_ns: float

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _line_events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def read(path: str, clock_mark_s: float, t0_s: float, t1_s: float) -> Trace:
    """Parse one xplane; ``clock_mark_s`` is the host ``perf_counter``
    time at which the ``bench.clock`` span was opened, and [t0_s, t1_s]
    the traced window in the same clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    mark = None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.append(Events.of(_line_events(line)))
                elif line.name == MODULES_LINE:
                    modules.append(Events.of(
                        [(_SUFFIX.sub("", n), s, d)
                         for n, s, d in _line_events(line)]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for n, s, d in _line_events(line):
                    if n == "bench.clock":
                        mark = s
                    elif n.startswith("bench."):
                        host.append((n, s, d))
    if mark is None:
        raise ValueError(f"{path}: no bench.clock span")
    off = mark - clock_mark_s * 1e9
    return Trace(ops, modules, Events.of(host), t0_s * 1e9 + off,
                 t1_s * 1e9 + off)


def union(starts: np.ndarray, ends: np.ndarray) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for s, e in sorted(zip(starts.tolist(), ends.tolist())):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(iv: List[Tuple[float, float]], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def busy_s(tr: Trace) -> Optional[float]:
    """Seconds in which some operation ran, averaged over the devices."""
    if not tr.ops:
        return None
    per = [sum(e - s for s, e in clip(union(ev.start, ev.end),
                                      tr.t0_ns, tr.t1_ns)) * 1e-9
           for ev in tr.ops]
    return float(np.mean(per))


def idle_gaps(tr: Trace, device: int = 0) -> List[Tuple[float, float]]:
    """Holes in the device's busy union inside the window (ns)."""
    busy = clip(union(tr.ops[device].start, tr.ops[device].end),
                tr.t0_ns, tr.t1_ns)
    gaps, cur = [], tr.t0_ns
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < tr.t1_ns:
        gaps.append((cur, tr.t1_ns))
    return gaps


def host_label(tr: Trace, t_ns: float, default: str = "scheduler") -> str:
    """The innermost bench.* span open at ``t_ns``, else ``default``
    (the engine's own Python between two dispatches)."""
    h = tr.host
    inside = [(h.end[i] - h.start[i], h.names[i]) for i in range(len(h.names))
              if h.start[i] <= t_ns <= h.end[i]]
    return min(inside)[1] if inside else default


def short_name(op: str) -> str:
    """``%copy.80 = bf16[...] copy(...)`` -> ``copy.80``."""
    return op.split(" = ", 1)[0].lstrip("%")


def op_labels(ops: Events, modules: Optional[Events]) -> List[str]:
    """``<program>/<op>`` for each operation, the program being the
    module execution that encloses it."""
    if modules is None or not modules.names:
        return [short_name(n) for n in ops.names]
    i = np.searchsorted(modules.start, ops.start, side="right") - 1
    out = []
    for n, j, s in zip(ops.names, i.tolist(), ops.start.tolist()):
        inside = j >= 0 and s < modules.end[j]
        out.append((modules.names[j] + "/" if inside else "")
                   + short_name(n))
    return out


def top_ops(tr: Trace, k: int = 10) -> List[Tuple[str, float]]:
    """Operations (innermost ones) that took the most device time in the
    window, by program and op name, in seconds summed over the devices."""
    tot: Dict[str, float] = {}
    for dev, ev in enumerate(tr.ops):
        w = ev.leaves().within(tr.t0_ns, tr.t1_ns)
        mods = tr.modules[dev] if dev < len(tr.modules) else None
        for n, d in zip(op_labels(w, mods), w.durations.tolist()):
            tot[n] = tot.get(n, 0.0) + d * 1e-9
    return sorted(tot.items(), key=lambda x: -x[1])[:k]


def longest_gaps(tr: Trace, k: int = 10) -> List[Tuple[str, float]]:
    if not tr.ops:
        return []
    gaps = sorted(idle_gaps(tr), key=lambda g: g[0] - g[1])[:k]
    return [(host_label(tr, (s + e) / 2), (e - s) * 1e-9) for s, e in gaps]


def program_times(tr: Trace, pattern: str) -> np.ndarray:
    """Device seconds of each execution of the programs whose module name
    matches ``pattern``, inside the window, device 0."""
    if not tr.modules:
        return np.zeros(0)
    ev = tr.modules[0].within(tr.t0_ns, tr.t1_ns).matching(pattern)
    return ev.durations * 1e-9


def op_times(tr: Trace, pattern: str) -> np.ndarray:
    """Device seconds of each innermost operation whose full HLO text
    matches ``pattern``, inside the window, device 0."""
    if not tr.ops:
        return np.zeros(0)
    ev = tr.ops[0].leaves().within(tr.t0_ns, tr.t1_ns).matching(pattern)
    return ev.durations * 1e-9
