"""Watches one ``PagedServeEngine.run`` from the benchmark's side.

The probe stands in for the engine's two jitted steps (``_prefill`` and
``_decode``, the names they carry today) with thin wrappers that call
them unchanged and note, for each call, the host time it was dispatched
and references to its small integer inputs (chunk start and length, the
decode lengths).  Those are read back once the run is over, so the
window pays no transfer for them.

Before the window it also watches for the last chunk of every request of
the first wave; once all have gone through, the next call opens the
window.  ``seconds`` after that the window closes, but the load goes on
until every request admitted by then has emitted its first token (its
TTFT is due), for at most ``GRACE_S``: the slots still prefilling at the
first decode step after the close are watched until each has decoded.
Then the probe ends the run the way a client would, through each
request's public ``cancel_at``: the engine retires everything at its
next tick.  Only that wait reads anything back during the run, and it
comes after the close.  With ``trace_dir`` it starts the profiler at the
opening and stops it ``trace_seconds`` later, both between two calls,
when the device is idle.
"""

from __future__ import annotations

import time
from typing import List, Optional

import jax
import numpy as np

GRACE_S = 60.0     # the longest the run goes on past the close


class Probe:
    def __init__(self, engine, requests, n_first: int, seconds: float, *,
                 trace_dir: Optional[str] = None,
                 trace_seconds: float = 10.0):
        self.engine = engine
        self.requests = requests
        self.seconds = float(seconds)
        self.trace_dir = trace_dir
        self.trace_seconds = min(float(trace_seconds), self.seconds)
        C = engine.prefill_chunk
        # first-wave requests by (start, length) of their last chunk
        self.waiting = {}
        for j in range(n_first):
            s = len(requests[j].prompt)
            last = (s - 1) // C * C
            self.waiting.setdefault((last, s - last), []).append(j)
        self.n_waiting = n_first
        self.open_next = n_first == 0
        self.opened: Optional[float] = None
        self.closed: Optional[float] = None
        self.ended: Optional[float] = None     # cancel set after the close
        self.prefilling: Optional[set] = None  # slots awaited after it
        self.trace_t0: Optional[float] = None
        self.trace_t1: Optional[float] = None
        self.clock_mark: Optional[float] = None
        self.prefills: List = []       # (t, start, n_valid) device refs
        self.decodes: List = []        # (t, lens) device refs
        self._prefill, self._decode = engine._prefill, engine._decode
        engine._prefill, engine._decode = self.prefill, self.decode

    def uninstall(self) -> None:
        self.engine._prefill, self.engine._decode = (self._prefill,
                                                     self._decode)
        if self.trace_t0 is not None and self.trace_t1 is None:
            self._stop_trace(time.perf_counter())

    # -- between two device calls -----------------------------------------

    def _boundary(self) -> float:
        now = time.perf_counter()
        if self.opened is None and self.open_next:
            self.opened = now
            if self.trace_dir is not None:
                self._start_trace()
                now = time.perf_counter()
        if (self.trace_t0 is not None and self.trace_t1 is None
                and now >= self.trace_t0 + self.trace_seconds):
            self._stop_trace(now)
        if (self.opened is not None and self.closed is None
                and now >= self.opened + self.seconds):
            self.closed = now
        if (self.closed is not None and self.ended is None
                and now >= self.closed + GRACE_S):
            self._end(now)
        return now

    def _await_first_tokens(self, lens, now: float) -> None:
        """After the close, at a decode step: a slot at length 0 is still
        prefilling (the queue keeps every slot filled); once each slot
        that was prefilling at the first such step has decoded, every
        request admitted by the close has its first token."""
        lens = np.asarray(jax.device_get(lens))
        if self.prefilling is None:
            self.prefilling = set(np.flatnonzero(lens == 0).tolist())
        else:
            self.prefilling -= set(np.flatnonzero(lens > 0).tolist())
        if not self.prefilling:
            self._end(now)

    def _end(self, now: float) -> None:
        self.ended = now
        for r in self.requests:
            r.cancel_at = 0              # retired at the engine's next tick

    def _start_trace(self) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.clock_mark = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.clock"):
            pass
        self.trace_t0 = time.perf_counter()

    def _stop_trace(self, now: float) -> None:
        self.trace_t1 = now
        jax.profiler.stop_trace()

    def _first_wave(self, toks, start, n_valid) -> None:
        pos, n = (int(x[0]) for x in jax.device_get((start, n_valid)))
        for j in self.waiting.get((pos, n), ()):
            if np.array_equal(np.asarray(toks)[0, :n],
                              self.requests[j].prompt[pos:pos + n]):
                self.waiting[(pos, n)].remove(j)
                self.n_waiting -= 1
                self.open_next = self.n_waiting == 0
                return

    # -- the wrapped steps --------------------------------------------------

    def prefill(self, p, c, toks, tables, start, n_valid):
        now = self._boundary()
        if not self.open_next:
            self._first_wave(toks, start, n_valid)
        if self.trace_t0 is not None and self.trace_t1 is None:
            with jax.profiler.TraceAnnotation("bench.prefill_dispatch"):
                out = self._prefill(p, c, toks, tables, start, n_valid)
        else:
            out = self._prefill(p, c, toks, tables, start, n_valid)
        self.prefills.append((now, start, n_valid))
        return out

    def decode(self, p, c, toks, tables, lens):
        now = self._boundary()
        if self.closed is not None and self.ended is None:
            self._await_first_tokens(lens, now)
        if self.trace_t0 is not None and self.trace_t1 is None:
            with jax.profiler.TraceAnnotation("bench.decode_dispatch"):
                out = self._decode(p, c, toks, tables, lens)
        else:
            out = self._decode(p, c, toks, tables, lens)
        self.decodes.append((now, lens))
        return out

    # -- after the run -------------------------------------------------------

    def calls(self):
        """(prefill times, starts, lengths), (decode times, lens (n, B))."""
        pf = jax.device_get([(s, n) for _, s, n in self.prefills])
        dc = jax.device_get([ln for _, ln in self.decodes])
        pt = np.array([t for t, _, _ in self.prefills])
        dt = np.array([t for t, _ in self.decodes])
        ps = np.array([int(s[0]) for s, _ in pf], np.int64)
        pn = np.array([int(n[0]) for _, n in pf], np.int64)
        lens = (np.stack([np.asarray(x) for x in dc]) if dc
                else np.zeros((0, self.engine.max_batch), np.int64))
        return (pt, ps, pn), (dt, lens.astype(np.int64))
