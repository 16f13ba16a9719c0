"""The one serve request/result schema both engines speak.

Before this module, :class:`~repro.serve.engine.ServeEngine` consumed
padded ``(B, S)`` token matrices while :class:`PagedServeEngine.run`
took bare ``(prompt, n_steps, arrival)`` tuples and returned an ad-hoc
stats dict — every consumer re-invented the conversion.  The typed surface is:

* :class:`Request` — one serve request: prompt tokens, tokens to
  generate, arrival tick;
* :class:`RequestResult` — per-request outcome: generated tokens plus
  the scheduling record (admitted/finished ticks, per-token emit
  wall-times, prefix-cache pages taken);
* :class:`RunStats` — the run-level accounting every engine returns.
  It is a dataclass but stays **dict-compatible** (``stats["tokens"]``,
  ``.get``, ``.keys``) so the pre-existing consumers keep working;
* ``run(trace, *, temperature=0.0, seed=0)`` — the shared protocol:
  both engines take a sequence of :class:`Request` (or legacy tuples,
  coerced by :func:`as_requests` for one more release) and return
  ``(List[RequestResult], RunStats)``.

The tuple form is deprecated: :func:`as_requests` emits a one-shot
:class:`DeprecationWarning` the first time it coerces one, and the shim
is dropped once external traces have moved to :class:`Request`.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import (Any, Dict, Iterator, List, Optional, Protocol, Sequence,
                    Tuple, Union, runtime_checkable)

import numpy as np

__all__ = ["Request", "RequestResult", "RunStats", "ServeAPI",
           "as_requests"]


@dataclasses.dataclass
class Request:
    """One serve request: ``prompt`` (1-D int32 tokens), ``n_steps``
    tokens to generate, ``arrival`` tick at which it may be admitted.

    ``deadline`` is the absolute tick the request must have *finished*
    by — crossing it retires the request with status ``TIMEOUT``
    (partial tokens kept).  ``cancel_at`` is the tick the client gives
    up, queued or in-flight, retiring with ``CANCELLED``.  Both are
    optional; ``None`` means the pre-resilience wait-forever behavior.
    """

    prompt: np.ndarray
    n_steps: int
    arrival: int = 0
    deadline: Optional[int] = None
    cancel_at: Optional[int] = None


@dataclasses.dataclass
class RequestResult:
    tokens: np.ndarray              # (n_steps,) generated tokens
    prompt_len: int
    arrival: int                    # tick the request became eligible
    admitted: int                   # tick it was admitted (-1: never)
    finished: int                   # tick its last token was emitted
    emit_times: List[float]         # perf_counter() per emitted token
    admit_time: float = 0.0         # perf_counter() at admission (TTFT base)
    prefix_blocks: int = 0          # pages taken from the prefix cache
    status: str = "OK"              # terminal state (repro.serve.resilience)
    detail: str = ""                # human-readable reason for non-OK ends
    preemptions: int = 0            # times this request was evicted/requeued


@dataclasses.dataclass
class RunStats:
    """Run-level accounting shared by every engine.

    Fields an engine has no notion of stay at their zero defaults (the
    synchronous bucket engine has no block pool, so its occupancy and
    prefix counters are 0; it reports ``batches`` instead).  Mapping-
    style access (``stats["tokens"]``) is kept for the consumers that
    predate this schema.
    """

    requests: int = 0
    tokens: int = 0                 # requested tokens actually emitted
    ticks: int = 0
    decode_steps: int = 0
    prefill_chunks: int = 0
    batches: int = 0                # sync bucket replay only
    prefix_blocks_reused: int = 0
    prefix_blocks_needed: int = 0
    prefix_hit_rate: float = 0.0
    occupancy_mean: float = 0.0
    occupancy_max: float = 0.0
    # -- graceful-degradation accounting (repro.serve.resilience) ----------
    completed: int = 0              # requests that ended with status OK
    shed: int = 0                   # rejected by admission control
    timeouts: int = 0               # deadline crossed before completion
    cancelled: int = 0              # client cancel_at reached
    preemptions: int = 0            # evictions (incl. re-queues that ran OK)
    stalled_ticks: int = 0          # data-plane ticks lost to stall faults

    # -- dict compatibility -------------------------------------------------

    def __getitem__(self, key: str) -> Any:
        try:
            return getattr(self, key)
        except AttributeError:
            raise KeyError(key) from None

    def get(self, key: str, default: Any = None) -> Any:
        return getattr(self, key, default)

    def keys(self) -> Iterator[str]:
        return iter(f.name for f in dataclasses.fields(self))

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@runtime_checkable
class ServeAPI(Protocol):
    """The shared serve protocol: replay a trace to completion."""

    def run(self, requests: Sequence[Union[Request, Tuple]], *,
            temperature: float = 0.0, seed: int = 0
            ) -> Tuple[List[RequestResult], RunStats]:
        ...


_WARNED: set = set()


def _warn_once(key: str, msg: str) -> None:
    if key not in _WARNED:
        _WARNED.add(key)
        warnings.warn(msg, DeprecationWarning, stacklevel=3)


def as_requests(trace: Sequence[Union[Request, Tuple]]) -> List[Request]:
    """Coerce a trace to typed :class:`Request` objects.

    Accepts ``Request`` instances (normalised in place: prompt flattened
    to 1-D int32) and, for one more release, bare
    ``(prompt, n_steps[, arrival])`` tuples/lists — the legacy form
    every caller used before :mod:`repro.serve.api` existed.  Coercing a
    tuple emits a one-shot :class:`DeprecationWarning`.
    """
    reqs: List[Request] = []
    for i, r in enumerate(trace):
        if not isinstance(r, Request):
            if not isinstance(r, (tuple, list)) or not 2 <= len(r) <= 3:
                raise TypeError(
                    f"request {i}: expected a repro.serve.Request or a "
                    f"legacy (prompt, n_steps[, arrival]) tuple, got "
                    f"{type(r).__name__}")
            _warn_once(
                "tuple-trace",
                "passing (prompt, n_steps[, arrival]) tuples to run() is "
                "deprecated; build repro.serve.Request objects (e.g. via "
                "the repro.serve.traces generators) instead")
            r = Request(*r)
        r.prompt = np.asarray(r.prompt, np.int32).reshape(-1)
        r.n_steps = int(r.n_steps)
        r.arrival = int(r.arrival)
        if r.deadline is not None:
            r.deadline = int(r.deadline)
        if r.cancel_at is not None:
            r.cancel_at = int(r.cancel_at)
        if r.n_steps < 1:
            raise ValueError(f"request {i}: n_steps={r.n_steps} < 1")
        reqs.append(r)
    return reqs
