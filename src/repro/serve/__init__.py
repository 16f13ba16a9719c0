"""Serving layer: synchronous batched engine (the parity oracle) and the
continuous-batching engine over the block-paged KV cache, behind the
shared typed ``run(trace)`` protocol in :mod:`repro.serve.api`.

The host half (the API, the resilience policies, the block allocator,
the scheduler and the trace registry) imports without jax, so a
host-only replica drives the scheduler without it; the engines and the
device pool load on first use."""

import importlib

from repro.serve.api import (Request, RequestResult,  # noqa: F401
                             RunStats, ServeAPI, as_requests)
from repro.serve.blocks import BlockAllocator, prefix_digests  # noqa: F401
from repro.serve.resilience import (CANCELLED, OK, PREEMPTED,  # noqa: F401
                                    SHED, STATUSES, TIMEOUT,
                                    AdmissionPolicy, DeadlineAwareShed,
                                    Fault, FaultPlan, FIFOPolicy,
                                    QueueCapPolicy, QueueEntry,
                                    min_service_ticks)
from repro.serve.scheduler import Scheduler  # noqa: F401
from repro.serve.traces import (get_trace, list_traces,  # noqa: F401
                                register_trace)

_JAX_BACKED = {"ServeEngine": "engine", "GenerateResult": "engine",
               "PagedKVCache": "paged_cache",
               "default_page_size": "paged_cache",
               "PagedServeEngine": "paged_engine"}


def __getattr__(name):
    if name in _JAX_BACKED:
        module = importlib.import_module(f"repro.serve.{_JAX_BACKED[name]}")
        return getattr(module, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
