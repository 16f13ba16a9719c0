"""Continuous-batching serve engine over the block-paged KV cache.

:class:`PagedServeEngine` is the data plane around the host scheduler in
:mod:`repro.serve.scheduler`, which owns the request states and the
tick's order (faults, cancel, timeout, forced preemptions, shed, admit,
prefill one chunk per prefilling slot, grow and decode).  The engine
runs the scheduler's phases inside its spans, hands the scheduler's
block tables and lengths to the two jitted steps, threads the pool
pytree through them, and samples the tokens the scheduler emits.

Bitwise-parity notes (unchanged from the pre-resilience engine): the
sync oracle runs ``ServeEngine(prefill_pad=True)`` on long prompts
(aligned-T recipe), greedy tokens are computed in-graph, every tick is
fully materialized before the next dispatch, and lazy tables point
unallocated rows at the null block — all reads are kv_len-masked, so
block-table raggedness never perturbs numerics (the stale-residue
determinism test pins this).

Temperature sampling uses per-request key streams
(``fold_in(PRNGKey(seed), request_index)``, split once per sampled
token); a preempted request's recompute replays the same stream from
the start, so sampled runs are preemption-deterministic too.

Spans: every tick records ``jax.profiler.TraceAnnotation`` spans, which
cost about a microsecond each when no profiler runs and otherwise land
in the profiler's trace on the device's clock.  Their integer arguments
are the tick's counts::

    serve.tick             tick, queued, busy (slots held)
      serve.control        steps 1-4: cancelled, timed_out, shed
      serve.admit          step 5: admitted, prefix_blocks
      serve.prefill        one chunk of step 6: req, slot, start, n_valid
                           (+ routed_rows, MoE configs)
        serve.prefill.wait   the wait for that chunk
      serve.grow           step 7a: grown, preempted
      serve.decode         step 7b: active (slots), kv_rows (sum of lens + 1),
                           kv_pages (pages the attention kernel fetches a
                           layer: ceil((lens + 1) / page) over all slots)
                           (+ routed_rows, MoE configs)
        serve.decode.wait    the wait for that step
      serve.check          the invariant checks, when on

``routed_rows`` is the MoE assignments the call's held experts computed,
summed over its layers (valid rows only); the step returns it beside the
tokens and it is read after the same wait.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.model import paged_decode_step, paged_prefill_step
from repro.serve.api import Request, RequestResult, RunStats
from repro.serve.paged_cache import PagedKVCache, default_page_size
from repro.serve.resilience import AdmissionPolicy, FaultPlan, shed_policies
from repro.serve.scheduler import Scheduler

__all__ = ["PagedServeEngine", "Request", "RequestResult", "serve_steps"]

span = jax.profiler.TraceAnnotation          # see "Spans" above


def _set_counters(sp, counters) -> None:
    """Put a MoE step's ``counters`` output on its span, once waited on."""
    if counters:
        sp.set_metadata(**{k: int(v) for k, v in
                           jax.device_get(counters[0]).items()})


def serve_steps(cfg: ModelConfig, *, aligned: bool):
    """The engine's two jitted programs, ``(decode, prefill)``:
    ``decode(params, pools, tokens (B, 1), tables, lens)`` and
    ``prefill(params, pools, tokens (1, C), tables (1, NB), start (1,),
    n_valid (1,))``, each returning ``(logits, greedy tokens, pools)``
    and, for a MoE config only, ``counters`` after them: its
    ``routed_rows``, which the call's spans carry.  Greedy tokens are computed
    in-graph: the scheduler's hot loop transfers (B,) ints per tick, not
    (B, V) logits + eager ops.

    The pool pytree is donated: run() threads one live pools value
    through every dispatch and never reads a superseded one, so a step's
    output reuses the input's buffers.  The rows themselves are written
    in place by the layer scan, which carries the pool stacks and writes
    each layer's new rows at its index; donation only spares the copy of
    the input.  ONE compiled prefill serves every prompt: fixed (1, C)
    tokens against the full table width, the ragged final chunk padded
    and masked via ``n_valid`` instead of recompiling."""

    def _step(p, c, t, tbl, ln):
        logits, new_c, counters = paged_decode_step(cfg, p, c, t, tbl, ln)
        toks = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return (logits, toks, new_c) + ((counters,) if counters else ())

    def _pstep(p, c, t, tbl, ln, nv):
        logits, new_c, counters = paged_prefill_step(
            cfg, p, c, t, tbl, ln, nv, aligned=aligned)
        toks = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return (logits, toks, new_c) + ((counters,) if counters else ())

    return (jax.jit(_step, donate_argnums=(1,)),
            jax.jit(_pstep, donate_argnums=(1,)))


class PagedServeEngine:
    """Continuous-batching engine: one compiled decode step, one compiled
    chunk-prefill step, ``max_batch`` slots, a :class:`PagedKVCache` pool
    shared by all in-flight requests.

    ``n_blocks=None`` sizes the pool so every slot can hold a full
    ``max_len`` request (plus the null block) — pass something smaller
    to exercise admission backpressure and mid-flight preemption.
    ``prefix_cache=False`` disables block sharing; ``prefill_chunk`` is
    the incremental-prefill granularity.

    Graceful-degradation knobs (see :mod:`repro.serve.resilience`):
    ``max_queue`` bounds the waiting queue (excess arrivals shed with a
    descriptive reason); ``admission`` plugs in a shed policy (e.g.
    ``DeadlineAwareShed``); ``max_preemptions`` caps how often one
    request may be evicted and recomputed before it retires terminal
    ``PREEMPTED``; ``check_invariants=True`` asserts the pool's
    conservation invariants after every tick (always on under a
    ``fault_plan``)."""

    def __init__(self, cfg: ModelConfig, params, *, max_len: int = 512,
                 max_batch: int = 8, n_blocks: Optional[int] = None,
                 page: Optional[int] = None, device=None,
                 prefix_cache: bool = True, prefill_chunk: int = 32,
                 max_queue: Optional[int] = None,
                 admission: Optional[AdmissionPolicy] = None,
                 max_preemptions: int = 8,
                 check_invariants: bool = False):
        if page is None:
            # cap the planner's block at max_len: an uncapped probe hands
            # back the largest VMEM-admissible page (512 on every current
            # device), and short-request engines would then gather, mask
            # and convert 4x more pool rows per tick than they can use
            page = default_page_size(cfg, device, cap=max_len)
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk={prefill_chunk} < 1")
        if max_preemptions < 0:
            raise ValueError(f"max_preemptions={max_preemptions} < 0")
        self.page = int(page)
        self.nb_table = math.ceil(max_len / self.page)
        if n_blocks is None:
            n_blocks = max_batch * self.nb_table + 1
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.max_batch = max_batch
        self.prefix_cache = prefix_cache
        self.prefill_chunk = int(prefill_chunk)
        self.max_preemptions = int(max_preemptions)
        self.check_invariants = bool(check_invariants)
        self.policies = shed_policies(max_queue, admission)
        self.max_queue = max_queue
        self.cache = PagedKVCache(cfg, n_blocks=n_blocks, page=self.page,
                                  device=device)

        # chunks start at multiples of prefill_chunk past a page boundary
        # (prefix matches are page-aligned), so when the chunk size
        # divides the page no chunk ever crosses a block boundary and the
        # pool write collapses to one contiguous slice (aligned=True)
        self._decode, self._prefill = serve_steps(
            cfg, aligned=self.page % self.prefill_chunk == 0)

    def _sample_tick(self, logits, greedy, keys, temperature: float):
        """One transfer for a whole decode tick -> (B,) host tokens.
        Greedy tokens were already computed in-graph (the sync engine's
        exact row-wise argmax); temperature draws one categorical per
        slot from that slot's own key stream."""
        if temperature <= 0.0:
            return np.asarray(greedy, np.int32)
        toks = jax.vmap(lambda k, l: jax.random.categorical(
            k, l / temperature, axis=-1))(jnp.stack(keys), logits)
        return np.asarray(toks, np.int32)

    # -- the data plane around the scheduler ---------------------------------

    def run(self, requests: Sequence[Union[Request, Tuple]], *,
            temperature: float = 0.0, seed: int = 0,
            fault_plan: Optional[FaultPlan] = None,
            max_ticks: Optional[int] = None
            ) -> Tuple[List[RequestResult], RunStats]:
        """Serve ``requests`` to completion: every request reaches a
        terminal status (``OK``/``TIMEOUT``/``CANCELLED``/``SHED``/
        ``PREEMPTED``) and ``results`` come back in input order.

        ``fault_plan`` injects the deterministic fault schedule (and
        turns per-tick ``check_invariants`` on); ``max_ticks`` is the
        deadlock canary — exceeding it raises ``RuntimeError`` instead
        of spinning forever (e.g. under a permanent stall fault).
        """
        sch = Scheduler(requests, self.cache, max_len=self.max_len,
                        max_batch=self.max_batch, page=self.page,
                        prefill_chunk=self.prefill_chunk,
                        prefix_cache=self.prefix_cache,
                        policies=self.policies,
                        max_preemptions=self.max_preemptions,
                        fault_plan=fault_plan, max_ticks=max_ticks,
                        check_invariants=self.check_invariants)
        sampled = temperature > 0.0
        root = jax.random.PRNGKey(seed)
        keys: Dict[int, jax.Array] = {}     # request -> its key stream
        pools = self.cache.pools
        C = self.prefill_chunk
        while sch.running():
            with span("serve.tick", tick=sch.tick, queued=len(sch.queue),
                      busy=sch.busy):
                with span("serve.control") as sp:
                    sp.set_metadata(**sch.control())
                with span("serve.admit") as sp:
                    counts, admitted = sch.admit()
                    for rid in admitted:    # each admission restarts it
                        keys[rid] = jax.random.fold_in(root, rid)
                    sp.set_metadata(**counts)
                for si, rid, pos, nv in sch.prefill_work():
                    with span("serve.prefill", req=rid, slot=si, start=pos,
                              n_valid=nv) as sp:
                        prompt = sch.reqs[rid].prompt
                        toks = np.zeros((1, C), np.int32)
                        toks[0, :nv] = prompt[pos:pos + nv]
                        # jnp.array (not asarray): don't alias scheduler
                        # state the async dispatch would race with (same
                        # rationale as decode)
                        logits, greedy, pools, *counters = self._prefill(
                            self.params, pools, jnp.array(toks),
                            jnp.array(sch.tables[si:si + 1]),
                            jnp.array([pos], np.int32),
                            jnp.array([nv], np.int32))
                        with span("serve.prefill.wait"):
                            jax.block_until_ready((logits, greedy, pools,
                                                   counters))
                        _set_counters(sp, counters)
                        tok = None
                        if pos + nv == prompt.shape[0]:   # the first token
                            if sampled:
                                keys[rid], sub = jax.random.split(
                                    keys[rid])
                                tok = int(jax.random.categorical(
                                    sub, logits[0, -1] / temperature))
                            else:
                                tok = int(greedy[0])
                        sch.prefilled(si, tok)

                with span("serve.grow") as sp:
                    sp.set_metadata(**sch.grow())

                active = sch.decode_batch()
                # every slot outside ``active`` sits at length 0, so the
                # rows the step attends, sum(lens[active] + 1), is one sum;
                # the kernel fetches ceil((lens + 1) / page) pages a slot
                # and a layer, every slot at least one
                lens = sch.lens
                if active:
                    with span("serve.decode", active=len(active),
                              kv_rows=int(lens.sum()) + len(active),
                              kv_pages=int(((lens + self.page)
                                            // self.page).sum())) as sp:
                        # jnp.array (not asarray): asarray zero-copies
                        # numpy on CPU, so the async decode would alias
                        # these host buffers while the scheduler keeps
                        # mutating them (retire zeroes table rows, lens
                        # advance) — a read/write race on real state.
                        # PREFILLING slots already sit at lens 0 so the
                        # decode masks them like idle slots; their table
                        # rows are real but every read is kv_len-masked and
                        # the pend-0 write lands at row 0 of their first
                        # block, which the next chunk overwrites (positions
                        # are absolute).
                        dec_tables = sch.tables.copy()
                        for si, slot in enumerate(sch.slots):
                            if slot is not None and lens[si] == 0:
                                dec_tables[si] = 0  # scatter to null block
                        logits, greedy, pools, *counters = self._decode(
                            self.params, pools, jnp.array(sch.pend[:, None]),
                            jnp.array(dec_tables), jnp.array(lens))
                        # materialize the whole tick before dispatching
                        # anything else: overlapping executions on
                        # XLA:CPU's shared thunk thread pool perturb
                        # parallel-reduction numerics, and a near-tie
                        # argmax flip breaks bitwise greedy parity with the
                        # synchronous engine (whose single lax.scan decode
                        # loop never overlaps itself).  The greedy-token
                        # transfer below already serialized most of the
                        # tick; this pins the pool updates too, so no
                        # computation from run() is ever still in flight
                        # when the caller's next one starts.
                        with span("serve.decode.wait"):
                            jax.block_until_ready((logits, greedy, pools,
                                                   counters))
                        _set_counters(sp, counters)
                        subs = None
                        if sampled:
                            subs = [root] * self.max_batch  # idle: discarded
                            for si in active:
                                rid = sch.slots[si].req
                                keys[rid], subs[si] = jax.random.split(
                                    keys[rid])
                        sch.decoded(active, self._sample_tick(
                            logits[:, -1], greedy, subs, temperature))
                sch.end_tick()
                if sch.checking:
                    with span("serve.check"):
                        sch.check()

        self.cache.pools = pools
        return sch.results(), sch.stats()

    def generate(self, tokens: np.ndarray, *, n_steps: int = 32,
                 temperature: float = 0.0, seed: int = 0) -> np.ndarray:
        """Batch convenience mirroring ``ServeEngine.generate``: serve the
        (B, S) prompts (all arriving at tick 0) and return (B, n_steps)."""
        tokens = np.asarray(tokens, np.int32)
        reqs = [Request(prompt=row, n_steps=n_steps) for row in tokens]
        results, _ = self.run(reqs, temperature=temperature, seed=seed)
        return np.stack([r.tokens for r in results])
