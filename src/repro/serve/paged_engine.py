"""Continuous-batching serve engine over the block-paged KV cache.

Scheduler states (per request)::

    PENDING --admit--> PREFILLING --complete--> ACTIVE --retire--> DONE
      (waits for a slot  (one prompt chunk        (decodes one        ^
       + prompt blocks)   per tick)                token per tick)    |
         ^                    |                        |              |
         |                    +-----<--preempt--<------+              |
         +--- re-queued as PENDING (tokens discarded, recompute) -----+

Each scheduler *tick* (the full order, shared verbatim with the fleet
replica ``repro.fleet.capacity.simulate_trace``):

1. **faults** — with a :class:`~repro.serve.resilience.FaultPlan`
   active: release expired block seizures, seize free blocks for
   ``exhaust`` faults firing now, note stall windows;
2. **cancel / timeout** — requests whose ``cancel_at`` has arrived
   retire ``CANCELLED``; requests whose ``deadline`` has passed retire
   ``TIMEOUT`` — queued or in-flight, partial tokens kept, blocks
   released refcount-exactly;
3. **forced preemptions** — ``preempt`` faults evict victims
   (latest-admitted first, the same rule organic exhaustion uses);
4. **shed** — the ``max_queue`` bound, then the pluggable
   :class:`~repro.serve.resilience.AdmissionPolicy`, reject waiting
   requests with a descriptive reason (terminal ``SHED``) so the
   arrival deque cannot grow without bound;
5. **admit / match prefix** — pending requests (arrival <= tick, FIFO)
   claim a free engine slot plus their **prompt** block reservation
   only (``ceil(s / page)`` blocks; decode blocks are allocated lazily
   as the sequence grows — that is what makes mid-flight exhaustion,
   and therefore preemption, possible at all).  With ``prefix_cache``
   on, the longest page-aligned cached prefix is taken straight from
   the pool (refcount bumps, zero prefill compute), capped at
   ``(s - 1) // page`` so the first-token logits always compute and
   every later write lands past the shared pages.  When the pool or
   slot array is exhausted the queue waits — admission is still the
   backpressure point;
6. **prefill one chunk** per PREFILLING slot (skipped on stalled
   ticks), exactly as before: fixed-shape ``(1, prefill_chunk)`` jitted
   chunks scatter into the slot's blocks and full pages register in the
   prefix index as they land;
7. **decode** — first each ACTIVE slot crossing a page boundary
   allocates its next block; when ``alloc`` returns ``None`` the
   scheduler **preempts-and-recomputes**: it evicts victims
   latest-admitted first (possibly the grower itself), dropping their
   pool state and re-queueing them as PENDING — a re-admitted victim
   re-prefills through the prefix cache (its already-registered pages
   make recompute cheap) and its greedy stream is bit-identical to an
   uninterrupted run (pinned by the parity suite).  A request evicted
   more than ``max_preemptions`` times retires terminal ``PREEMPTED``
   instead of livelocking.  Then ONE jitted decode advances every
   remaining ACTIVE slot as before.

Steps 5-7 are the data plane (a ``stall`` fault skips them); steps 1-4
are the control plane and always run — deadlines age through stalls.
Every terminal path goes through one retire helper that releases the
slot's blocks exactly once (shared prefix blocks just drop a
reference), so ``PagedKVCache.check_invariants()`` holds after every
tick — the chaos suite asserts it.

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

``routed_rows`` is the MoE assignments the call's held experts computed,
summed over its layers (valid rows only); the step returns it beside the
tokens and it is read after the same wait.
      serve.check          the invariant checks, when on
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.model import paged_decode_step, paged_prefill_step
from repro.serve.api import Request, RequestResult, RunStats, as_requests
from repro.serve.paged_cache import PagedKVCache, default_page_size
from repro.serve.resilience import (CANCELLED, OK, PREEMPTED, SHED, TIMEOUT,
                                    AdmissionPolicy, FaultPlan,
                                    QueueCapPolicy, queue_entries)

__all__ = ["PagedServeEngine", "Request", "RequestResult", "serve_steps"]

span = jax.profiler.TraceAnnotation          # see "Spans" above


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


@dataclasses.dataclass
class _Slot:
    req: int                        # index into the request list
    ids: List[int]                  # held pool blocks (shared first)
    remaining: int
    key: jax.Array                  # per-request sampling key stream
    filled: int                     # prompt tokens already in the pool
    registered: int                 # full pages entered in the prefix index
    seq: int                        # admission order (victim selection)


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
        # shed policies run queue-cap first (bound the deque), then the
        # user's pluggable policy — both see the same QueueEntry view
        self.policies: List[AdmissionPolicy] = []
        if max_queue is not None:
            self.policies.append(QueueCapPolicy(max_queue))
        if admission is not None:
            self.policies.append(admission)
        self.max_queue = max_queue
        self.cache = PagedKVCache(cfg, n_blocks=n_blocks, page=self.page,
                                  device=device)

        # chunks start at multiples of prefill_chunk past a page boundary
        # (prefix matches are page-aligned), so when the chunk size
        # divides the page no chunk ever crosses a block boundary and the
        # pool write collapses to one contiguous slice (aligned=True)
        self._decode, self._prefill = serve_steps(
            cfg, aligned=self.page % self.prefill_chunk == 0)

    def _prompt_blocks(self, s: int) -> int:
        """Blocks the prompt itself occupies (>= 1); decode rows are
        allocated lazily as the sequence crosses page boundaries."""
        return max(1, math.ceil(s / self.page))

    def _sample(self, logits: jax.Array, key, temperature: float):
        """logits (V,) -> int token (same math as ServeEngine._sample)."""
        if temperature <= 0.0:
            return int(jnp.argmax(logits, axis=-1))
        return int(jax.random.categorical(key, logits / temperature,
                                          axis=-1))

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

    # -- the scheduler -----------------------------------------------------

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
        reqs = as_requests(requests)
        for i, r in enumerate(reqs):
            s = r.prompt.shape[0]
            if s + r.n_steps > self.max_len:
                raise ValueError(
                    f"request {i} does not fit: prompt length {s} + n_steps "
                    f"{r.n_steps} = {s + r.n_steps} exceeds this engine's "
                    f"max_len of {self.max_len}")
            # fail fast instead of deadlocking: an oversized head request
            # would otherwise sit at the queue head forever waiting for
            # blocks the pool can never hold at once
            need = math.ceil((s + r.n_steps) / self.page)
            if need > self.cache.capacity:
                raise ValueError(
                    f"request {i} needs {need} blocks "
                    f"(prompt {s} + n_steps {r.n_steps} = {s + r.n_steps} "
                    f"tokens at page size {self.page}) but the pool's "
                    f"capacity is {self.cache.capacity} blocks "
                    f"(n_blocks={self.cache.n_blocks} minus the null "
                    f"block); construct the engine with n_blocks >= "
                    f"{need + 1} or shorten the request")

        checking = self.check_invariants or fault_plan is not None
        root = jax.random.PRNGKey(seed)
        results: List[Optional[RequestResult]] = [None] * len(reqs)
        out_tokens: List[List[int]] = [[] for _ in reqs]
        emit_times: List[List[float]] = [[] for _ in reqs]
        admitted_at = [-1] * len(reqs)
        admit_time = [0.0] * len(reqs)
        prefix_blocks = [0] * len(reqs)
        preempt_count = [0] * len(reqs)
        # FIFO by (arrival, submission order); deque: admission pops the
        # head O(1); preempted requests re-insert at their sorted spot
        queue = collections.deque(
            sorted(range(len(reqs)), key=lambda i: (reqs[i].arrival, i)))

        B, NB = self.max_batch, self.nb_table
        slots: List[Optional[_Slot]] = [None] * B
        tables = np.zeros((B, NB), np.int32)          # null block everywhere
        lens = np.zeros((B,), np.int32)               # 0 while prefilling
        pend = np.zeros((B,), np.int32)
        pools = self.cache.pools
        seized: List[Tuple[int, List[int]]] = []      # (release_tick, ids)

        tick = 0
        seq_counter = 0
        decode_steps = 0
        prefill_chunks = 0
        blocks_reused = 0
        blocks_needed = 0
        n_shed = n_timeout = n_cancel = n_preempt = n_stalled = 0
        occupancy: List[float] = []

        def emit(rid: int, tok: int) -> None:
            out_tokens[rid].append(tok)
            emit_times[rid].append(time.perf_counter())

        def finish(rid: int, status: str, detail: str = "") -> None:
            results[rid] = RequestResult(
                tokens=np.asarray(out_tokens[rid], np.int32),
                prompt_len=reqs[rid].prompt.shape[0],
                arrival=reqs[rid].arrival, admitted=admitted_at[rid],
                finished=tick, emit_times=emit_times[rid],
                admit_time=admit_time[rid],
                prefix_blocks=prefix_blocks[rid], status=status,
                detail=detail, preemptions=preempt_count[rid])

        def clear_slot(si: int) -> None:
            self.cache.free(slots[si].ids)
            slots[si] = None
            tables[si] = 0
            lens[si] = 0
            pend[si] = 0

        def retire(si: int, status: str = OK, detail: str = "") -> None:
            rid = slots[si].req
            clear_slot(si)
            finish(rid, status, detail)

        def drop_queued(rids, status: str, detail_fn) -> None:
            nonlocal queue
            dropped = set(rids)
            if not dropped:
                return
            queue = collections.deque(
                r for r in queue if r not in dropped)
            for rid in rids:
                finish(rid, status, detail_fn(rid))

        def preempt(si: int, why: str) -> None:
            """Evict slot ``si``: drop its pool state and either re-queue
            it as PENDING for recompute or, past the preemption budget,
            retire it terminal PREEMPTED."""
            nonlocal n_preempt
            slot = slots[si]
            rid = slot.req
            clear_slot(si)
            preempt_count[rid] += 1
            n_preempt += 1
            if preempt_count[rid] > self.max_preemptions:
                finish(rid, PREEMPTED,
                       f"evicted {preempt_count[rid]} times "
                       f"(max_preemptions={self.max_preemptions}); last "
                       f"eviction at tick {tick}: {why}")
                return
            # recompute: discard emitted tokens and re-admit through the
            # prefix cache — the greedy re-run is bit-identical, and the
            # request's registered pages make the re-prefill cheap
            out_tokens[rid].clear()
            emit_times[rid].clear()
            admitted_at[rid] = -1
            admit_time[rid] = 0.0
            prefix_blocks[rid] = 0
            key = (reqs[rid].arrival, rid)
            pos = 0
            for pos, q in enumerate(queue):           # sorted re-insert
                if (reqs[q].arrival, q) > key:
                    break
            else:
                pos = len(queue)
            queue.insert(pos, rid)

        def victims_latest_first() -> List[int]:
            held = [(slots[si].seq, si) for si in range(B)
                    if slots[si] is not None]
            return [si for _, si in sorted(held, reverse=True)]

        while queue or any(s is not None for s in slots):
            if max_ticks is not None and tick >= max_ticks:
                raise RuntimeError(
                    f"scheduler exceeded max_ticks={max_ticks} with "
                    f"{len(queue)} queued and "
                    f"{sum(s is not None for s in slots)} in-flight "
                    "requests — deadlock canary tripped")
            with span("serve.tick", tick=tick, queued=len(queue),
                      busy=B - slots.count(None)):
                stalled = False
                with span("serve.control") as sp:
                    counts0 = (n_cancel, n_timeout, n_shed)
                    # 1. faults: release expired seizures, then seize for
                    # faults firing now (seizing is a real alloc, so
                    # conservation holds)
                    if fault_plan is not None:
                        keep = []
                        for release, ids in seized:
                            if release <= tick:
                                self.cache.free(ids)
                            else:
                                keep.append((release, ids))
                        seized = keep
                        for f in fault_plan.seizures(tick):
                            k = self.cache.free_blocks if f.n is None \
                                else min(f.n, self.cache.free_blocks)
                            ids = self.cache.alloc(k) or []
                            if ids:
                                seized.append((tick + f.duration, ids))
                        stalled = fault_plan.stalled(tick)
                        if stalled:
                            n_stalled += 1

                    # 2. cancellations, then 3. timeouts — queued or
                    # in-flight, partial tokens kept, blocks released
                    # refcount-exactly
                    cancelled = [rid for rid in queue
                                 if reqs[rid].cancel_at is not None
                                 and tick >= reqs[rid].cancel_at]
                    drop_queued(cancelled, CANCELLED,
                                lambda rid: f"cancelled at tick "
                                            f"{reqs[rid].cancel_at} while "
                                            "queued")
                    n_cancel += len(cancelled)
                    for si in range(B):
                        slot = slots[si]
                        if slot is None:
                            continue
                        r = reqs[slot.req]
                        if r.cancel_at is not None and tick >= r.cancel_at:
                            retire(si, CANCELLED,
                                   f"cancelled at tick {r.cancel_at} in "
                                   "flight")
                            n_cancel += 1
                    timed_out = [rid for rid in queue
                                 if reqs[rid].deadline is not None
                                 and tick > reqs[rid].deadline]
                    drop_queued(timed_out, TIMEOUT,
                                lambda rid: f"deadline {reqs[rid].deadline} "
                                            "passed while queued")
                    n_timeout += len(timed_out)
                    for si in range(B):
                        slot = slots[si]
                        if slot is None:
                            continue
                        r = reqs[slot.req]
                        if r.deadline is not None and tick > r.deadline:
                            retire(si, TIMEOUT,
                                   f"deadline {r.deadline} passed with "
                                   f"{slot.remaining} tokens still to emit")
                            n_timeout += 1

                    # 3b. fault-forced preemptions (same victim rule as
                    # organic)
                    if fault_plan is not None:
                        for si in victims_latest_first()[
                                :fault_plan.forced_preemptions(tick)]:
                            preempt(si, "forced by fault plan")

                    # 4. shed: queue-cap bound first, then the pluggable
                    # policy
                    if self.policies:
                        for policy in self.policies:
                            waiting = [rid for rid in queue
                                       if reqs[rid].arrival <= tick]
                            if not waiting:
                                break
                            entries = queue_entries(tick, waiting, reqs,
                                                    self.prefill_chunk)
                            verdicts = dict(policy.shed(tick, entries))
                            drop_queued(list(verdicts), SHED,
                                        verdicts.__getitem__)
                            n_shed += len(verdicts)
                    sp.set_metadata(cancelled=n_cancel - counts0[0],
                                    timed_out=n_timeout - counts0[1],
                                    shed=n_shed - counts0[2])

                # 5. admit: FIFO while a slot and the PROMPT reservation
                # fit (decode blocks grow lazily); a stalled tick admits
                # nothing
                with span("serve.admit") as sp:
                    counts0 = (seq_counter, blocks_reused)
                    while not stalled and queue \
                            and reqs[queue[0]].arrival <= tick:
                        free_slots = [i for i, s in enumerate(slots)
                                      if s is None]
                        if not free_slots:
                            break
                        rid = queue[0]
                        r = reqs[rid]
                        s = r.prompt.shape[0]
                        need = self._prompt_blocks(s)
                        matched: List[int] = []
                        if self.prefix_cache:
                            # cap: >= 1 suffix token must prefill
                            # (first-token logits), which also keeps every
                            # later write past the shared pages — see the
                            # module docstring
                            matched = self.cache.match_prefix(
                                r.prompt)[:(s - 1) // self.page]
                            self.cache.acquire(matched)
                        ids = self.cache.alloc(need - len(matched))
                        if ids is None:
                            if matched:
                                self.cache.free(matched)  # drop hold, wait
                            break                 # wait for retirements
                        queue.popleft()
                        si = free_slots[0]
                        admitted_at[rid] = tick
                        admit_time[rid] = time.perf_counter()
                        prefix_blocks[rid] = len(matched)
                        blocks_reused += len(matched)
                        blocks_needed += (s - 1) // self.page
                        slots[si] = _Slot(req=rid, ids=matched + ids,
                                          remaining=r.n_steps,
                                          key=jax.random.fold_in(root, rid),
                                          filled=len(matched) * self.page,
                                          registered=len(matched),
                                          seq=seq_counter)
                        seq_counter += 1
                        tables[si, :] = 0
                        tables[si, :len(slots[si].ids)] = slots[si].ids
                        lens[si] = 0              # ACTIVE only after prefill
                    sp.set_metadata(admitted=seq_counter - counts0[0],
                                    prefix_blocks=blocks_reused - counts0[1])

                occupancy.append(self.cache.occupancy())

                # 6. prefill: one chunk per PREFILLING slot, then decode
                # below — long prompts stall a tick by at most one chunk of
                # compute
                C = self.prefill_chunk
                for si in range(B):
                    slot = slots[si]
                    if stalled or slot is None or lens[si] > 0:
                        continue
                    r = reqs[slot.req]
                    s = r.prompt.shape[0]
                    pos = slot.filled
                    nv = min(C, s - pos)
                    with span("serve.prefill", req=slot.req, slot=si,
                              start=pos, n_valid=nv) as sp:
                        toks = np.zeros((1, C), np.int32)
                        toks[0, :nv] = r.prompt[pos:pos + nv]
                        # jnp.array (not asarray): don't alias scheduler
                        # state the async dispatch would race with (same
                        # rationale as decode)
                        logits, greedy, pools, *counters = self._prefill(
                            self.params, pools, jnp.array(toks),
                            jnp.array(tables[si:si + 1]),
                            jnp.array([pos], np.int32),
                            jnp.array([nv], np.int32))
                        with span("serve.prefill.wait"):
                            jax.block_until_ready((logits, greedy, pools,
                                                   counters))
                        if counters:
                            sp.set_metadata(**{k: int(v) for k, v in
                                               jax.device_get(counters[0])
                                               .items()})
                        prefill_chunks += 1
                        slot.filled = pos + nv
                        if self.prefix_cache:
                            full = slot.filled // self.page
                            if full > slot.registered:
                                self.cache.register_prefix(
                                    r.prompt[:full * self.page],
                                    slot.ids[:full])
                                slot.registered = full
                        if slot.filled == s:        # prefill done -> ACTIVE
                            if temperature <= 0.0:
                                tok = int(greedy[0])
                            else:
                                slot.key, sub = jax.random.split(slot.key)
                                tok = self._sample(logits[0, -1], sub,
                                                   temperature)
                            lens[si] = s
                            pend[si] = tok
                            emit(slot.req, tok)
                            slot.remaining -= 1
                            if slot.remaining == 0:
                                retire(si)

                # 7a. grow: each ACTIVE slot writing into a fresh page this
                # tick allocates its next block; exhaustion preempts
                # victims latest-admitted first (possibly the grower
                # itself) instead of deadlocking the tick
                with span("serve.grow") as sp:
                    grown, preempts0 = 0, n_preempt
                    for si in range(B):
                        if stalled:
                            break
                        slot = slots[si]
                        if slot is None or lens[si] == 0:
                            continue
                        if int(lens[si]) < len(slot.ids) * self.page:
                            continue                # page not full yet
                        got = self.cache.alloc(1)
                        if got is None:
                            for vi in victims_latest_first():
                                victim_is_self = vi == si
                                preempt(vi, "pool exhausted growing slot "
                                            f"{si} at length {int(lens[si])}")
                                if victim_is_self:
                                    break
                                got = self.cache.alloc(1)
                                if got is not None:
                                    break
                        if got is None or slots[si] is None:
                            continue                # grower was evicted
                        slot.ids.append(got[0])
                        tables[si, len(slot.ids) - 1] = got[0]
                        grown += 1
                    sp.set_metadata(grown=grown,
                                    preempted=n_preempt - preempts0)

                active = [] if stalled else \
                    [i for i, sl in enumerate(slots)
                     if sl is not None and lens[i] > 0]
                # every slot outside ``active`` sits at length 0, so the
                # rows the step attends, sum(lens[active] + 1), is one sum;
                # the kernel fetches ceil((lens + 1) / page) pages a slot
                # and a layer, every slot at least one
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
                        dec_tables = tables.copy()
                        for si in range(B):
                            if slots[si] is not None and lens[si] == 0:
                                dec_tables[si] = 0  # scatter to null block
                        logits, greedy, pools, *counters = self._decode(
                            self.params, pools, jnp.array(pend[:, None]),
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
                        if counters:
                            sp.set_metadata(**{k: int(v) for k, v in
                                               jax.device_get(counters[0])
                                               .items()})
                        decode_steps += 1
                        lens[active] += 1
                        keys = None
                        if temperature > 0.0:
                            keys = []
                            active_set = set(active)
                            for si in range(B):
                                if si in active_set:
                                    slots[si].key, sub = jax.random.split(
                                        slots[si].key)
                                    keys.append(sub)
                                else:
                                    keys.append(root)  # idle: discarded
                        toks = self._sample_tick(logits[:, -1], greedy,
                                                 keys, temperature)
                        for si in active:
                            slot = slots[si]
                            tok = int(toks[si])
                            pend[si] = tok
                            emit(slot.req, tok)
                            slot.remaining -= 1
                            if slot.remaining == 0:
                                retire(si)
                tick += 1
                if checking:
                    with span("serve.check"):
                        self.cache.check_invariants()
                        self._assert_refcount_exact(slots, seized)

        # the run can end inside a seizure window (every request already
        # terminal); hand the fault-held blocks back so the pool drains
        for _, ids in seized:
            self.cache.free(ids)
        seized = []
        if checking:
            self.cache.check_invariants()
            self._assert_refcount_exact(slots, seized)

        self.cache.pools = pools
        n_ok = sum(1 for r in results if r is not None and r.status == OK)
        stats = RunStats(
            requests=len(reqs),
            tokens=sum(len(t) for t in out_tokens),
            ticks=tick,
            decode_steps=decode_steps,
            prefill_chunks=prefill_chunks,
            prefix_blocks_reused=blocks_reused,
            prefix_blocks_needed=blocks_needed,
            prefix_hit_rate=(blocks_reused / blocks_needed
                             if blocks_needed else 0.0),
            occupancy_mean=float(np.mean(occupancy)) if occupancy else 0.0,
            occupancy_max=float(np.max(occupancy)) if occupancy else 0.0,
            completed=n_ok, shed=n_shed, timeouts=n_timeout,
            cancelled=n_cancel, preemptions=n_preempt,
            stalled_ticks=n_stalled,
        )
        return [r for r in results if r is not None], stats

    def _assert_refcount_exact(self, slots, seized) -> None:
        """Every reference the pool counts must be owned by exactly one
        holder the scheduler knows: a slot's block list or a fault
        seizure.  (Parked prefix blocks sit at refcount 0 and are the
        cache's own business — ``check_invariants`` covers them.)"""
        expected: Dict[int, int] = collections.Counter()
        for slot in slots:
            if slot is not None:
                expected.update(slot.ids)
        for _, ids in seized:
            expected.update(ids)
        for b in range(1, self.cache.n_blocks):
            if self.cache.ref_count(b) != expected.get(b, 0):
                raise AssertionError(
                    f"refcount drift on block {b}: cache counts "
                    f"{self.cache.ref_count(b)} but the scheduler holds "
                    f"{expected.get(b, 0)} references")

    def generate(self, tokens: np.ndarray, *, n_steps: int = 32,
                 temperature: float = 0.0, seed: int = 0) -> np.ndarray:
        """Batch convenience mirroring ``ServeEngine.generate``: serve the
        (B, S) prompts (all arriving at tick 0) and return (B, n_steps)."""
        tokens = np.asarray(tokens, np.int32)
        reqs = [Request(prompt=row, n_steps=n_steps) for row in tokens]
        results, _ = self.run(reqs, temperature=temperature, seed=seed)
        return np.stack([r.tokens for r in results])
