"""The paged engine's host scheduler: the one place a tick is written.

Scheduler states (per request)::

    PENDING --admit--> PREFILLING --complete--> ACTIVE --retire--> DONE
      (waits for a slot  (one prompt chunk        (decodes one        ^
       + prompt blocks)   per tick)                token per tick)    |
         ^                    |                        |              |
         |                    +-----<--preempt--<------+              |
         +--- re-queued as PENDING (tokens discarded, recompute) -----+

Each tick runs the steps below, exposed as phases.  The
:class:`~repro.serve.paged_engine.PagedServeEngine` wraps them in its
``serve.*`` spans and supplies the device calls; a host-only replica
drives the same phases with token 0 and no device.

``control()`` — the control plane, which always runs:

1. **faults** — with a :class:`~repro.serve.resilience.FaultPlan`
   active: release expired block seizures, seize free blocks for
   ``exhaust`` faults firing now (a real ``alloc``, so conservation
   holds), note stall windows;
2. **cancel / timeout** — ``cancel_at <= tick`` retires ``CANCELLED``,
   then ``deadline < tick`` retires ``TIMEOUT``, queued or in flight
   (partial tokens kept, blocks released refcount-exactly);
3. **forced preemptions** — ``preempt`` faults evict victims
   (latest-admitted first, the rule organic exhaustion uses);
4. **shed** — the shed policies
   (:func:`~repro.serve.resilience.shed_policies`) reject waiting
   requests with a reason (terminal ``SHED``).

The data plane, which a stalled tick skips (deadlines still age):

5. ``admit()`` — pending requests (arrival <= tick, FIFO) claim a free
   slot plus their **prompt** blocks only (``ceil(s / page)``; decode
   blocks are allocated lazily, which is what makes mid-flight
   exhaustion and preemption reachable).  With ``prefix_cache`` on the
   longest page-aligned cached prefix is taken from the pool (refcount
   bumps, no compute), capped at ``(s - 1) // page`` pages so the
   first-token logits always compute and every later write lands past
   the shared pages.  When the pool or the slots run out the queue
   waits: admission is the backpressure point;
6. ``prefill_work()`` / ``prefilled()`` — one ``prefill_chunk`` of each
   PREFILLING slot; full pages register in the prefix index as they
   land, and the last chunk emits the first token;
7. ``grow()`` (7a) — each ACTIVE slot crossing a page boundary
   allocates its next block; when ``alloc`` returns ``None`` victims are
   preempted latest-admitted first (possibly the grower itself): their
   blocks are dropped and they re-queue as PENDING, to re-prefill
   through the prefix cache.  A request evicted more than
   ``max_preemptions`` times retires terminal ``PREEMPTED`` instead of
   livelocking; ``decode_batch()`` / ``decoded()`` (7b) — one decode
   step for every ACTIVE slot.

``end_tick()`` closes the tick.  Every terminal path goes through one
retire that frees the slot's blocks exactly once (shared prefix blocks
drop a reference), so ``check()`` — the allocator's invariants plus the
refcount-exact ownership below — holds after every tick.

The scheduler owns the arrays the device calls read: ``tables`` (B, NB)
block ids (idle rows at the null block 0), ``lens`` (B,) cached tokens
(0 while a slot prefills, so the decode step masks it) and ``pend``
(B,) the next token to feed each slot.  It imports no jax.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from repro.serve.api import Request, RequestResult, RunStats, as_requests
from repro.serve.resilience import (CANCELLED, OK, PREEMPTED, SHED, TIMEOUT,
                                    AdmissionPolicy, FaultPlan, queue_entries)

__all__ = ["Scheduler"]


@dataclasses.dataclass
class _Slot:
    req: int                        # index into the request list
    ids: List[int]                  # held pool blocks (shared first)
    remaining: int
    filled: int                     # prompt tokens already in the pool
    registered: int                 # full pages entered in the prefix index
    seq: int                        # admission order (victim selection)


class Scheduler:
    """One run's queue, slots and counters over ``allocator`` (anything
    with the :class:`~repro.serve.blocks.BlockAllocator` interface).

    Validates every request up front: one that could never fit
    ``max_len`` or the whole pool raises ``ValueError`` naming its need.
    ``max_ticks`` is the deadlock canary: :meth:`running` raises
    ``RuntimeError`` once the run passes it.  ``checking`` (on under a
    ``fault_plan`` or with ``check_invariants``) tells the caller to
    call :meth:`check` after every tick.
    """

    def __init__(self, requests: Sequence[Request], allocator, *,
                 max_len: int, max_batch: int, page: int,
                 prefill_chunk: int, prefix_cache: bool,
                 policies: Sequence[AdmissionPolicy] = (),
                 max_preemptions: int = 8,
                 fault_plan: Optional[FaultPlan] = None,
                 max_ticks: Optional[int] = None,
                 check_invariants: bool = False):
        self.reqs = reqs = as_requests(requests)
        self.alloc = allocator
        for i, r in enumerate(reqs):
            s = r.prompt.shape[0]
            if s + r.n_steps > max_len:
                raise ValueError(
                    f"request {i} does not fit: prompt length {s} + n_steps "
                    f"{r.n_steps} = {s + r.n_steps} exceeds this engine's "
                    f"max_len of {max_len}")
            # fail fast instead of deadlocking: an oversized head request
            # would otherwise sit at the queue head forever waiting for
            # blocks the pool can never hold at once
            need = math.ceil((s + r.n_steps) / page)
            if need > allocator.capacity:
                raise ValueError(
                    f"request {i} needs {need} blocks "
                    f"(prompt {s} + n_steps {r.n_steps} = {s + r.n_steps} "
                    f"tokens at page size {page}) but the pool's "
                    f"capacity is {allocator.capacity} blocks "
                    f"(n_blocks={allocator.n_blocks} minus the null "
                    f"block); construct the engine with n_blocks >= "
                    f"{need + 1} or shorten the request")
        self.page = page
        self.prefill_chunk = prefill_chunk
        self.prefix_cache = prefix_cache
        self.policies = list(policies)
        self.max_preemptions = max_preemptions
        self.fault_plan = fault_plan
        self.max_ticks = max_ticks
        self.checking = check_invariants or fault_plan is not None

        # FIFO by (arrival, submission order); deque: admission pops the
        # head O(1); preempted requests re-insert at their sorted spot
        self.queue = collections.deque(
            sorted(range(len(reqs)), key=lambda i: (reqs[i].arrival, i)))
        self.slots: List[Optional[_Slot]] = [None] * max_batch
        nb_table = math.ceil(max_len / page)
        self.tables = np.zeros((max_batch, nb_table), np.int32)
        self.lens = np.zeros((max_batch,), np.int32)
        self.pend = np.zeros((max_batch,), np.int32)
        self.seized: List[Tuple[int, List[int]]] = []   # (release_tick, ids)
        self.tick = 0
        self.stalled = False

        n = len(reqs)
        self._results: List[Optional[RequestResult]] = [None] * n
        self.out_tokens: List[List[int]] = [[] for _ in range(n)]
        self.emit_times: List[List[float]] = [[] for _ in range(n)]
        self.admitted_at = [-1] * n
        self.admit_time = [0.0] * n
        self.prefix_blocks = [0] * n
        self.preempt_count = [0] * n
        self.seq = 0
        self.decode_steps = self.prefill_chunks = 0
        self.blocks_reused = self.blocks_needed = 0
        self.n_shed = self.n_timeout = self.n_cancel = 0
        self.n_preempt = self.n_stalled = 0
        self.occupancy: List[float] = []

    # -- the loop ------------------------------------------------------------

    @property
    def busy(self) -> int:
        """Slots held."""
        return len(self.slots) - self.slots.count(None)

    def running(self) -> bool:
        """True while a request is queued or in flight."""
        if not self.queue and not self.busy:
            return False
        if self.max_ticks is not None and self.tick >= self.max_ticks:
            raise RuntimeError(
                f"scheduler exceeded max_ticks={self.max_ticks} with "
                f"{len(self.queue)} queued and {self.busy} in-flight "
                "requests — deadlock canary tripped")
        return True

    def control(self) -> Dict[str, int]:
        """Steps 1-4; returns the tick's ``cancelled``, ``timed_out`` and
        ``shed`` counts."""
        tick, plan, reqs = self.tick, self.fault_plan, self.reqs
        counts0 = (self.n_cancel, self.n_timeout, self.n_shed)
        self.stalled = False
        if plan is not None:
            keep = []
            for release, ids in self.seized:
                if release <= tick:
                    self.alloc.free(ids)
                else:
                    keep.append((release, ids))
            self.seized = keep
            for f in plan.seizures(tick):
                free = self.alloc.free_blocks
                ids = self.alloc.alloc(free if f.n is None
                                       else min(f.n, free)) or []
                if ids:
                    self.seized.append((tick + f.duration, ids))
            self.stalled = plan.stalled(tick)
            self.n_stalled += self.stalled

        cancelled = [rid for rid in self.queue
                     if reqs[rid].cancel_at is not None
                     and tick >= reqs[rid].cancel_at]
        self._drop_queued(cancelled, CANCELLED,
                          lambda rid: f"cancelled at tick "
                                      f"{reqs[rid].cancel_at} while queued")
        self.n_cancel += len(cancelled)
        for si, slot in enumerate(self.slots):
            if slot is None:
                continue
            r = reqs[slot.req]
            if r.cancel_at is not None and tick >= r.cancel_at:
                self._retire(si, CANCELLED,
                             f"cancelled at tick {r.cancel_at} in flight")
                self.n_cancel += 1
        timed_out = [rid for rid in self.queue
                     if reqs[rid].deadline is not None
                     and tick > reqs[rid].deadline]
        self._drop_queued(timed_out, TIMEOUT,
                          lambda rid: f"deadline {reqs[rid].deadline} "
                                      "passed while queued")
        self.n_timeout += len(timed_out)
        for si, slot in enumerate(self.slots):
            if slot is None:
                continue
            r = reqs[slot.req]
            if r.deadline is not None and tick > r.deadline:
                self._retire(si, TIMEOUT,
                             f"deadline {r.deadline} passed with "
                             f"{slot.remaining} tokens still to emit")
                self.n_timeout += 1

        if plan is not None:
            for si in self._victims()[:plan.forced_preemptions(tick)]:
                self._preempt(si, "forced by fault plan")

        for policy in self.policies:
            waiting = [rid for rid in self.queue if reqs[rid].arrival <= tick]
            if not waiting:
                break
            verdicts = dict(policy.shed(tick, queue_entries(
                tick, waiting, reqs, self.prefill_chunk)))
            self._drop_queued(list(verdicts), SHED, verdicts.__getitem__)
            self.n_shed += len(verdicts)
        return dict(cancelled=self.n_cancel - counts0[0],
                    timed_out=self.n_timeout - counts0[1],
                    shed=self.n_shed - counts0[2])

    def admit(self) -> Tuple[Dict[str, int], List[int]]:
        """Step 5; returns the tick's counts (``admitted`` requests and the
        ``prefix_blocks`` they took from the cache) and the admitted
        requests in admission order."""
        reqs, page = self.reqs, self.page
        counts0 = (self.seq, self.blocks_reused)
        admitted: List[int] = []
        while not self.stalled and self.queue \
                and reqs[self.queue[0]].arrival <= self.tick:
            free_slots = [i for i, s in enumerate(self.slots) if s is None]
            if not free_slots:
                break
            rid = self.queue[0]
            r = reqs[rid]
            s = r.prompt.shape[0]
            need = max(1, math.ceil(s / page))
            matched: List[int] = []
            if self.prefix_cache:
                # cap: >= 1 suffix token must prefill (first-token logits)
                matched = self.alloc.match_prefix(r.prompt)[:(s - 1) // page]
                self.alloc.acquire(matched)
            ids = self.alloc.alloc(need - len(matched))
            if ids is None:
                if matched:
                    self.alloc.free(matched)        # drop the hold, wait
                break                               # wait for retirements
            self.queue.popleft()
            admitted.append(rid)
            si = free_slots[0]
            self.admitted_at[rid] = self.tick
            self.admit_time[rid] = time.perf_counter()
            self.prefix_blocks[rid] = len(matched)
            self.blocks_reused += len(matched)
            self.blocks_needed += (s - 1) // page
            slot = self.slots[si] = _Slot(
                req=rid, ids=matched + ids, remaining=r.n_steps,
                filled=len(matched) * page, registered=len(matched),
                seq=self.seq)
            self.seq += 1
            self.tables[si, :] = 0
            self.tables[si, :len(slot.ids)] = slot.ids
            self.lens[si] = 0                       # ACTIVE only after prefill
        self.occupancy.append(self.alloc.occupancy())
        return dict(admitted=self.seq - counts0[0],
                    prefix_blocks=self.blocks_reused - counts0[1]), admitted

    def prefill_work(self) -> Iterator[Tuple[int, int, int, int]]:
        """Step 6: ``(slot, request, start, n_valid)`` of each PREFILLING
        slot's next chunk, in slot order.  Call :meth:`prefilled` after
        each before taking the next."""
        if self.stalled:
            return
        for si in range(len(self.slots)):
            slot = self.slots[si]
            if slot is None or self.lens[si] > 0:
                continue
            s = self.reqs[slot.req].prompt.shape[0]
            yield (si, slot.req, slot.filled,
                   min(self.prefill_chunk, s - slot.filled))

    def prefilled(self, si: int, tok: Optional[int]) -> None:
        """Slot ``si``'s chunk has landed; ``tok`` is the first token when
        it was the prompt's last chunk (else unused)."""
        slot = self.slots[si]
        prompt = self.reqs[slot.req].prompt
        s = prompt.shape[0]
        self.prefill_chunks += 1
        slot.filled = min(s, slot.filled + self.prefill_chunk)
        if self.prefix_cache:
            full = slot.filled // self.page
            if full > slot.registered:
                self.alloc.register_prefix(prompt[:full * self.page],
                                           slot.ids[:full])
                slot.registered = full
        if slot.filled == s:                        # prefill done -> ACTIVE
            self.lens[si] = s
            self._emit(si, tok)

    def grow(self) -> Dict[str, int]:
        """Step 7a: the next block for each ACTIVE slot whose pages are
        full; returns the tick's ``grown`` slots and ``preempted``
        victims."""
        if self.stalled:
            return dict(grown=0, preempted=0)
        grown, preempts0 = 0, self.n_preempt
        for si in range(len(self.slots)):
            slot = self.slots[si]
            if slot is None or self.lens[si] == 0:
                continue
            if int(self.lens[si]) < len(slot.ids) * self.page:
                continue                            # page not full yet
            got = self.alloc.alloc(1)
            if got is None:
                for vi in self._victims():
                    self._preempt(vi, "pool exhausted growing slot "
                                      f"{si} at length {int(self.lens[si])}")
                    if vi == si:
                        break
                    got = self.alloc.alloc(1)
                    if got is not None:
                        break
            if got is None or self.slots[si] is None:
                continue                            # grower was evicted
            slot.ids.append(got[0])
            self.tables[si, len(slot.ids) - 1] = got[0]
            grown += 1
        return dict(grown=grown, preempted=self.n_preempt - preempts0)

    def decode_batch(self) -> List[int]:
        """Step 7b: the ACTIVE slots this tick's decode step advances."""
        if self.stalled:
            return []
        return [i for i, sl in enumerate(self.slots)
                if sl is not None and self.lens[i] > 0]

    def decoded(self, active: Sequence[int], toks) -> None:
        """The decode step over ``active`` returned ``toks`` (B,), indexed
        by slot."""
        self.decode_steps += 1
        self.lens[active] += 1
        for si in active:
            self._emit(si, int(toks[si]))

    def end_tick(self) -> None:
        self.tick += 1
        if not self.queue and not self.busy:
            # the run can end inside a seizure window (every request
            # already terminal); hand the fault-held blocks back so the
            # pool drains
            for _, ids in self.seized:
                self.alloc.free(ids)
            self.seized = []

    def check(self) -> None:
        """The allocator's invariants, then refcount-exact ownership: every
        reference the pool counts is held by exactly one slot's block
        list or fault seizure.  (Parked prefix blocks sit at refcount 0
        and are the allocator's own business.)"""
        self.alloc.check_invariants()
        expected: Dict[int, int] = collections.Counter()
        for slot in self.slots:
            if slot is not None:
                expected.update(slot.ids)
        for _, ids in self.seized:
            expected.update(ids)
        for b in range(1, self.alloc.n_blocks):
            if self.alloc.ref_count(b) != expected.get(b, 0):
                raise AssertionError(
                    f"refcount drift on block {b}: cache counts "
                    f"{self.alloc.ref_count(b)} but the scheduler holds "
                    f"{expected.get(b, 0)} references")

    def results(self) -> List[RequestResult]:
        """Every request's result, in input order."""
        return [r for r in self._results if r is not None]

    def stats(self) -> RunStats:
        occ = self.occupancy
        return RunStats(
            requests=len(self.reqs),
            tokens=sum(len(t) for t in self.out_tokens),
            ticks=self.tick,
            decode_steps=self.decode_steps,
            prefill_chunks=self.prefill_chunks,
            prefix_blocks_reused=self.blocks_reused,
            prefix_blocks_needed=self.blocks_needed,
            prefix_hit_rate=(self.blocks_reused / self.blocks_needed
                             if self.blocks_needed else 0.0),
            occupancy_mean=float(np.mean(occ)) if occ else 0.0,
            occupancy_max=float(np.max(occ)) if occ else 0.0,
            completed=sum(r is not None and r.status == OK
                          for r in self._results),
            shed=self.n_shed, timeouts=self.n_timeout,
            cancelled=self.n_cancel, preemptions=self.n_preempt,
            stalled_ticks=self.n_stalled)

    # -- slot and queue bookkeeping ------------------------------------------

    def _emit(self, si: int, tok: int) -> None:
        slot = self.slots[si]
        self.pend[si] = tok
        self.out_tokens[slot.req].append(tok)
        self.emit_times[slot.req].append(time.perf_counter())
        slot.remaining -= 1
        if slot.remaining == 0:
            self._retire(si)

    def _finish(self, rid: int, status: str, detail: str = "") -> None:
        self._results[rid] = RequestResult(
            tokens=np.asarray(self.out_tokens[rid], np.int32),
            prompt_len=self.reqs[rid].prompt.shape[0],
            arrival=self.reqs[rid].arrival, admitted=self.admitted_at[rid],
            finished=self.tick, emit_times=self.emit_times[rid],
            admit_time=self.admit_time[rid],
            prefix_blocks=self.prefix_blocks[rid], status=status,
            detail=detail, preemptions=self.preempt_count[rid])

    def _clear(self, si: int) -> int:
        """Free slot ``si``'s blocks and empty it; returns its request."""
        rid = self.slots[si].req
        self.alloc.free(self.slots[si].ids)
        self.slots[si] = None
        self.tables[si] = 0
        self.lens[si] = 0
        self.pend[si] = 0
        return rid

    def _retire(self, si: int, status: str = OK, detail: str = "") -> None:
        self._finish(self._clear(si), status, detail)

    def _drop_queued(self, rids: List[int], status: str, detail_fn) -> None:
        if not rids:
            return
        dropped = set(rids)
        self.queue = collections.deque(
            r for r in self.queue if r not in dropped)
        for rid in rids:
            self._finish(rid, status, detail_fn(rid))

    def _preempt(self, si: int, why: str) -> None:
        """Evict slot ``si``: drop its blocks and either re-queue it as
        PENDING for recompute or, past the preemption budget, retire it
        terminal PREEMPTED."""
        rid = self._clear(si)
        self.preempt_count[rid] += 1
        self.n_preempt += 1
        if self.preempt_count[rid] > self.max_preemptions:
            self._finish(rid, PREEMPTED,
                         f"evicted {self.preempt_count[rid]} times "
                         f"(max_preemptions={self.max_preemptions}); last "
                         f"eviction at tick {self.tick}: {why}")
            return
        # recompute: discard emitted tokens and re-admit through the
        # prefix cache — the greedy re-run is bit-identical, and the
        # request's registered pages make the re-prefill cheap
        self.out_tokens[rid].clear()
        self.emit_times[rid].clear()
        self.admitted_at[rid] = -1
        self.admit_time[rid] = 0.0
        self.prefix_blocks[rid] = 0
        key = (self.reqs[rid].arrival, rid)
        pos = len(self.queue)
        for i, q in enumerate(self.queue):          # sorted re-insert
            if (self.reqs[q].arrival, q) > key:
                pos = i
                break
        self.queue.insert(pos, rid)

    def _victims(self) -> List[int]:
        """Held slots, latest-admitted first."""
        held = [(slot.seq, si) for si, slot in enumerate(self.slots)
                if slot is not None]
        return [si for _, si in sorted(held, reverse=True)]
