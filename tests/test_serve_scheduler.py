"""The paged scheduler's phases, driven over a bare ``BlockAllocator``.

No model and no device: each test calls the phases in the order the
engine's data plane does (control, admit, one chunk per prefilling slot,
grow, decode, end of tick) and feeds back made-up tokens.
"""

import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.serve import (Fault, FaultPlan, OK, PREEMPTED, Request, Scheduler,
                         TIMEOUT)
from repro.serve.blocks import BlockAllocator


def _sched(specs, *, n_blocks, page=16, max_len=64, max_batch=2,
           prefix_cache=False, **kw):
    reqs = [Request(prompt=np.asarray(p, np.int32), n_steps=n, arrival=a,
                    **extra)
            for p, n, a, extra in specs]
    return Scheduler(reqs, BlockAllocator(n_blocks, page), max_len=max_len,
                     max_batch=max_batch, page=page, prefill_chunk=page,
                     prefix_cache=prefix_cache, **kw)


def _tick(sch, toks=None):
    """One tick through every phase; returns what each phase did."""
    did = dict(sch.control(), **sch.admit()[0])
    chunks = []
    for work in sch.prefill_work():
        chunks.append(work)
        sch.prefilled(work[0], 0 if toks is None else int(toks[work[0]]))
    did.update(sch.grow())
    active = sch.decode_batch()
    if active:
        sch.decoded(active, np.zeros(len(sch.slots), np.int32)
                    if toks is None else toks)
    sch.end_tick()
    return dict(did, chunks=chunks, active=active)


def test_stalled_tick_runs_only_the_control_plane():
    """Under a plan with all three fault kinds: seizures hold real blocks
    (the last one past the run's end, handed back when it ends), the
    forced preemption takes the latest admission, and a stalled tick
    admits, prefills, grows and decodes nothing while a deadline in
    flight still expires and frees a slot that stays empty."""
    plan = FaultPlan(faults=[Fault("exhaust", tick=2, n=2, duration=3),
                             Fault("preempt", tick=3, n=1),
                             Fault("stall", tick=4, duration=4),
                             Fault("exhaust", tick=9, n=1, duration=999)])
    sch = _sched([(np.arange(20), 10, 0, {}), (np.arange(8), 30, 0, {}),
                  (np.arange(8), 9, 1, dict(deadline=6)),
                  (np.arange(8) + 1, 4, 5, {})],
                 n_blocks=9, max_batch=3, fault_plan=plan)
    assert sch.checking
    log = []
    while sch.running():
        free = sch.alloc.free_blocks
        log.append(_tick(sch))
        sch.check()
        if sch.tick == 3:                  # after tick 2: two blocks seized
            assert [len(ids) for _, ids in sch.seized] == [2]
            assert sch.alloc.free_blocks <= free - 2
    assert sch.preempt_count == [0, 0, 1, 0]         # latest admitted: r2
    stalled = log[4:8]
    for did in stalled:
        assert (did["admitted"], did["grown"], did["preempted"]) == (0, 0, 0)
        assert did["chunks"] == [] and did["active"] == []
    assert [did["timed_out"] for did in stalled] == [0, 0, 0, 1]
    assert log[8]["admitted"] == 1 and log[8]["active"]
    res = sch.results()
    assert [r.status for r in res] == [OK, OK, TIMEOUT, OK]
    assert res[2].detail.startswith("deadline 6 passed with")
    assert res[3].admitted == 8
    st = sch.stats()
    assert (st.stalled_ticks, st.preemptions, st.timeouts) == (4, 1, 1)
    assert sch.tick < 9 + 999
    assert not sch.seized and sch.alloc.free_blocks == sch.alloc.capacity


def test_victims_go_latest_admitted_first_including_the_grower():
    """A full pool at a page boundary: slot 0 grows by evicting the
    latest admission (slot 2); slot 1 is then the latest left and
    evicts itself rather than slot 0."""
    sch = _sched([(np.arange(16) + i, 20, 0, {}) for i in range(3)],
                 n_blocks=4, max_batch=3)
    did = dict(sch.control(), **sch.admit()[0])
    assert did["admitted"] == 3 and sch.alloc.free_blocks == 0
    for si, *_ in sch.prefill_work():
        sch.prefilled(si, 7)
    assert list(sch.lens) == [16, 16, 16]
    assert sch.grow() == dict(grown=1, preempted=2)
    assert sch.slots[1] is None and sch.slots[2] is None
    assert len(sch.slots[0].ids) == 2
    assert list(sch.queue) == [1, 2]                 # re-queued, FIFO spot
    assert sch.preempt_count == [0, 1, 1]
    assert sch.out_tokens == [[7], [], []]           # recompute from scratch
    assert sch.decode_batch() == [0]
    sch.check()


def test_preemption_budget_retires_preempted():
    plan = FaultPlan(faults=[Fault("preempt", tick=1, n=1, every=2)])
    sch = _sched([(np.arange(8), 20, 0, {})], n_blocks=5, fault_plan=plan,
                 max_preemptions=2)
    while sch.running():
        _tick(sch)
        sch.check()
    r, = sch.results()
    assert r.status == PREEMPTED and r.preemptions == 3
    assert "max_preemptions=2" in r.detail


def test_prefix_admission_is_capped_below_the_last_page():
    """A prompt of three whole cached pages takes only two: at least one
    token must prefill for the first-token logits."""
    prompt = np.arange(48)
    sch = _sched([(prompt, 4, 0, {}), (prompt, 4, 6, {}),
                  (np.concatenate([prompt[:32], [1] * 9]), 4, 6, {})],
                 n_blocks=16, max_batch=2, prefix_cache=True)
    log = [_tick(sch) for _ in range(7)]
    assert [c[2:] for c in log[0]["chunks"] + log[1]["chunks"]
            + log[2]["chunks"]] == [(0, 16), (16, 16), (32, 16)]
    assert log[6]["admitted"] == 2 and log[6]["prefix_blocks"] == 4
    assert sch.prefix_blocks == [0, 2, 2]
    assert [c[1:] for c in log[6]["chunks"]] == [(1, 32, 16), (2, 32, 9)]
    while sch.running():
        _tick(sch)
        sch.check()
    st = sch.stats()
    assert st.prefix_blocks_reused == 4
    assert st.prefix_blocks_needed == 2 + 2 + 2


def test_invariants_hold_every_tick_of_a_seeded_soak():
    """200 ticks of mixed traffic on a pool too small for every slot's
    growth, with shared prefixes, deadlines, cancellations and a random
    fault plan: the allocator's invariants and refcount-exact ownership
    hold after every tick."""
    rng = np.random.default_rng(1234)
    page, max_len = 16, 96
    system = rng.integers(0, 500, 32)
    specs = []
    for i in range(60):
        s = int(rng.integers(4, 60))
        prompt = rng.integers(0, 500, s)
        if i % 3 == 0:
            prompt = np.concatenate([system, prompt])[:s + 16]
        s = len(prompt)
        extra = {}
        if i % 7 == 0:
            extra["deadline"] = int(i * 3 + rng.integers(5, 40))
        if i % 11 == 0:
            extra["cancel_at"] = int(i * 3 + rng.integers(1, 20))
        specs.append((prompt, int(rng.integers(1, max_len - s + 1)),
                      int(i * 3), extra))
    sch = _sched(specs, n_blocks=14, page=page, max_len=max_len, max_batch=4,
                 prefix_cache=True, max_preemptions=3,
                 fault_plan=FaultPlan.random(7, horizon=200))
    while sch.tick < 200:
        assert sch.running()
        _tick(sch, rng.integers(0, 500, 4).astype(np.int32))
        sch.check()
    st = sch.stats()
    assert st.preemptions > 0 and st.prefix_blocks_reused > 0
    assert st.timeouts + st.cancelled > 0 and st.completed > 0


def test_validation_names_the_need():
    with pytest.raises(ValueError, match="exceeds this engine's max_len"):
        _sched([(np.arange(60), 8, 0, {})], n_blocks=9)
    with pytest.raises(ValueError, match=r"needs 4 blocks .* n_blocks >= 5"):
        _sched([(np.arange(50), 10, 0, {})], n_blocks=3)
    sch = _sched([(np.arange(8), 4, 0, {})], n_blocks=3, max_ticks=2,
                 fault_plan=FaultPlan(faults=[Fault("stall", 0,
                                                    duration=9)]))
    _tick(sch), _tick(sch)
    with pytest.raises(RuntimeError, match="max_ticks=2"):
        sch.running()


def test_scheduler_imports_without_jax():
    """A host-only caller runs a request to completion over the
    scheduler and the block allocator without loading jax."""
    import repro.serve
    src = pathlib.Path(repro.serve.__file__).parents[2]
    code = textwrap.dedent("""
        import sys
        import numpy as np
        from repro.serve import BlockAllocator, Request, Scheduler
        sch = Scheduler([Request(prompt=np.arange(20, dtype=np.int32),
                                 n_steps=5)],
                        BlockAllocator(5, 16), max_len=32, max_batch=1,
                        page=16, prefill_chunk=16, prefix_cache=True)
        while sch.running():
            sch.control()
            sch.admit()
            for si, *_ in sch.prefill_work():
                sch.prefilled(si, 1)
            sch.grow()
            active = sch.decode_batch()
            if active:
                sch.decoded(active, np.ones(1, np.int32))
            sch.end_tick()
            sch.check()
        res = sch.results()[0]
        print(res.status, len(res.tokens))
        print(sorted(m for m in sys.modules
                     if m.split('.')[0] in ('jax', 'jaxlib')))
    """)
    env = dict(os.environ, PYTHONPATH=str(src))
    out = subprocess.run([sys.executable, "-c", code], check=True, env=env,
                         capture_output=True, text=True).stdout
    assert out.split("\n")[:2] == [f"{OK} 5", "[]"]
