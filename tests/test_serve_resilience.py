"""Graceful degradation for the paged serve engine.

Pins the ISSUE 10 acceptance contracts: every request reaches exactly
one terminal status with refcount-exact block reclamation, deadlines
and cancellations fire queued or in-flight, overload sheds instead of
growing the queue without bound, mid-flight pool exhaustion preempts
and recomputes instead of deadlocking, and — the load-bearing one — a
preempted-then-recomputed request emits bit-identical greedy tokens to
an uninterrupted run (the PR 7 aligned-T recipe, now under preemption).

The fault-injection harness is exercised three ways: hand-written plans
that force each fault kind, a seeded ``FaultPlan.random`` chaos sweep
(any red run names its seed and replays exactly), and per-tick
``PagedKVCache.check_invariants()`` which the engine asserts after
every tick whenever a plan is active.

The allocator gets a property test (random op interleavings preserve
the invariants) via the optional-hypothesis shim, plus a deterministic
rng stress twin so the coverage exists even without hypothesis.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.configs import get_config
from repro.fleet.capacity import simulate_trace
from repro.models import init_params
from repro.serve import (CANCELLED, OK, PREEMPTED, SHED, STATUSES, TIMEOUT,
                         DeadlineAwareShed, Fault, FaultPlan, FIFOPolicy,
                         PagedKVCache, PagedServeEngine, QueueCapPolicy,
                         Request, ServeEngine, min_service_ticks)
from tests._hypothesis_compat import given, settings, st

CFG = get_config("qwen2-7b").reduced()
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
PAGE = 128


def _engine(**kw):
    kw.setdefault("max_len", 64)
    kw.setdefault("max_batch", 2)
    kw.setdefault("page", PAGE)
    return PagedServeEngine(CFG, PARAMS, **kw)


def _requests(specs, seed=7, **extra):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, CFG.vocab_size, (s,))
                    .astype(np.int32), n_steps=n, arrival=a, **extra)
            for s, n, a in specs]


# ---------------------------------------------------------------------------
# resilience.py host logic (no jax)
# ---------------------------------------------------------------------------

def test_min_service_ticks():
    # 1 chunk covering the prompt + first token, then n-1 decode ticks
    assert min_service_ticks(8, 1, 32) == 1
    assert min_service_ticks(8, 5, 32) == 5
    assert min_service_ticks(64, 5, 32) == 6       # 2 chunks + 4 decodes
    assert min_service_ticks(65, 5, 32) == 7
    assert min_service_ticks(0, 3, 32) == 3        # empty prompt still ticks


def test_queue_cap_policy_sheds_newest_first():
    from repro.serve.resilience import queue_entries
    reqs = _requests([(8, 4, 0), (8, 4, 1), (8, 4, 2)])
    entries = queue_entries(5, [0, 1, 2], reqs, 32)
    shed = QueueCapPolicy(2).shed(5, entries)
    assert [rid for rid, _ in shed] == [2]          # newest arrival goes
    assert "max_queue 2" in shed[0][1]
    assert QueueCapPolicy(3).shed(5, entries) == []
    with pytest.raises(ValueError, match="max_queue"):
        QueueCapPolicy(0)


def test_deadline_aware_shed_rejects_only_unreachable():
    from repro.serve.resilience import queue_entries
    reqs = [Request(prompt=np.zeros(8, np.int32), n_steps=4, arrival=0,
                    deadline=3),                    # needs 4 ticks: t3 ok
            Request(prompt=np.zeros(8, np.int32), n_steps=4, arrival=0,
                    deadline=2),                    # finish t3 > 2: doomed
            Request(prompt=np.zeros(8, np.int32), n_steps=4, arrival=0)]
    entries = queue_entries(0, [0, 1, 2], reqs, 32)
    shed = DeadlineAwareShed().shed(0, entries)
    assert [rid for rid, _ in shed] == [1]
    assert "unreachable" in shed[0][1]
    assert DeadlineAwareShed(slack=1).shed(0, entries) == []
    assert FIFOPolicy().shed(0, entries) == []


def test_fault_validation_and_periodic_firing():
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault("melt", tick=0)
    with pytest.raises(ValueError, match="tick"):
        Fault("stall", tick=-1)
    with pytest.raises(ValueError, match="duration"):
        Fault("stall", tick=0, duration=0)
    with pytest.raises(ValueError, match="every"):
        Fault("exhaust", tick=0, every=0)
    f = Fault("preempt", tick=4, every=3, until=10)
    assert [t for t in range(14) if f.fires_at(t)] == [4, 7, 10]
    one = Fault("preempt", tick=4)
    assert [t for t in range(14) if one.fires_at(t)] == [4]


def test_fault_plan_effects_are_pure_functions_of_tick():
    plan = FaultPlan(seed=1, faults=[
        Fault("exhaust", tick=2, n=3, duration=2),
        Fault("preempt", tick=5, n=2),
        Fault("preempt", tick=5),
        Fault("stall", tick=7, duration=2),
        Fault("stall", tick=20, every=5, until=30, duration=2)])
    assert [f.n for f in plan.seizures(2)] == [3]
    assert plan.seizures(3) == []
    assert plan.forced_preemptions(5) == 3          # 2 + default 1
    assert plan.forced_preemptions(6) == 0
    assert plan.stalled(7) and plan.stalled(8) and not plan.stalled(9)
    # periodic stall: 2-tick windows at 20, 25, 30 — `until` bounds the
    # whole window, so the tick-30 firing is clipped to a single tick
    assert [t for t in range(19, 33) if plan.stalled(t)] == \
        [20, 21, 25, 26, 30]
    # replay: same queries give same answers (no hidden run state)
    assert plan.forced_preemptions(5) == 3
    with pytest.raises(TypeError, match="Fault objects"):
        FaultPlan(faults=["stall"])


def test_fault_plan_random_is_reproducible():
    a = FaultPlan.random(3, horizon=40)
    b = FaultPlan.random(3, horizon=40)
    assert a.faults == b.faults and a.seed == 3
    assert len(a.faults) == 6
    assert all(0 <= f.tick < 40 for f in a.faults)
    assert FaultPlan.random(4, horizon=40).faults != a.faults


# ---------------------------------------------------------------------------
# PagedKVCache invariants: example, stress, and property coverage
# ---------------------------------------------------------------------------

def _apply_ops(pc, ops):
    """Drive the allocator through an op script, mirroring how the
    engine holds references; invalid ops (refused by the cache) are
    skipped — the property is that *accepted* ops preserve invariants."""
    rng = np.random.default_rng(0)
    held = []                                       # engine-side ownership
    registered = 0
    for kind, arg in ops:
        if kind == "alloc":
            ids = pc.alloc(arg)
            if ids:                                 # alloc(0) holds nothing
                held.append(ids)
        elif kind == "free" and held:
            pc.free(held.pop(arg % len(held)))
        elif kind == "acquire" and held:
            ids = held[arg % len(held)]
            pc.acquire(ids)
            held.append(list(ids))
        elif kind == "register" and held:
            ids = held[arg % len(held)]
            toks = rng.integers(0, 97, (len(ids) * pc.page,))
            registered += 1
            pc.register_prefix(toks.astype(np.int32), ids)
        elif kind == "fork" and held and pc.free_blocks >= 1:
            ids = held[arg % len(held)]
            b = ids[arg % len(ids)]
            ids[ids.index(b)] = pc.fork(b)
        pc.check_invariants()
    for ids in held:
        pc.free(ids)
    pc.check_invariants()


_OP_KINDS = ("alloc", "free", "acquire", "register", "fork")


def test_cache_invariants_under_deterministic_stress():
    """Hypothesis-free twin of the property test below: 300 random ops
    from a fixed seed, invariants checked after every accepted op (and
    park/evict paths exercised via register + realloc)."""
    rng = np.random.default_rng(42)
    pc = PagedKVCache(CFG, n_blocks=9, page=PAGE)
    ops = [(_OP_KINDS[int(rng.integers(0, len(_OP_KINDS)))],
            int(rng.integers(0, 8))) for _ in range(300)]
    _apply_ops(pc, ops)
    assert pc.free_blocks == pc.capacity            # everything reclaimed


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(_OP_KINDS),
                          st.integers(min_value=0, max_value=7)),
                max_size=60))
def test_cache_invariants_property(ops):
    """Random interleavings of alloc/acquire/free/park/evict/fork must
    preserve check_invariants() after every accepted op — the refcount
    leaks example-based tests can't reach."""
    _apply_ops(PagedKVCache(CFG, n_blocks=6, page=PAGE), ops)


def test_check_invariants_catches_seeded_corruption():
    pc = PagedKVCache(CFG, n_blocks=5, page=PAGE)
    ids = pc.alloc(2)
    pc.check_invariants()
    pc._refs[ids[0]] = 0                            # leak: held but unowned
    with pytest.raises(AssertionError):
        pc.check_invariants()
    pc._refs[ids[0]] = 1
    pc.check_invariants()
    pc._fresh.append(ids[1])                        # double-owned
    with pytest.raises(AssertionError):
        pc.check_invariants()


# ---------------------------------------------------------------------------
# Terminal states on the engine
# ---------------------------------------------------------------------------

def test_deadline_timeout_in_flight_keeps_partial_tokens():
    eng = _engine()
    trace = _requests([(8, 40, 0)])
    trace[0].deadline = 5
    results, stats = eng.run(trace)
    (r,) = results
    assert r.status == TIMEOUT and "deadline 5" in r.detail
    assert 0 < len(r.tokens) < 40                   # partial stream kept
    assert r.admitted == 0 and r.finished == 6      # fired at tick 6 > 5
    assert stats.timeouts == 1 and stats.completed == 0
    assert eng.cache.free_blocks == eng.cache.capacity
    eng.cache.check_invariants()


def test_deadline_timeout_while_queued_never_admits():
    eng = _engine(max_batch=1)
    trace = _requests([(8, 30, 0), (8, 30, 0)])
    trace[1].deadline = 4                           # dies behind request 0
    results, stats = eng.run(trace)
    assert [r.status for r in results] == [OK, TIMEOUT]
    assert results[1].admitted == -1 and len(results[1].tokens) == 0
    assert "while queued" in results[1].detail
    assert stats.timeouts == 1 and stats.completed == 1


def test_cancellation_queued_and_in_flight():
    eng = _engine(max_batch=1)
    trace = _requests([(8, 30, 0), (8, 30, 0), (8, 6, 0)])
    trace[0].cancel_at = 3                          # in flight by then
    trace[1].cancel_at = 1                          # still queued
    results, stats = eng.run(trace)
    assert [r.status for r in results] == [CANCELLED, CANCELLED, OK]
    assert 0 < len(results[0].tokens) < 30
    assert len(results[1].tokens) == 0 and results[1].admitted == -1
    assert stats.cancelled == 2 and stats.completed == 1
    assert len(results[2].tokens) == 6
    assert eng.cache.free_blocks == eng.cache.capacity


def test_max_queue_sheds_newest_with_reason():
    eng = _engine(max_batch=1, max_queue=2)
    trace = _requests([(8, 12, 0), (8, 12, 0), (8, 12, 0), (8, 12, 0)])
    results, stats = eng.run(trace)
    statuses = [r.status for r in results]
    # the cap bounds the queue BEFORE admission runs: 4 arrive at tick 0,
    # the 2 newest are shed, the 2 oldest keep their FIFO claim
    assert statuses == [OK, OK, SHED, SHED]
    assert "max_queue 2" in results[3].detail
    assert stats.shed == 2 and stats.completed == 2


def test_deadline_aware_shed_policy_on_engine():
    eng = _engine(max_batch=1, admission=DeadlineAwareShed())
    trace = _requests([(8, 30, 0), (8, 30, 0)])
    trace[1].deadline = 10                          # unreachable behind r0
    results, stats = eng.run(trace)
    assert [r.status for r in results] == [OK, SHED]
    assert "unreachable" in results[1].detail
    # shed beats timing out: rejected the moment it became doomed, not
    # after burning queue time until the deadline passed
    assert results[1].finished < 10
    assert stats.shed == 1 and stats.timeouts == 0


def test_oversized_request_error_names_capacity_and_need():
    eng = _engine(max_len=192, n_blocks=2)          # capacity 1 block
    trace = _requests([(100, 60, 0)])               # needs 2 blocks
    with pytest.raises(ValueError) as ei:
        eng.run(trace)
    msg = str(ei.value)
    assert "needs 2 blocks" in msg
    assert "capacity is 1 blocks" in msg
    assert "n_blocks >= 3" in msg
    with pytest.raises(ValueError, match="max_len"):
        eng.run(_requests([(150, 60, 0)]))          # 210 > max_len 192


# ---------------------------------------------------------------------------
# Preemption: organic exhaustion, forced faults, and bitwise parity
# ---------------------------------------------------------------------------

def test_organic_preemption_recompute_is_bit_identical():
    """THE regression this PR exists for: a pool too small for both
    growing requests forces preempt-and-recompute, and the preempted
    stream must match both an uncontended paged run and the synchronous
    aligned-T oracle bit for bit."""
    trace = _requests([(8, 150, 0), (8, 140, 0)], seed=11)
    roomy = PagedServeEngine(CFG, PARAMS, max_len=384, max_batch=2,
                             page=PAGE)
    r_results, r_stats = roomy.run(trace)
    assert r_stats.preemptions == 0

    # capacity 3 < the 4 blocks both requests eventually need: the
    # second request self-preempts at its page boundary and recomputes
    tight = PagedServeEngine(CFG, PARAMS, max_len=384, max_batch=2,
                             page=PAGE, n_blocks=4, check_invariants=True)
    t_results, t_stats = tight.run(trace, max_ticks=2000)
    assert t_stats.preemptions >= 1
    assert [r.status for r in t_results] == [OK, OK]
    assert any(r.preemptions > 0 for r in t_results)
    for a, b in zip(r_results, t_results):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    assert tight.cache.free_blocks == tight.cache.capacity

    oracle = ServeEngine(CFG, PARAMS, max_len=384, prefill_pad=True)
    o_results, _ = oracle.run(trace)
    for a, b in zip(o_results, t_results):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_forced_preemption_fault_is_bit_identical():
    trace = _requests([(8, 20, 0), (12, 16, 0)])
    eng = _engine()
    clean, _ = eng.run(trace)
    plan = FaultPlan(faults=[Fault("preempt", tick=4, n=1)])
    faulted, stats = _engine().run(trace, fault_plan=plan, max_ticks=500)
    assert stats.preemptions >= 1
    assert [r.status for r in faulted] == [OK, OK]
    for a, b in zip(clean, faulted):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_preemption_budget_is_terminal():
    """max_preemptions=0: the first eviction is final — partial tokens
    kept, status PREEMPTED, blocks reclaimed."""
    trace = _requests([(8, 20, 0), (12, 16, 0)])
    plan = FaultPlan(faults=[Fault("preempt", tick=4, n=1)])
    eng = _engine(max_preemptions=0)
    results, stats = eng.run(trace, fault_plan=plan, max_ticks=500)
    statuses = sorted(r.status for r in results)
    assert statuses == [OK, PREEMPTED]
    victim = next(r for r in results if r.status == PREEMPTED)
    assert victim.preemptions == 1 and "max_preemptions=0" in victim.detail
    assert stats.preemptions == 1
    assert eng.cache.free_blocks == eng.cache.capacity


# ---------------------------------------------------------------------------
# The chaos harness: exhaustion mid-flight, stalls, seeded sweeps
# ---------------------------------------------------------------------------

def test_exhaustion_fault_mid_flight_completes_without_deadlock():
    """ISSUE acceptance: seize the whole pool mid-flight, stall the data
    plane, force preemptions — the run must still terminate with every
    request in a terminal state and invariants green after every tick
    (the engine asserts them itself whenever a fault_plan is active)."""
    trace = _requests([(8, 24, 0), (40, 16, 0), (12, 20, 2), (8, 12, 4)])
    trace[2].deadline = 30
    trace[3].cancel_at = 18
    plan = FaultPlan(seed=0, faults=[
        Fault("exhaust", tick=3, n=None, duration=4),   # seize everything
        Fault("stall", tick=9, duration=2),
        Fault("preempt", tick=13, n=2),
        Fault("exhaust", tick=16, n=2, duration=3)])
    eng = _engine(max_batch=2, n_blocks=4)
    results, stats = eng.run(trace, fault_plan=plan, max_ticks=1000)
    assert len(results) == len(trace)
    assert all(r.status in STATUSES for r in results)
    assert stats.stalled_ticks == 2
    assert stats.preemptions >= 1
    assert stats.completed + stats.shed + stats.timeouts \
        + stats.cancelled \
        + sum(1 for r in results if r.status == PREEMPTED) \
        == stats.requests
    assert eng.cache.free_blocks == eng.cache.capacity  # nothing leaked
    eng.cache.check_invariants()


def test_seizure_outliving_run_is_released():
    """A seizure window can extend past the last request's completion
    (seed 10 of the CI sweep found this): the engine must hand the
    fault-held blocks back when the run drains, not leak them."""
    trace = _requests([(8, 4, 0)])
    plan = FaultPlan(faults=[Fault("exhaust", tick=1, n=2, duration=500)])
    eng = _engine(max_batch=2, n_blocks=5, check_invariants=True)
    results, _ = eng.run(trace, fault_plan=plan, max_ticks=1000)
    assert results[0].status == OK
    assert eng.cache.free_blocks == eng.cache.capacity


def test_random_fault_plans_seed_sweep():
    """Chaos sweep: any seed's plan must terminate every request and
    keep the pool conserved; a failure names its seed for exact replay."""
    trace = _requests([(8, 10, 0), (16, 8, 1), (8, 12, 3)])
    for seed in range(4):
        plan = FaultPlan.random(seed, horizon=25)
        eng = _engine(max_batch=2, n_blocks=4)
        results, _ = eng.run(trace, fault_plan=plan, max_ticks=3000)
        assert len(results) == len(trace), f"seed {seed}"
        assert all(r.status in STATUSES for r in results), f"seed {seed}"
        assert eng.cache.free_blocks == eng.cache.capacity, f"seed {seed}"


def test_stall_fault_ages_deadlines():
    """Stalls lose data-plane ticks but the control plane keeps running:
    a deadline that fits without the stall times out under it."""
    trace = _requests([(8, 10, 0)])
    trace[0].deadline = 11
    clean, _ = _engine().run(trace)
    assert clean[0].status == OK
    plan = FaultPlan(faults=[Fault("stall", tick=1, duration=6)])
    stalled, stats = _engine().run(trace, fault_plan=plan, max_ticks=200)
    assert stalled[0].status == TIMEOUT
    assert stats.stalled_ticks == 6


def test_check_invariants_flag_without_faults():
    eng = _engine(check_invariants=True)
    results, _ = eng.run(_requests([(8, 6, 0), (12, 5, 1)]))
    assert [r.status for r in results] == [OK, OK]


# ---------------------------------------------------------------------------
# The fleet replica stays tick-exact under resilience
# ---------------------------------------------------------------------------

def test_simulate_trace_tick_exact_on_overload_with_faults():
    """The calibration contract extended to the degraded regime: same
    trace, same policies, same FaultPlan — every tick counter and every
    resilience counter must match the real engine exactly."""
    from repro.serve.traces import get_trace
    trace = get_trace("overload")(10, CFG.vocab_size, seed=3)
    plan = FaultPlan(faults=[Fault("exhaust", tick=4, n=2, duration=3),
                             Fault("preempt", tick=8, n=1),
                             Fault("stall", tick=11, duration=2)])
    policy = DeadlineAwareShed(slack=2)
    eng = PagedServeEngine(CFG, PARAMS, max_len=160, max_batch=2,
                           page=PAGE, prefix_cache=False, max_queue=4,
                           admission=policy)
    _, stats = eng.run(trace, fault_plan=plan, max_ticks=5000)
    sim = simulate_trace(trace, max_len=160, max_batch=2, page=PAGE,
                         n_blocks=eng.cache.n_blocks, prefill_chunk=32,
                         max_queue=4, admission=policy, fault_plan=plan,
                         max_ticks=5000)
    for field in ("requests", "tokens", "ticks", "decode_steps",
                  "prefill_chunks", "completed", "shed", "timeouts",
                  "cancelled", "preemptions", "stalled_ticks"):
        assert getattr(sim, field) == stats[field], field
    assert sim.occupancy_max == pytest.approx(stats["occupancy_max"])
    assert stats.shed + stats.timeouts > 0          # overload actually bit
