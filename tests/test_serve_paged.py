"""Continuous-batching serve engine + block-paged KV cache.

Pins the ISSUE acceptance contracts: admission backpressure when the
block pool is exhausted, retirement returning blocks to the free list,
and — the load-bearing one — interleaved prefill/decode producing
bit-identical greedy tokens vs the synchronous ``ServeEngine`` oracle
for ragged, staggered-arrival request mixes.

Prefix-cache era additions: refcounted acquire/release round-trips,
chained-hash prefix match/register/revive/evict, copy-on-write fork
leaving the shared block bit-identical, chunked continuation prefill
holding the same bitwise parity on long prompts, and shared-prefix
traces reusing blocks (nonzero hit rate) without perturbing tokens.
The long-prompt oracles run ``ServeEngine(prefill_pad=True)``: bitwise
parity needs every attention contraction at the same aligned KV length
(ragged exact-length prefill rounds its tail reduction differently).
"""

import re
import warnings

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serve import (PagedKVCache, PagedServeEngine, Request,
                         RunStats, ServeEngine, default_page_size,
                         prefix_digests)

CFG = get_config("qwen2-7b").reduced()
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
PAGE = 128


def _engine(**kw):
    kw.setdefault("max_len", 64)
    kw.setdefault("max_batch", 2)
    kw.setdefault("page", PAGE)
    return PagedServeEngine(CFG, PARAMS, **kw)


def _requests(specs, seed=7):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, CFG.vocab_size, (s,))
                    .astype(np.int32), n_steps=n, arrival=a)
            for s, n, a in specs]


# ---------------------------------------------------------------------------
# PagedKVCache: allocator + layout contracts
# ---------------------------------------------------------------------------

def test_cache_alloc_free_roundtrip():
    pc = PagedKVCache(CFG, n_blocks=5, page=PAGE)
    assert pc.capacity == 4 and pc.free_blocks == 4
    ids = pc.alloc(3)
    assert len(ids) == 3 and len(set(ids)) == 3
    assert all(1 <= b < 5 for b in ids)          # null block 0 never leaves
    assert pc.used_blocks == 3
    assert pc.alloc(2) is None                   # all-or-nothing
    assert pc.free_blocks == 1                   # failed alloc took nothing
    pc.free(ids)
    assert pc.free_blocks == 4 and pc.occupancy() == 0.0


def test_cache_free_validates():
    pc = PagedKVCache(CFG, n_blocks=3, page=PAGE)
    ids = pc.alloc(1)
    pc.free(ids)
    with pytest.raises(ValueError, match="double-freed"):
        pc.free(ids)
    with pytest.raises(ValueError, match="allocatable range"):
        pc.free([0])


def test_cache_pool_shapes_mirror_init_cache():
    pc = PagedKVCache(CFG, n_blocks=3, page=PAGE)
    from repro.models.blocks import schedule
    first_k, period, n_periods = schedule(CFG)
    assert len(pc.pools["layers0"]) == first_k
    assert len(pc.pools["layers"]) == period
    k = pc.pools["layers"][0]["k"]
    assert k.shape == (n_periods, 3, CFG.n_kv_heads, PAGE, CFG.hd)


def test_cache_rejects_non_attention_layers():
    mamba = get_config("mamba2-370m").reduced()
    with pytest.raises(NotImplementedError,
                       match="only attention layers page.*SSM state"):
        PagedKVCache(mamba, n_blocks=3, page=PAGE)


def test_default_page_size_is_planner_block():
    # the pool's gather granularity IS the paged kernel's kv tile
    page = default_page_size(CFG)
    from repro.kernels import plan_for
    plan = plan_for("paged_decode_attention",
                    {"B": 1, "T": 512, "H": CFG.n_heads,
                     "KV": CFG.n_kv_heads, "hd": CFG.hd},
                    dtype=CFG.dtype)
    assert page == plan.blocks["block_kv"]


def test_cache_rejects_misaligned_page():
    with pytest.raises(ValueError):
        PagedKVCache(CFG, n_blocks=3, page=100)


# ---------------------------------------------------------------------------
# Scheduler: admission backpressure + eviction
# ---------------------------------------------------------------------------

def test_admission_waits_when_pool_full():
    """Two 1-block requests on a 2-allocatable-block pool run concurrently;
    the third must wait for a retirement before being admitted."""
    eng = _engine(max_batch=3, n_blocks=3)      # capacity 2 < 3 requests
    reqs = _requests([(8, 4, 0), (8, 6, 0), (8, 3, 0)])
    results, stats = eng.run(reqs)
    assert len(results) == 3
    assert results[0].admitted == 0 and results[1].admitted == 0
    # req2 could only enter once req0 (the shortest) retired
    assert results[2].admitted > results[0].finished - 1
    assert stats["occupancy_max"] <= 1.0
    assert all(r.tokens.shape == (reqs[i].n_steps,)
               for i, r in enumerate(results))


def test_retirement_returns_blocks_to_free_list():
    eng = _engine(max_batch=2, n_blocks=3)
    reqs = _requests([(5, 3, 0), (9, 5, 1), (7, 2, 2), (6, 4, 2)])
    results, stats = eng.run(reqs)
    assert len(results) == 4
    assert eng.cache.free_blocks == eng.cache.capacity   # all returned
    assert eng.cache.occupancy() == 0.0
    assert stats["tokens"] == sum(r.n_steps for r in reqs)


def test_request_larger_than_pool_raises():
    eng = _engine(max_len=192, max_batch=2, n_blocks=2)   # capacity 1 block
    # needs ceil((120+16)/128) = 2 blocks > capacity: can never be admitted
    with pytest.raises(ValueError, match="blocks"):
        eng.run(_requests([(120, 16, 0)]), temperature=0.0)


def test_request_overflowing_max_len_raises():
    eng = _engine()
    with pytest.raises(ValueError, match="max_len"):
        eng.run(_requests([(60, 8, 0)]))


# ---------------------------------------------------------------------------
# Parity: interleaved prefill/decode == the synchronous oracle, bitwise
# ---------------------------------------------------------------------------

def test_greedy_parity_vs_sync_engine():
    """Ragged prompts, staggered arrivals, a pool small enough to force
    wait-then-admit interleaving: every request's greedy stream must be
    bit-identical to a solo run on the synchronous engine."""
    specs = [(5, 6, 0), (17, 9, 0), (12, 4, 2), (30, 3, 3), (9, 8, 5)]
    reqs = _requests(specs)
    eng = _engine(max_batch=2, n_blocks=3)
    results, stats = eng.run(reqs)
    assert stats["requests"] == len(specs)
    sync = ServeEngine(CFG, PARAMS, max_len=64)
    for i, (r, req) in enumerate(zip(results, reqs)):
        ref = sync.generate(req.prompt[None], n_steps=req.n_steps).tokens[0]
        np.testing.assert_array_equal(
            ref, r.tokens, err_msg=f"request {i} diverged from the oracle")


def test_generate_parity_batch_api():
    """The (B, S) convenience wrapper matches ServeEngine.generate."""
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, CFG.vocab_size, (3, 12)).astype(np.int32)
    ref = ServeEngine(CFG, PARAMS, max_len=64).generate(
        prompts, n_steps=8).tokens
    got = _engine(max_batch=4).generate(prompts, n_steps=8)
    np.testing.assert_array_equal(ref, got)


def test_run_is_deterministic_across_reuse():
    """Re-serving the same trace on a dirty pool (stale residue, permuted
    free list) reproduces the first run's tokens exactly — results must
    never depend on which physical blocks a request lands in."""
    reqs = _requests([(5, 4, 0), (17, 6, 0), (9, 5, 1)])
    eng = _engine(max_batch=2, n_blocks=3)
    first, _ = eng.run(reqs)
    second, _ = eng.run(reqs)
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_temperature_seed_control():
    reqs = _requests([(8, 6, 0), (11, 6, 0)])
    eng = _engine(max_batch=2)
    a, _ = eng.run(reqs, temperature=1.0, seed=0)
    b, _ = eng.run(reqs, temperature=1.0, seed=0)
    c, _ = eng.run(reqs, temperature=5.0, seed=1)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.tokens, y.tokens)
    assert any(not np.array_equal(x.tokens, y.tokens)
               for x, y in zip(a, c))


# ---------------------------------------------------------------------------
# The layer scan carries the pool stacks: no step copies a layer's pool
# ---------------------------------------------------------------------------

_TENSOR = re.compile(r"tensor<([0-9x]+)x[a-z0-9]+>")


def _pool_moves(text, pool_shape):
    """The lowered module's dynamic_slice results and dynamic_update_slice
    updates that are a whole layer's (1, P, KV, page, hd) pool."""
    moves = []
    for line in text.splitlines():
        if "stablehlo.dynamic_slice" in line:
            moved = _TENSOR.findall(line.split("->")[-1])[:1]
        elif "stablehlo.dynamic_update_slice" in line:
            moved = _TENSOR.findall(line.split(":")[-1])[1:2]
        else:
            continue
        if moved and tuple(map(int, moved[0].split("x"))) == pool_shape:
            moves.append(line.strip())
    return moves


@pytest.mark.parametrize("chunk", [32, 48])      # aligned write, row scatter
def test_steps_never_slice_or_rebuild_a_layer_pool(chunk):
    eng = _engine(max_len=256, max_batch=2, n_blocks=9, prefill_chunk=chunk)
    k = eng.cache.pools["layers"][0]["k"]
    assert k.shape[0] > 1                        # a stack the scan walks
    pool_shape = (1,) + k.shape[1:]
    B, NB = eng.max_batch, eng.nb_table
    dec = eng._decode.lower(
        PARAMS, eng.cache.pools, np.zeros((B, 1), np.int32),
        np.zeros((B, NB), np.int32), np.zeros((B,), np.int32)).as_text()
    pre = eng._prefill.lower(
        PARAMS, eng.cache.pools, np.zeros((1, chunk), np.int32),
        np.zeros((1, NB), np.int32), np.zeros((1,), np.int32),
        np.full((1,), chunk, np.int32)).as_text()
    assert "stablehlo.while" in dec and "stablehlo.while" in pre
    assert _pool_moves(dec, pool_shape) == []
    assert _pool_moves(pre, pool_shape) == []


# ---------------------------------------------------------------------------
# Refcounted block sharing: acquire/release, prefix index, COW fork
# ---------------------------------------------------------------------------

def _toks(n, seed=11):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, (n,)).astype(np.int32)


def test_cache_refcount_acquire_release_roundtrip():
    pc = PagedKVCache(CFG, n_blocks=4, page=PAGE)
    ids = pc.alloc(2)
    assert all(pc.ref_count(b) == 1 for b in ids)
    pc.acquire(ids)                              # second holder
    assert all(pc.ref_count(b) == 2 for b in ids)
    pc.free(ids)                                 # first holder leaves
    assert all(pc.ref_count(b) == 1 for b in ids)
    assert pc.used_blocks == 2                   # still held, not free
    pc.free(ids)                                 # last holder leaves
    assert pc.free_blocks == pc.capacity
    with pytest.raises(ValueError, match="double-freed"):
        pc.free(ids)
    with pytest.raises(ValueError, match="not live or cached"):
        pc.acquire(ids)                          # unwritten blocks: alloc only


def test_cache_prefix_match_register_revive():
    pc = PagedKVCache(CFG, n_blocks=4, page=PAGE)
    toks = _toks(2 * PAGE + 40)
    ids = pc.alloc(2)
    pc.register_prefix(toks, ids)
    assert pc.match_prefix(toks) == ids          # both full pages indexed
    assert pc.match_prefix(toks[:PAGE + 5]) == ids[:1]
    other = _toks(2 * PAGE, seed=99)
    assert pc.match_prefix(other) == []
    pc.free(ids)                                 # refcount 0: parked, not lost
    assert pc.free_blocks == pc.capacity and pc.cached_blocks == 2
    assert pc.match_prefix(toks) == ids          # still matchable
    pc.acquire(ids)                              # revival: a cache hit
    assert pc.cached_blocks == 0
    assert all(pc.ref_count(b) == 1 for b in ids)
    pc.free(ids)


def test_cache_eviction_only_reclaims_ref0_blocks():
    pc = PagedKVCache(CFG, n_blocks=4, page=PAGE)   # capacity 3
    toks_live, toks_dead = _toks(PAGE, seed=1), _toks(PAGE, seed=2)
    live = pc.alloc(1)
    pc.register_prefix(toks_live, live)
    dead = pc.alloc(1)
    pc.register_prefix(toks_dead, dead)
    pc.free(dead)                                # parked at refcount 0
    ids = pc.alloc(2)                            # 1 fresh + must evict `dead`
    assert dead[0] in ids and live[0] not in ids
    assert pc.match_prefix(toks_dead) == []      # evicted => deregistered
    assert pc.match_prefix(toks_live) == live    # live entry untouched
    assert pc.alloc(1) is None                   # live block is not takeable
    pc.free(ids)
    pc.free(live)


def test_cache_fork_leaves_shared_block_bit_identical():
    pc = PagedKVCache(CFG, n_blocks=4, page=PAGE)
    b = pc.alloc(1)[0]

    def paint(val, blk):
        def pt(p):
            return (p.at[:, blk].set(val) if p.ndim == 5
                    else p.at[blk].set(val))
        pc.pools = jax.tree.map(pt, pc.pools)

    def rows(blk):
        return [np.asarray(p[:, blk] if p.ndim == 5 else p[blk])
                for p in jax.tree.leaves(pc.pools)]

    paint(7.0, b)
    before = rows(b)
    pc.acquire([b])                              # two holders share b
    dst = pc.fork(b)                             # holder 2 goes private
    assert dst != b
    assert pc.ref_count(b) == 1 and pc.ref_count(dst) == 1
    for a, c in zip(rows(dst), before):
        np.testing.assert_array_equal(a, c)      # copy is bitwise
    paint(9.0, dst)                              # the forker writes...
    for a, c in zip(rows(b), before):
        np.testing.assert_array_equal(a, c)      # ...shared block untouched
    loose = pc.alloc(1)[0]
    pc.free([loose])
    with pytest.raises(ValueError, match="no references"):
        pc.fork(loose)                           # freed block: nothing to share


def test_prefix_digests_chain_over_pages():
    toks = _toks(3 * PAGE)
    ds = prefix_digests(toks, PAGE)
    assert len(ds) == 3 and len(set(ds)) == 3
    mut = toks.copy()
    mut[5] += 1                                  # flip a token in page 0
    ds2 = prefix_digests(mut, PAGE)
    assert all(a != b for a, b in zip(ds, ds2))  # chain: all suffixes move
    assert prefix_digests(toks[:PAGE - 1], PAGE) == []


# ---------------------------------------------------------------------------
# Chunked continuation prefill + prefix sharing: long-prompt parity
# ---------------------------------------------------------------------------

def _long_engine(**kw):
    kw.setdefault("max_len", 384)
    kw.setdefault("max_batch", 2)
    kw.setdefault("page", PAGE)
    return PagedServeEngine(CFG, PARAMS, **kw)


def _oracle():
    return ServeEngine(CFG, PARAMS, max_len=384, prefill_pad=True)


def test_chunked_prefill_long_prompt_parity():
    """Prompts spanning several pages prefill in 32-token chunks that
    attend back through the block table; greedy streams must stay
    bit-identical to the aligned-prefill synchronous oracle."""
    specs = [(129, 5, 0), (279, 6, 0), (200, 4, 2)]
    reqs = _requests(specs)
    eng = _long_engine()
    results, stats = eng.run(reqs)
    assert stats["prefill_chunks"] >= sum(-(-s // 32) for s, _, _ in specs)
    sync = _oracle()
    for i, (r, req) in enumerate(zip(results, reqs)):
        ref = sync.generate(req.prompt[None], n_steps=req.n_steps).tokens[0]
        np.testing.assert_array_equal(
            ref, r.tokens, err_msg=f"request {i} diverged from the oracle")


def test_prefill_chunk_size_invariance():
    """The chunk size is a scheduling knob, not a numerics knob."""
    reqs = _requests([(279, 5, 0), (150, 4, 1)])
    base, _ = _long_engine(prefill_chunk=32).run(reqs)
    for chunk in (64, 128):
        got, _ = _long_engine(prefill_chunk=chunk).run(reqs)
        for a, b in zip(base, got):
            np.testing.assert_array_equal(a.tokens, b.tokens)


def _shared_prefix_reqs(n=4, prefix_len=256, tail=24, steps=5):
    rng = np.random.default_rng(21)
    prefix = rng.integers(0, CFG.vocab_size, (prefix_len,)).astype(np.int32)
    return [Request(prompt=np.concatenate(
                [prefix, rng.integers(0, CFG.vocab_size, (tail,))
                 .astype(np.int32)]),
                    n_steps=steps, arrival=i) for i in range(n)]


def test_shared_prefix_parity_and_hit_rate():
    """Requests sharing a 2-page system prefix: later arrivals take the
    prefix blocks by refcount bump (zero prefill compute), tokens stay
    bit-identical to solo oracle runs, and the hit rate is visible in
    both the stats payload and the per-request results."""
    reqs = _shared_prefix_reqs()
    eng = _long_engine()
    results, stats = eng.run(reqs)
    assert stats["prefix_blocks_reused"] > 0
    assert stats["prefix_blocks_needed"] == 2 * len(reqs)
    assert 0.0 < stats["prefix_hit_rate"] <= 1.0
    assert results[0].prefix_blocks == 0         # first writer pays
    assert any(r.prefix_blocks == 2 for r in results[1:])
    sync = _oracle()
    for i, (r, req) in enumerate(zip(results, reqs)):
        ref = sync.generate(req.prompt[None], n_steps=req.n_steps).tokens[0]
        np.testing.assert_array_equal(
            ref, r.tokens, err_msg=f"request {i} diverged from the oracle")


def test_prefix_cache_off_is_equivalent_but_never_shares():
    reqs = _shared_prefix_reqs(n=3)
    on, s_on = _long_engine().run(reqs)
    off, s_off = _long_engine(prefix_cache=False).run(reqs)
    assert s_off["prefix_blocks_reused"] == 0
    assert s_off["prefix_hit_rate"] == 0.0
    assert s_on["prefix_blocks_reused"] > 0
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_ttft_fields_and_prefill_accounting():
    reqs = _requests([(129, 4, 0)])
    results, stats = _long_engine().run(reqs)
    r = results[0]
    assert r.admit_time > 0.0
    assert r.emit_times[0] >= r.admit_time       # TTFT = first emit - admit
    assert stats["prefill_chunks"] == -(-129 // 32)


def test_oversized_request_fails_fast_at_validation():
    """A too-big request must raise up front — not deadlock at the queue
    head while runnable requests starve behind it."""
    eng = _engine(max_len=192, max_batch=2, n_blocks=2)   # capacity 1 block
    ok, huge = _requests([(8, 4, 0), (120, 16, 0)])
    with pytest.raises(ValueError, match="blocks"):
        eng.run([ok, huge])


# ---------------------------------------------------------------------------
# Typed serve API: shared run(trace) protocol, RunStats, tuple shim
# ---------------------------------------------------------------------------

def test_run_protocol_parity_across_engines():
    """Both engines serve the same typed trace through the shared
    ``run(trace)`` protocol; the synchronous engine in its batch=1
    oracle mode must match the paged engine's greedy streams token for
    token, and both hand back a RunStats."""
    reqs = _requests([(5, 6, 0), (17, 9, 1), (12, 4, 2)])
    paged_res, paged_stats = _engine(max_batch=2, n_blocks=3).run(reqs)
    sync_res, sync_stats = ServeEngine(CFG, PARAMS, max_len=64).run(reqs)
    assert isinstance(paged_stats, RunStats)
    assert isinstance(sync_stats, RunStats)
    assert sync_stats["tokens"] == paged_stats["tokens"]
    assert sync_stats["batches"] == len(reqs)     # solo oracle groups
    for i, (a, b) in enumerate(zip(paged_res, sync_res)):
        np.testing.assert_array_equal(
            a.tokens, b.tokens,
            err_msg=f"request {i}: run() protocol engines diverged")
        assert a.prompt_len == b.prompt_len
        assert len(b.emit_times) == len(b.tokens)


def test_sync_run_batched_matches_generate_slices():
    """batch>1 replay is the padded-bucket semantics run_sync always had:
    group max steps, per-request slice."""
    reqs = _requests([(6, 4, 0), (11, 7, 0), (9, 3, 1)])
    eng = ServeEngine(CFG, PARAMS, max_len=64)
    results, stats = eng.run(reqs, batch=3)
    assert stats["batches"] == 1 and stats["decode_steps"] == 7
    s_max = max(r.prompt.shape[0] for r in reqs)
    padded = np.stack([np.pad(r.prompt, (0, s_max - r.prompt.shape[0]))
                       for r in reqs])
    ref = eng.generate(padded, n_steps=7).tokens
    for i, r in enumerate(results):
        np.testing.assert_array_equal(ref[i, :reqs[i].n_steps], r.tokens)


def test_tuple_trace_shim_warns_once_and_matches_typed():
    """Legacy (prompt, n_steps, arrival) tuples still run — coerced with
    a one-shot DeprecationWarning — and produce the same tokens as the
    typed trace."""
    import repro.serve.api as api
    reqs = _requests([(6, 4, 0), (9, 3, 1)])
    tuples = [(r.prompt.copy(), r.n_steps, r.arrival) for r in reqs]
    eng = _engine(max_batch=2)
    typed, _ = eng.run(reqs)
    api._WARNED.discard("tuple-trace")            # arm the one-shot
    with pytest.warns(DeprecationWarning, match="repro.serve.Request"):
        shim, _ = eng.run(tuples)
    with warnings.catch_warnings():               # second coercion: silent
        warnings.simplefilter("error", DeprecationWarning)
        eng.run(tuples)
    for a, b in zip(typed, shim):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_run_rejects_garbage_trace_entries():
    eng = _engine()
    with pytest.raises(TypeError, match="Request"):
        eng.run(["not a request"])
    with pytest.raises(ValueError, match="n_steps"):
        eng.run([Request(prompt=np.zeros(4, np.int32), n_steps=0)])


def test_runstats_is_dict_compatible():
    _, stats = _engine().run(_requests([(6, 3, 0)]))
    assert stats["tokens"] == stats.tokens == 3
    assert {"ticks", "decode_steps", "prefix_hit_rate"} <= set(stats.keys())
    assert stats.get("not_a_field", 42) == 42
    with pytest.raises(KeyError):
        stats["not_a_field"]
    assert stats.as_dict()["requests"] == 1
