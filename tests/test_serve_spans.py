"""The paged engine's tick spans, read back from a profiler trace.

``PagedServeEngine.run`` records a ``serve.tick`` span per scheduler
tick with one span per phase inside it, and carries the tick's counts
as integer arguments.  These tests run small workloads under
``jax.profiler`` and check the span tree against ``RunStats`` and the
requests' results: every count the spans carry adds up to what the run
did.
"""

import collections
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serve import PagedServeEngine, Request

CFG = get_config("qwen2-7b").reduced()
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
PAGE = 128
CALLS = {"serve.prefill.wait": "serve.prefill",
         "serve.decode.wait": "serve.decode"}
PHASES = ("serve.control", "serve.admit", "serve.prefill", "serve.grow",
          "serve.decode", "serve.check")


class Span:
    def __init__(self, name, start, end, args):
        self.name, self.start, self.end, self.args = name, start, end, args
        self.parent = None


def _read_spans(trace_dir):
    """Every ``serve.*`` event of the host planes, each with its parent
    (the innermost span of the same thread enclosing it).  The program's
    tests do not import the benchmark, whose ``chipbench/spans.py``
    nests the same events; only the parent link is kept here."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                           dict(e.stats)) for e in line.events
                          if e.name.startswith("serve.")),
                         key=lambda e: (e[1], -e[2]))
            stack = []
            for name, s, e, args in evs:
                sp = Span(name, s, e, args)
                while stack and not (s >= stack[-1].start
                                     and e <= stack[-1].end):
                    stack.pop()
                sp.parent = stack[-1] if stack else None
                stack.append(sp)
                out.append(sp)
    return out


def _traced_run(eng, reqs, trace_dir, **kw):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        results, stats = eng.run(reqs, **kw)
    finally:
        jax.profiler.stop_trace()
    spans = _read_spans(trace_dir)
    by = collections.defaultdict(list)
    for sp in spans:
        by[sp.name].append(sp)
    return results, stats, spans, by


def _requests(specs, seed=7):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, CFG.vocab_size, (s,))
                    .astype(np.int32), n_steps=n, arrival=a)
            for s, n, a in specs]


def _shared_prefix_mix():
    """Prompts of several chunks and pages; the later ones share a
    two-page prefix, so part of their prompt is never computed."""
    rng = np.random.default_rng(21)
    prefix = rng.integers(0, CFG.vocab_size, (256,)).astype(np.int32)
    shared = [Request(prompt=np.concatenate(
                  [prefix, rng.integers(0, CFG.vocab_size, (n,))
                   .astype(np.int32)]), n_steps=5, arrival=i)
              for i, n in enumerate((24, 40, 7))]
    return shared + _requests([(129, 6, 0), (45, 3, 2)])


@pytest.fixture(scope="module")
def clean_run(tmp_path_factory):
    eng = PagedServeEngine(CFG, PARAMS, max_len=384, max_batch=2, page=PAGE,
                           check_invariants=True)
    reqs = _shared_prefix_mix()
    results, stats, spans, by = _traced_run(
        eng, reqs, tmp_path_factory.mktemp("clean"))
    return reqs, results, stats, spans, by


def test_every_span_name_is_recorded(clean_run):
    *_, by = clean_run
    assert set(by) == {"serve.tick", *PHASES, *CALLS}


def test_spans_nest_wait_in_call_in_tick(clean_run):
    _, _, _, spans, _ = clean_run
    for sp in spans:
        if sp.name in CALLS:
            assert sp.parent is not None and sp.parent.name == CALLS[sp.name]
        elif sp.name in PHASES:
            assert sp.parent is not None and sp.parent.name == "serve.tick"
        else:
            assert sp.name == "serve.tick" and sp.parent is None


def test_one_tick_span_per_tick_in_order(clean_run):
    _, _, stats, _, by = clean_run
    ticks = by["serve.tick"]
    assert len(ticks) == stats.ticks
    assert [t.args["tick"] for t in ticks] == list(range(stats.ticks))
    assert ticks[0].args["queued"] == 5 and ticks[0].args["busy"] == 0
    assert all(0 <= t.args["busy"] <= 2 for t in ticks)


def test_call_spans_count_the_device_calls(clean_run):
    _, _, stats, _, by = clean_run
    assert len(by["serve.prefill"]) == stats.prefill_chunks
    assert len(by["serve.decode"]) == stats.decode_steps
    assert len(by["serve.prefill.wait"]) == stats.prefill_chunks
    assert len(by["serve.decode.wait"]) == stats.decode_steps
    assert len(by["serve.check"]) == stats.ticks


def test_counters_add_up_to_the_run(clean_run):
    reqs, results, stats, _, by = clean_run
    # every token after a request's first comes from a decode step
    assert sum(d.args["active"] for d in by["serve.decode"]) == \
        sum(len(r.tokens) - 1 for r in results)
    computed = sum(r.prompt_len - r.prefix_blocks * PAGE for r in results)
    assert stats.prefix_blocks_reused > 0
    assert sum(p.args["n_valid"] for p in by["serve.prefill"]) == computed
    assert sum(a.args["admitted"] for a in by["serve.admit"]) == len(reqs)
    assert sum(a.args["prefix_blocks"] for a in by["serve.admit"]) == \
        stats.prefix_blocks_reused
    assert all(d.args["active"] >= 1 for d in by["serve.decode"])
    for c in by["serve.control"]:
        assert (c.args["cancelled"], c.args["timed_out"],
                c.args["shed"]) == (0, 0, 0)


def _replay_decodes(reqs, results, by):
    """Replay the chunks and steps in order, yielding each decode span
    with the lengths of the requests decoding in it (request -> length):
    a request decodes from the step after its last chunk until it has
    all its tokens (prompt, then one a step)."""
    lens, left = {}, {}
    for ev in sorted(by["serve.prefill"] + by["serve.decode"],
                     key=lambda sp: sp.start):
        if ev.name == "serve.prefill":
            rid = ev.args["req"]
            s = reqs[rid].prompt.shape[0]
            if ev.args["start"] + ev.args["n_valid"] == s:
                left[rid] = len(results[rid].tokens) - 1
                if left[rid]:
                    lens[rid] = s
            continue
        yield ev, dict(lens)
        for rid in list(lens):
            lens[rid] += 1
            left[rid] -= 1
            if not left[rid]:
                del lens[rid]
    assert not lens and set(left) == set(range(len(reqs)))


def test_decode_counters_replay_the_slots(clean_run):
    """Each step's ``active`` and ``kv_rows`` are the count of decoding
    requests and the sum of their lengths + 1."""
    reqs, results, _, _, by = clean_run
    steps = list(_replay_decodes(reqs, results, by))
    assert len(steps) == len(by["serve.decode"])
    for ev, lens in steps:
        assert ev.args["active"] == len(lens)
        assert ev.args["kv_rows"] == sum(n + 1 for n in lens.values())


def test_decode_kv_pages_count_the_kernels_pages(clean_run):
    """``kv_pages`` is the pages the paged kernel fetches a layer:
    ceil((length + 1) / page) for each decoding slot and one for every
    other slot of the batch, which attends one masked row."""
    reqs, results, _, _, by = clean_run
    pages = [(ev.args["kv_pages"],
              sum(-(-(n + 1) // PAGE) for n in lens.values())
              + 2 - len(lens))
             for ev, lens in _replay_decodes(reqs, results, by)]
    assert pages and all(got == want for got, want in pages)
    assert max(got for got, _ in pages) >= 5     # multi-page contexts


def test_prefill_spans_cover_each_prompt_once(clean_run):
    reqs, results, _, _, by = clean_run
    chunks = collections.defaultdict(list)
    for p in by["serve.prefill"]:
        chunks[p.args["req"]].append((p.args["start"], p.args["n_valid"]))
        assert 0 <= p.args["slot"] < 2
    assert set(chunks) == set(range(len(reqs)))
    for rid, cs in chunks.items():
        cs.sort()
        pos = results[rid].prefix_blocks * PAGE
        for start, n in cs:
            assert start == pos and n >= 1
            pos += n
        assert pos == reqs[rid].prompt.shape[0]


@pytest.fixture(scope="module")
def stressed_run(tmp_path_factory):
    """A pool too small for two long requests (organic preemption), a
    queue cap, a deadline and a cancellation that fall while queued."""
    reqs = _requests([(8, 150, 0), (8, 140, 0), (8, 4, 1), (8, 4, 1),
                      (8, 4, 2), (8, 4, 2)], seed=11)
    reqs[2].deadline = 5
    reqs[3].cancel_at = 3
    eng = PagedServeEngine(CFG, PARAMS, max_len=384, max_batch=2, page=PAGE,
                           n_blocks=4, max_queue=3)
    _, stats, _, by = _traced_run(eng, reqs,
                                  tmp_path_factory.mktemp("stressed"),
                                  max_ticks=2000)
    return stats, by


@pytest.mark.parametrize("span,arg,field", [
    ("serve.control", "cancelled", "cancelled"),
    ("serve.control", "timed_out", "timeouts"),
    ("serve.control", "shed", "shed"),
    ("serve.grow", "preempted", "preemptions"),
])
def test_phase_counters_match_run_stats(stressed_run, span, arg, field):
    stats, by = stressed_run
    assert getattr(stats, field) >= 1
    assert sum(sp.args[arg] for sp in by[span]) == getattr(stats, field)
    assert len(by[span]) == stats.ticks


def test_grow_counts_the_blocks_decode_allocates(stressed_run):
    """Every block a decoding slot takes at a page boundary is one
    ``grown``.  Only the two long requests cross a boundary (length
    128), at most once an admission, and both finish."""
    stats, by = stressed_run
    grown = sum(sp.args["grown"] for sp in by["serve.grow"])
    assert 2 <= grown <= 2 + stats.preemptions
    assert "serve.check" not in by
