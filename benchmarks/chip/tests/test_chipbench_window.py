"""The window's arithmetic on hand-made results, and the sample drawn
for the correctness check."""

import types

import numpy as np

import chipbench_tiny  # noqa: F401  (puts chipbench on the path)
from chipbench import check, window


def _r(admit, emits, status="OK", tokens=None):
    return types.SimpleNamespace(admit_time=admit, emit_times=emits,
                                 status=status,
                                 tokens=np.array(tokens or [1] * len(emits)))


def test_only_events_inside_the_window_count():
    res = [_r(0.0, [0.5, 1.2, 1.5, 2.5]),     # under way at the opening
           _r(1.1, [1.4, 1.9, 3.2]),          # admitted inside
           _r(2.9, [3.6]),                    # first token after the close
           _r(2.95, [], status="CANCELLED"),  # none by the end: censored
           _r(3.1, [3.3])]                    # admitted after the close
    ws = window.measure(res, 1.0, 3.0, end_t=4.0)
    assert ws.tokens == 5                     # 1.2 1.5 2.5 1.4 1.9
    assert np.allclose(sorted(ws.gaps_s), [0.3, 0.5, 1.0])
    assert np.allclose(ws.ttft_s, [0.3, 0.7, 1.05])
    assert ws.censored == 1
    assert ws.tokens_per_s == 2.5
    assert window.pct(np.arange(101.0), 95) == 95.0


def test_sample_has_the_longest_and_enough_tokens():
    res = [_r(0, [0] * n, tokens=list(range(n))) for n in (3, 9, 4, 5)]
    res.append(_r(0, [0] * 20, status="CANCELLED"))
    prompts = [np.arange(10, dtype=np.int32)] * 5
    s = check.draw(res, prompts, seed=1, min_tokens=12, max_requests=3)
    assert s.ids[0] == 1 and 4 not in s.ids
    assert sum(len(t) for t in s.served) >= 12
    # the rows that predicted each served token
    assert len(s.seqs[0]) == 10 + 9 - 1
    assert list(s.rows[0]) == list(range(9, 18))
    lg = [np.eye(20)[np.arange(len(t))] for t in s.served]
    assert check.served_gap(lg, [np.arange(len(t)) for t in s.served]) == 0
    assert check.served_gap(lg, [np.arange(len(t)) + 1
                                 for t in s.served]) == 1


def test_run_goes_on_until_the_window_admissions_have_a_first_token():
    import jax.numpy as jnp
    from chipbench.probe import Probe

    eng = types.SimpleNamespace(prefill_chunk=4, max_batch=2,
                                _prefill=None,
                                _decode=lambda p, c, t, tbl, ln: ln)
    reqs = [types.SimpleNamespace(prompt=np.arange(4), cancel_at=None)
            for _ in range(3)]
    probe = Probe(eng, reqs, n_first=0, seconds=0.0)  # opens and closes
    eng._decode(0, 0, 0, 0, jnp.array([0, 5]))        # slot 0 prefilling
    assert probe.closed is not None and probe.ended is None
    eng._decode(0, 0, 0, 0, jnp.array([0, 6]))
    assert probe.ended is None and reqs[0].cancel_at is None
    eng._decode(0, 0, 0, 0, jnp.array([9, 7]))        # it has decoded
    assert probe.ended is not None
    assert all(r.cancel_at == 0 for r in reqs)


def test_host_log_names_a_stall_and_where_it_fell(capsys):
    from chipbench import harness
    dt = np.arange(1.0, 3.0, 0.08)                # decode every 80 ms
    dt[dt > 2.0] += 1.5                            # the host stood still
    calls = ((np.zeros(0), None, None), (dt, None))
    gcw = harness.GcWatch()
    gcw.pauses.append((1.5, 0.002, 2))
    harness.log_host_time(calls, gcw, open_t=1.0, close_t=9.0)
    out = capsys.readouterr().out
    assert "max 1580.000 (0.960 s into the window)" in out
    assert "time over 2x median 1420.000 ms" in out
    assert "gc generation 2: 1 pauses in the window" in out
    assert "after prefill" not in out


def test_token_gap_reader_takes_the_traced_part_only():
    from chipbench import harness
    mod = harness.load_module(
        harness.BENCH_DIR / "metrics" / "token_gap_p95_ms.py",
        "chipbench_metric_token_gap_p95_ms")
    emits = [np.concatenate([np.arange(0.0, 10.0, 0.1),
                             np.arange(10.0, 40.0, 1.0)]),   # slow later
             np.arange(0.05, 10.0, 0.2), np.array([1.0])]
    ctx = types.SimpleNamespace(emits=emits, lo=5.0, hi=10.0,
                                note=lambda msg: None)
    v = mod.read(ctx)
    both = np.concatenate([np.diff(e[(e >= 5.0) & (e < 10.0)])
                           for e in emits])
    assert np.isclose(v, 1e3 * np.percentile(both, 95))
    assert np.isclose(v, 200.0)          # the 1 s gaps lie outside
    ctx.lo, ctx.hi = 50.0, 60.0
    assert mod.read(ctx) is None
