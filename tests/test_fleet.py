"""Fleet capacity planner: queueing model, frontier, calibration.

Three layers under test, in increasing integration order:

1. the closed-form queueing model (monotonicity, SLO binding, the
   bisection) on hand-built :class:`ServeCost` fixtures — no perf, no
   jax;
2. the scenario registry + the frontier sweep (analytic cost graphs
   through ``perf.predict``/``perf.sweep``), including the overlay
   what-if composing into the frontier and the compute <-> collective
   bound switch;
3. calibration: ``simulate_trace`` must reproduce the *exact* tick
   accounting of a real ``PagedServeEngine`` replay, and tick costs
   fitted from measured walls must predict a held-out trace's
   per-token latency within the calibration band.
"""

import dataclasses
import math
import time

import numpy as np
import pytest

from repro.arch.overlay import IDENTITY, Overlay
from repro.fleet import (SLO, ServeCost, TickCosts, TrafficScenario,
                         fit_tick_costs, frontier, get_scenario,
                         list_scenarios, max_sustainable_qps, p99_latency_s,
                         register_scenario, serve_cost, simulate_trace,
                         token_latency_s)
from repro.fleet.capacity import analytic_graphs
from repro.fleet.cli import main as fleet_main
from repro.fleet.cli import parse_overlay

DEVICES = ("mi200", "mi300", "mi300x", "tpu_v5e", "tpu_v5p")


def _cost(decode_ms=5.0, prefill_ms=20.0, max_batch=8, chunks=2):
    """A hand-built ServeCost: the queueing model needs nothing else."""
    return ServeCost(scenario="synthetic", device="unit", max_batch=max_batch,
                     decode_tick_s=decode_ms / 1e3,
                     prefill_chunk_s=prefill_ms / 1e3,
                     decode_bound="memory", prefill_bound="compute",
                     prefill_chunks_per_request=chunks)


def _scn(**kw):
    kw.setdefault("name", "unit")
    kw.setdefault("prompt_mean", 512)
    kw.setdefault("output_mean", 64)
    kw.setdefault("max_batch", 8)
    kw.setdefault("prefill_chunk", 256)
    return TrafficScenario(**kw)


# ---------------------------------------------------------------------------
# 1. Queueing model
# ---------------------------------------------------------------------------

def test_latency_strictly_monotonic_in_qps():
    scn, cost = _scn(), _cost()
    qs = np.linspace(0.0, 4.0, 60)
    p99 = [p99_latency_s(q, scn, cost) for q in qs]
    tok = [token_latency_s(q, scn, cost) for q in qs]
    assert all(b > a for a, b in zip(p99, p99[1:]))
    assert all(b >= a for a, b in zip(tok, tok[1:]))
    # overload is infinite, idle equals the bare decode tick
    assert p99[0] == pytest.approx(cost.decode_tick_s)
    assert p99_latency_s(1e9, scn, cost) == math.inf


def test_burstiness_inflates_tail_not_idle():
    scn_calm, cost = _scn(burstiness=1.0), _cost()
    scn_burst = _scn(burstiness=4.0)
    assert p99_latency_s(0.0, scn_calm, cost) == \
        p99_latency_s(0.0, scn_burst, cost)
    assert p99_latency_s(1.0, scn_burst, cost) > \
        p99_latency_s(1.0, scn_calm, cost)


def test_max_qps_is_zero_when_idle_device_misses_slo():
    scn = _scn(slo=SLO(p99_token_ms=1.0))       # < the 5ms decode tick
    assert max_sustainable_qps(scn, _cost()) == 0.0


def test_slo_binding_switches_latency_vs_throughput():
    """Loose SLO: the binding constraint is overload (rho -> 1), so
    max_qps approaches the work-conservation ceiling.  Tight SLO: the
    binding constraint is the latency target, max_qps sits well below
    the ceiling and p99 lands ON the target."""
    cost = _cost()
    ceiling = 1.0 / (2 * cost.prefill_chunk_s
                     + 64 * cost.decode_tick_s / cost.max_batch)
    loose = max_sustainable_qps(_scn(slo=SLO(p99_token_ms=1e6)), cost)
    tight_scn = _scn(slo=SLO(p99_token_ms=8.0))
    tight = max_sustainable_qps(tight_scn, cost)
    assert loose == pytest.approx(ceiling, rel=1e-3)
    assert tight < 0.9 * ceiling
    assert p99_latency_s(tight, tight_scn, cost) * 1e3 == \
        pytest.approx(8.0, rel=1e-3)
    # and the ttft SLO can be the binding one instead
    ttft_scn = _scn(slo=SLO(p99_token_ms=1e6, ttft_p99_ms=45.0))
    ttft = max_sustainable_qps(ttft_scn, cost)
    assert 0.0 < ttft < loose


def test_bisection_result_is_the_feasibility_boundary():
    scn, cost = _scn(slo=SLO(p99_token_ms=25.0)), _cost()
    q = max_sustainable_qps(scn, cost)
    assert p99_latency_s(q, scn, cost) <= scn.slo.p99_token_ms / 1e3
    assert p99_latency_s(q * 1.01, scn, cost) > scn.slo.p99_token_ms / 1e3


# ---------------------------------------------------------------------------
# 2. Scenario registry + cost graphs + frontier
# ---------------------------------------------------------------------------

def test_builtin_scenarios_registered():
    assert {"chat", "long_context", "bursty_batch"} <= set(list_scenarios())
    chat = get_scenario("chat")
    assert chat.trace == "base" and chat.slo.p99_token_ms == 200.0
    assert chat.prefill_chunks_per_request == 2


def test_scenario_registry_roundtrip_and_duplicates():
    scn = register_scenario(_scn(name="test-roundtrip"))
    try:
        assert get_scenario("test-roundtrip") is scn
        assert "test-roundtrip" in list_scenarios()
        with pytest.raises(ValueError, match="already registered"):
            register_scenario(_scn(name="test-roundtrip"))
    finally:
        from repro.fleet import scenario as mod
        del mod._REGISTRY["test-roundtrip"]
    with pytest.raises(KeyError, match="unknown scenario"):
        get_scenario("test-roundtrip")


def test_scenario_validation():
    with pytest.raises(ValueError, match="qps"):
        _scn(name="bad", qps=0.0)
    with pytest.raises(ValueError, match="output_mean"):
        _scn(name="bad", output_mean=0)


def test_analytic_graph_aggregates_consistent_with_ops():
    """The roofline engine consumes the aggregates, the MFMA engines the
    per-op list — both views of the same graph must agree."""
    for name in list_scenarios():
        graphs = analytic_graphs(get_scenario(name))
        for kind, g in graphs.items():
            dot_flops = sum(op.count * op.flops for op in g.ops)
            assert g.flops == pytest.approx(dot_flops), (name, kind)
            wire = sum(op.count * op.wire_bytes for op in g.ops)
            assert g.collective_wire == pytest.approx(wire)
            assert g.bytes_accessed > 0 and g.flops > 0


def test_tensor_parallel_adds_collectives_and_shrinks_memory():
    base = _scn(name="tp1", arch="yi-34b", tp=1)
    tp4 = _scn(name="tp4", arch="yi-34b", tp=4)
    g1 = analytic_graphs(base)["decode"]
    g4 = analytic_graphs(tp4)["decode"]
    assert g1.collective_wire == 0.0
    assert g4.collective_wire > 0.0
    assert any(op.kind == "collective" and op.opcode == "all-reduce"
               and op.group == 4 for op in g4.ops)
    # sharding 4 ways streams roughly a quarter of the weights
    assert g4.bytes_accessed < 0.5 * g1.bytes_accessed


def test_serve_cost_bound_switches_compute_to_collective():
    """A tp=8 short-context batch is compute-bound at baseline (the LM
    head GEMM); an overlay that speeds the matrix units 8x leaves the
    per-layer all-reduces as the bottleneck — the planner must surface
    the switch, because it changes what a faster interconnect buys."""
    scn = _scn(name="tp8-probe", arch="qwen2-7b", prompt_mean=16,
               output_mean=16, max_batch=256, prefill_chunk=16, tp=8)
    base = serve_cost(scn, "mi300")
    fast_mfma = serve_cost(scn, "mi300", overlay=Overlay(mfma_scale=0.125))
    assert base.decode_bound == "compute"
    assert fast_mfma.decode_bound == "collective"
    assert fast_mfma.decode_tick_s < base.decode_tick_s


def test_serve_cost_bound_switches_memory_to_compute():
    chat = get_scenario("chat")
    assert serve_cost(chat, "mi300").decode_bound == "memory"
    assert serve_cost(chat, "mi300",
                      overlay=Overlay(bw_scale=100.0)).decode_bound \
        == "compute"


def test_frontier_all_devices_all_scenarios_finite():
    """Every registered built-in scenario must yield a finite, feasible
    frontier on every catalog device (also linted standalone by
    scripts/check_device_specs.py)."""
    rep = frontier(list_scenarios(), DEVICES)
    assert len(rep.rows) == len(list_scenarios()) * len(DEVICES)
    for r in rep.rows:
        assert r.feasible, (r.scenario, r.device)
        assert 0 < r.max_qps < math.inf
        assert 1 <= r.devices_needed < 1000
        assert r.p99_token_ms <= r.slo_p99_ms
        assert math.isfinite(r.cost_per_mtok)
        assert r.bound in ("compute", "memory", "collective", "matrix")
    for name in list_scenarios():
        assert rep.best(name) is not None


def test_frontier_deterministic():
    a = frontier("chat", ("mi300", "tpu_v5p"))
    b = frontier("chat", ("mi300", "tpu_v5p"))
    assert a.rows == b.rows


def test_overlay_composes_into_frontier():
    """The acceptance what-if: an mfma_scale overlay must move the
    frontier, and the overlay rows must be labelled as such."""
    rep = frontier("chat", ("mi300",),
                   overlays=[IDENTITY, Overlay(mfma_scale=2.0)])
    base, what_if = rep.rows
    assert base.overlay == "baseline" and what_if.overlay == "mfma x2"
    assert what_if.max_qps != base.max_qps
    assert what_if.prefill_chunk_ms != base.prefill_chunk_ms


def test_frontier_infeasible_slo_reports_inf():
    scn = dataclasses.replace(get_scenario("chat"), name="chat-impossible",
                              slo=SLO(p99_token_ms=1e-3))
    rep = frontier(scn, ("mi300",))
    row = rep.rows[0]
    assert not row.feasible
    assert row.devices_needed == 0 and row.cost_per_mtok == math.inf
    assert rep.best("chat-impossible") is None
    assert "inf" in rep.table()


def test_fleet_report_table_shape():
    rep = frontier("chat", ("mi300", "mi300x"))
    lines = rep.table().splitlines()
    assert len(lines) == 2 + 2                      # header + rule + rows
    assert lines[0].startswith("| scenario | device |")
    d = rep.as_dict()
    assert {r["device"] for r in d["rows"]} == {"mi300", "mi300x"}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_smoke_small(capsys):
    assert fleet_main(["--small", "--devices", "mi300,mi300x,tpu_v5p"]) == 0
    out = capsys.readouterr().out
    assert "| scenario | device |" in out
    assert "mi300" in out and "mi300x" in out and "tpu_v5p" not in out
    assert "cheapest feasible device" in out


def test_cli_json_and_overrides(capsys):
    assert fleet_main(["--scenario", "chat", "--devices", "mi300",
                       "--slo-p99-ms", "50", "--qps", "100",
                       "--json"]) == 0
    import json
    rows = json.loads(capsys.readouterr().out)["rows"]
    assert rows[0]["slo_p99_ms"] == 50.0
    assert rows[0]["scenario"] == "chat"


def test_cli_overlay_parsing():
    ov = parse_overlay("mfma_scale=2, bw_scale=1.5")
    assert ov.mfma_scale == 2.0 and ov.bw_scale == 1.5
    with pytest.raises(ValueError, match="unknown overlay knob"):
        parse_overlay("warp_scale=2")
    with pytest.raises(ValueError, match="knob=value"):
        parse_overlay("mfma_scale")


# ---------------------------------------------------------------------------
# 3. Calibration against the real PagedServeEngine
# ---------------------------------------------------------------------------

def _sim_kwargs(eng):
    return dict(max_len=eng.max_len, max_batch=eng.max_batch, page=eng.page,
                n_blocks=eng.cache.n_blocks, prefill_chunk=eng.prefill_chunk)


@pytest.fixture(scope="module")
def paged_engine():
    jax = pytest.importorskip("jax")
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve import PagedServeEngine
    cfg = get_config("qwen2-7b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = PagedServeEngine(cfg, params, max_len=160, max_batch=2,
                           page=128, prefix_cache=False)
    return cfg, eng


def _trace(cfg, specs, seed=7):
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, (s,))
                    .astype(np.int32), n_steps=n, arrival=a)
            for s, n, a in specs]


# trace mixes with linearly independent (decode, prefill, tick) columns:
# decode-heavy, prefill-heavy (chunked long prompts), arrival-gapped
# (overhead-only ticks while the queue waits), and backpressured
_TRACES = {
    "decode_heavy": [(6, 24, 0), (9, 30, 0), (7, 18, 1)],
    "prefill_heavy": [(130, 3, 0), (120, 2, 0), (96, 2, 1)],
    "gapped": [(8, 6, 0), (10, 5, 14), (12, 4, 30)],
    "mixed": [(64, 10, 0), (9, 20, 0), (100, 4, 2), (12, 12, 3)],
}


def test_simulate_trace_matches_engine_tick_accounting(paged_engine):
    """The dry drive and the engine's data plane call the scheduler's
    phases alike, so they agree EXACTLY on ticks, decode steps and
    prefill chunks — that is what makes fitted tick costs transferable
    to unseen traces."""
    cfg, eng = paged_engine
    for name, specs in _TRACES.items():
        trace = _trace(cfg, specs)
        _, stats = eng.run(trace)
        sim = simulate_trace(trace, **_sim_kwargs(eng))
        for field in ("requests", "tokens", "ticks", "decode_steps",
                      "prefill_chunks"):
            assert getattr(sim, field) == stats[field], (name, field)
        assert sim.occupancy_max == pytest.approx(stats["occupancy_max"])


def test_simulate_trace_models_block_backpressure():
    """Third request must wait for a retirement on a 2-block pool —
    visible as extra ticks vs an uncontended pool (no jax needed)."""
    rng = np.random.default_rng(0)

    def mk(n_reqs):
        from repro.serve.api import Request
        return [Request(prompt=rng.integers(0, 64, (8,)).astype(np.int32),
                        n_steps=4, arrival=0) for _ in range(n_reqs)]

    tight = simulate_trace(mk(3), max_len=64, max_batch=3, page=128,
                           n_blocks=3, prefill_chunk=32)
    roomy = simulate_trace(mk(3), max_len=64, max_batch=3, page=128,
                           n_blocks=4, prefill_chunk=32)
    assert tight.ticks > roomy.ticks
    assert tight.decode_steps >= roomy.decode_steps


def test_simulate_trace_validates_like_the_engine():
    from repro.serve.api import Request
    big = Request(prompt=np.zeros(120, np.int32), n_steps=16)
    with pytest.raises(ValueError, match="max_len"):
        simulate_trace([big], max_len=64, max_batch=2, page=64)
    with pytest.raises(ValueError, match="blocks"):
        simulate_trace([big], max_len=192, max_batch=2, page=128, n_blocks=2)


def test_fit_tick_costs_recovers_exact_synthetic_costs():
    true = TickCosts(decode_s=3e-3, prefill_s=1.5e-3, overhead_s=2e-4)
    obs = []
    for d, p, t in [(10, 2, 13), (3, 9, 12), (20, 5, 26), (7, 7, 20)]:
        from repro.fleet.capacity import SimStats
        st = SimStats(requests=1, tokens=d + 1, ticks=t, decode_steps=d,
                      prefill_chunks=p, occupancy_mean=0.5, occupancy_max=1.0)
        obs.append((st, true.wall_s(st)))
    fit = fit_tick_costs(obs)
    assert fit.decode_s == pytest.approx(true.decode_s, rel=1e-6)
    assert fit.prefill_s == pytest.approx(true.prefill_s, rel=1e-6)
    assert fit.overhead_s == pytest.approx(true.overhead_s, rel=1e-6)
    with pytest.raises(ValueError, match=">= 3"):
        fit_tick_costs(obs[:2])


def test_fitted_costs_predict_heldout_trace_latency(paged_engine):
    """The acceptance band: tick costs fitted on three probe traces must
    predict a held-out trace's measured per-token latency within
    [0.5, 2.0]x — the tolerance that makes the planner's capacity
    numbers trustworthy at fleet granularity."""
    cfg, eng = paged_engine
    eng.run(_trace(cfg, [(8, 3, 0)]))             # warm the jit caches

    def timed(specs, seed):
        trace = _trace(cfg, specs, seed=seed)
        t0 = time.perf_counter()
        _, stats = eng.run(trace)
        return stats, time.perf_counter() - t0

    obs = [timed(_TRACES[k], seed)
           for seed, k in enumerate(("decode_heavy", "prefill_heavy",
                                     "gapped"))]
    costs = fit_tick_costs(obs)
    held_stats, held_wall = timed(_TRACES["mixed"], seed=99)
    predicted = costs.token_latency_s(held_stats)
    measured = held_wall / held_stats["tokens"]
    assert 0.5 * measured <= predicted <= 2.0 * measured, \
        f"predicted {predicted * 1e3:.2f}ms/tok vs measured " \
        f"{measured * 1e3:.2f}ms/tok"
