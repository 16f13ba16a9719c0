"""The latent-attention, routed-expert family (``families/mla_moe.py``)
against the program, at a tiny size on the CPU.

The program serves through ``PagedServeEngine``: chunked prefill into
the latent pool (chunks that start mid-page, on both write paths),
decode through the paged kernel's latent mode, a request preempted and
recomputed, an expert share of four of sixteen experts.  Its greedy
tokens are held to the family's float32 reference; the fp8 control has
to fail the same comparison.
"""

import copy
import dataclasses
import functools
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import BENCH, PEAKS, harness, tiny_run
from chipbench import check

fam = harness.load_module(BENCH / "families" / "mla_moe.py",
                          "chipbench_family_mla_moe")
faults = harness.load_module(BENCH / "faults.py", "chipbench_faults")
CONF = json.loads((BENCH / "configs" / "deepseek-v2-lite.json").read_text())

# every width cut, the structure kept: latent 64 + rope 16, 4 heads,
# a dense first layer, 16 routed experts of which 4 are held (ids 4-7),
# top-3, two shared experts, YaRN as published; initializer_range
# scaled by sqrt(2048 / 128) so activations keep their published scale
TINY = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=4,
            qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
            kv_lora_rank=64, intermediate_size=256, moe_intermediate_size=64,
            n_routed_experts=4, num_experts_per_tok=3, vocab_size=512,
            num_hidden_layers=3, initializer_range=0.02 * 4.0,
            max_batch=4, max_len=1024, prefill_chunk=64, page=512,
            n_blocks=9)
SHARE = {"published": 16, "here": 4, "offset": 4, "why": "test share"}


def tiny_conf(**kw):
    conf = copy.deepcopy(CONF)
    conf.update(TINY, **kw)
    conf["reduced"]["n_routed_experts"] = dict(SHARE)
    return conf


def program(conf, dtype="float32"):
    from repro.models.config import ModelConfig
    d = fam.dims(conf)
    kw = dict(fam.program_config(conf), dtype=dtype)
    return d, ModelConfig(name="tiny-mla-moe", **kw, use_pallas=True)


def serve(conf, key, prompts, n_steps, *, page, chunk, n_blocks,
          dtype="float32", trace_dir=None):
    """Serve through the engine; (results, stats)."""
    from repro.serve import PagedServeEngine, Request
    d, mc = program(conf, dtype)
    params = jax.jit(functools.partial(fam.program_params, d))(key)
    if dtype == "float32":
        params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    eng = PagedServeEngine(mc, params, max_len=conf["max_len"], max_batch=3,
                           page=page, prefill_chunk=chunk,
                           n_blocks=n_blocks, check_invariants=True)
    reqs = [Request(prompt=p, n_steps=n) for p, n in zip(prompts, n_steps)]
    if trace_dir is None:
        return eng.run(reqs)
    jax.profiler.start_trace(str(trace_dir))
    try:
        return eng.run(reqs)
    finally:
        jax.profiler.stop_trace()


def _prompts(d, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, d.V, n).astype(np.int32) for n in lengths]


def _sample(results, prompts):
    return check.draw(results, prompts, 0, 10**9, len(results))


@pytest.mark.parametrize("chunk,n_blocks", [
    (96, 7),     # row-scatter writes, chunks mid-page; pool forces preemption
    (64, 13),    # aligned writes (the chunk divides the page), no preemption
])
def test_program_matches_reference_through_the_latent_pool(chunk, n_blocks):
    """Greedy tokens served in float32 against the float32 reference: the
    served token's reference logit may lie below the best by float32
    rounding only (1e-3 of the logits' scale, where near-ties of the
    random head sit).  The fp8 control misses by far more."""
    conf = tiny_conf()
    d, _ = program(conf)
    key = fam.seed_key(2**33 + 11)
    prompts = _prompts(d, (300, 150, 40))
    results, stats = serve(conf, key, prompts, (150, 200, 12), page=128,
                           chunk=chunk, n_blocks=n_blocks)
    assert all(r.status == "OK" for r in results)
    if n_blocks == 7:
        assert stats.preemptions > 0              # evicted and recomputed
    s = _sample(results, prompts)
    ref = fam.reference_logits(d, key, s.seqs, s.rows, row_len=1024)
    scale = max(float(np.abs(r).max()) for r in ref)
    gap = check.served_gap(ref, s.served)
    assert gap <= 1e-3 * scale, (gap, scale)
    ctl = fam.reference_logits(d, key, s.seqs, s.rows, row_len=1024,
                               quant="fp8")
    assert check.picked_gap(ref, ctl) > 30 * 1e-3 * scale


def _tiny_longdoc():
    """The cell at the tiny size: its own files, widths and lengths cut."""
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    c = harness.resolve(bench, "deepseek-v2-lite.longdoc")
    c.conf = tiny_conf()
    c.mix = dict(c.mix, prompt={"dist": "lognormal", "median": 300,
                                "sigma": 0.6, "min": 40, "max": 700},
                 output={"dist": "lognormal", "median": 30, "sigma": 0.6,
                         "min": 4, "max": 100}, requests=48)
    c.limits = dict(c.limits, sample_tokens=48, sample_requests=3)
    return c


def test_bf16_program_through_the_harness_is_correct():
    """The timed path at a tiny size: the cell's own files, weights served
    in bf16, a window, the sample, the verdict — and every per-layer
    metric of the cell that the CPU can read."""
    out = tiny_run(_tiny_longdoc(), 2**35 + 3, seconds=1.0)
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"output_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_is_not_correct(fault):
    """Each of ``faults.py``'s faults, planted in the timed path of the
    same tiny run, reads above the cell's limit: the latent kernel
    reading the wrong pages or returning zeros, the held experts
    skipped or given the next share's assignments."""
    import jax
    from repro.kernels import dispatch
    dispatch.reset_decisions()
    out = faults.run_faulted(_tiny_longdoc(), fault, 2**35 + 3, 1.0,
                             jax.devices(), peaks=PEAKS, pallas_device=None)
    assert dispatch.last_decisions()["paged_decode_attention"].use_kernel
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


def test_routed_rows_on_the_spans_equal_the_reference_count(tmp_path):
    """Every chunk's and decode step's ``routed_rows`` summed over the run
    equals the reference's assignments to the held experts over the same
    positions: each prompt token once, then each token fed back."""
    conf = tiny_conf()
    d, _ = program(conf)
    key = fam.seed_key(77)
    prompts = _prompts(d, (130, 60), seed=3)
    n_steps = (9, 14)
    results, _ = serve(conf, key, prompts, n_steps, page=128, chunk=48,
                       n_blocks=13, trace_dir=tmp_path)
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    got, calls = 0, 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("serve.prefill", "serve.decode"):
                        got += int(dict(e.stats)["routed_rows"])
                        calls += 1
    assert calls > 0
    fed = [np.concatenate([p, r.tokens[:-1]]) for p, r in zip(prompts,
                                                                results)]
    want = fam.held_routed_rows(d, key, fed, row_len=1024)
    assert got == want > 0


def test_expert_shares_add_up_to_the_uncut_layer():
    """The reference's share cut: four shares of four experts each, the
    shared experts counted once, equal the layer holding all sixteen."""
    conf = tiny_conf()
    d = fam.dims(conf)
    key = fam.seed_key(5)
    whole = dataclasses.replace(d, held=16, offset=0)
    w = jax.jit(lambda k: fam.layer_weights(whole, k, 1, True))(key)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, d.D))
    with jax.default_matmul_precision("highest"):
        y_all, n_all = fam._experts(whole, w["ffn"], x, None)
        sh = w["ffn"]["shared"]
        shared = fam._swiglu(x, sh["wg"], sh["wi"], sh["wo"], None)
        total, n = shared, 0
        for off in range(0, 16, 4):
            part = dataclasses.replace(d, held=4, offset=off)
            wp = jax.jit(lambda k: fam.layer_weights(part, k, 1, True))(key)
            wp = jax.tree.map(lambda a: a.astype(jnp.float32), wp)
            y, c = fam._experts(part, wp["ffn"], x, None)
            total, n = total + (y - shared), n + c
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_all),
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(n) == np.asarray(n_all)).all()
    assert (np.asarray(n_all) == d.topk).all()


def test_yarn_frequencies_match_the_closed_form():
    """The family's and the program's YaRN inverse frequencies at the
    published rope width: the correction dims of 32 and 1 rotations over
    4096 positions are 10.47 and 22.5 (floor 10, ceil 23), so pairs 0-10
    keep theta^(-2i/64), pairs 23-31 turn 40 times slower, and the ramp
    between mixes the two."""
    from repro.models.config import YarnSpec
    from repro.models.layers import rope_inv_freq, yarn_mscale
    d = fam.dims(CONF)
    i = np.arange(32, dtype=np.float64)
    base = 10000.0 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = base / 40 * ramp + base * (1 - ramp)
    np.testing.assert_allclose(fam.yarn_inv_freq(d), want, rtol=1e-12)
    got = rope_inv_freq(64, 10000.0, YarnSpec(
        factor=40, original_max_position=4096, beta_fast=32, beta_slow=1,
        mscale=0.707, mscale_all_dim=0.707))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6)
    assert want[10] == base[10] and want[23] == base[23] / 40
    m = 0.1 * 0.707 * np.log(40) + 1
    assert yarn_mscale(40, 0.707) == pytest.approx(m)
    assert fam.softmax_scale(d) == pytest.approx(m * m / np.sqrt(192))


def test_published_figures():
    """Hand figures of the configuration as it runs on one chip."""
    d = fam.dims(CONF)
    assert (d.L, d.D, d.H, d.R, d.rope, d.E, d.held, d.topk) == (
        27, 2048, 16, 512, 64, 64, 8, 6)
    assert fam.attn_params(d) == 13_762_560
    assert fam.ffn_params(d, True) == 86_638_592
    assert fam.n_params(d) == CONF["memory"]["params"]
    assert fam.n_params(d) == pytest.approx(3.111e9, rel=1e-3)
    assert fam.kv_bytes_per_token(d) == 31_104
    kv = np.array([1, 8000, 16384])
    flops, byts = fam.decode_attention_work(d, kv)
    assert flops == 2 * 16 * kv.sum() * (576 + 512)
    assert byts == kv.sum() * 576 * 2 + 3 * 16 * (576 + 512) * 2
    f, b = fam.moe_gmm_work(d, np.array([0, 100]))
    w = 26 * 3 * 8 * 2048 * 1408 * 2
    assert list(f) == [0, 100 * 6 * 2048 * 1408]
    assert list(b) == [w, w + 100 * 3 * (2048 + 1408) * 2]
    start, n = 5000, 37
    per_token = sum(fam.token_flops(d, start + i + 1, False)
                    for i in range(n)) + 2 * d.D * d.V
    assert fam.chunk_flops(d, start, n) == pytest.approx(per_token, rel=1e-12)


def test_program_config_builds_the_share():
    _, mc = program(tiny_conf())
    assert mc.moe.n_experts == 16 and mc.moe.held == 4
    assert mc.moe.held_offset == 4 and not mc.moe.norm_topk
    assert mc.rope_scaling.factor == 40 and mc.mla.kv_lora_rank == 64
    assert mc.norm_eps == 1e-6


def _roofline_ctx(rows, ops):
    from chipbench import spans
    from chipbench import trace as tm

    class Cell:
        family = fam
    tr = tm.Trace([tm.Events.of(ops)], [tm.Events.of([])],
                  tm.Events.of([]), 0.0, 1000.0)
    ctx = harness.Context(Cell(), fam.dims(tiny_conf()), PEAKS, 4, 0.0,
                          1000e-9, None, None, [], tr, [])
    ctx.serve_ticks = spans.whole_ticks([rows], tr.t0_ns, tr.t1_ns)
    return ctx


def test_moe_gmm_roofline_on_hand_made_spans():
    """Two whole ticks, a chunk and two decode steps carrying routed_rows;
    three moe_gmm kernels inside them (one outside, one in a cut tick,
    and another op): the share is the summed per-call bound over the
    kernels' summed time.  Spans without the counter (a dense program,
    or the parent's) give nothing."""
    reader = harness.load_module(BENCH / "metrics" / "moe_gmm_roofline.py",
                                 "chipbench_metric_moe_gmm_roofline")
    rows = [("serve.tick", 100.0, 300.0, {"tick": 1}),
            ("serve.prefill", 110.0, 200.0, {"routed_rows": 300}),
            ("serve.prefill.wait", 150.0, 150.0, {}),
            ("serve.decode", 320.0, 70.0, {"routed_rows": 12}),
            ("serve.decode.wait", 330.0, 50.0, {}),
            ("serve.tick", 450.0, 300.0, {"tick": 2}),
            ("serve.decode", 460.0, 200.0, {"routed_rows": 10}),
            ("serve.decode.wait", 470.0, 180.0, {}),
            ("serve.tick", 900.0, 200.0, {"tick": 3}),       # cut at t1
            ("serve.decode", 910.0, 100.0, {"routed_rows": 11})]
    ops = [("%moe_gmm.3 = bf16[4,128,64] custom-call()", 50.0, 20.0),
           ("%moe_gmm.3 = bf16[4,128,64] custom-call()", 160.0, 40.0),
           ("%fusion.1 = f32[] fusion()", 210.0, 30.0),
           ("%moe_gmm.4 = bf16[4,128,128] custom-call()", 340.0, 20.0),
           ("%moe_gmm.3 = bf16[4,128,64] custom-call()", 480.0, 60.0),
           ("%moe_gmm.3 = bf16[4,128,64] custom-call()", 950.0, 10.0)]
    ctx = _roofline_ctx(rows, ops)
    d = ctx.d
    f, b = fam.moe_gmm_work(d, np.array([300, 12, 10]))
    bound = np.maximum(f / PEAKS["bf16_flops"], b / PEAKS["hbm_bytes_per_s"])
    want = 100.0 * bound.sum() / 120e-9
    assert reader.read(ctx) == pytest.approx(want, rel=1e-9)
    assert "3 kernel events for 3 calls (2 decode, 1 prefill)" in ctx.notes[0]
    bare = [(n, s, t, {k: v for k, v in a.items() if k != "routed_rows"})
            for n, s, t, a in rows]
    assert reader.read(_roofline_ctx(bare, ops)) is None


@pytest.mark.parametrize("name,program", [
    ("decode_step_ms.longdoc", "jit__step"),
    ("prefill_chunk_ms.longdoc", "jit__pstep"),
])
def test_step_time_readers_of_the_cell(name, program):
    """The cell's step times: the mean device time of its program's
    executions inside the window, in ms; nothing without a device plane."""
    from chipbench import trace as tm
    reader = harness.load_module(BENCH / "metrics" / f"{name}.py",
                                 f"chipbench_metric_{name}")
    mods = [(program, 100.0, 40e6), (program, 50e6, 20e6),
            ("jit__other", 80e6, 5e6), (program, 2e9, 1e6)]   # last: outside
    tr = tm.Trace([tm.Events.of([])], [tm.Events.of(mods)],
                  tm.Events.of([]), 0.0, 1e9)
    ctx = harness.Context(None, None, PEAKS, 4, 0.0, 1.0, None, None, [],
                          tr, [])
    assert reader.read(ctx) == pytest.approx(30.0)
    assert ctx.notes and "2 executions" in ctx.notes[0]
    bare = tm.Trace([], [], tm.Events.of([]), 0.0, 1e9)
    assert reader.read(harness.Context(None, None, PEAKS, 4, 0.0, 1.0, None,
                                       None, [], bare, [])) is None
