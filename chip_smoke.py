"""Bring-up smoke run of the paged serve path on a TPU chip.

Runs ``PagedServeEngine`` at the published widths of ``qwen2-7b``
(d_model 3584, 28/4 heads of 128, d_ff 18944, vocab 152064, QKV bias)
cut to 14 of its 28 layers: one stage of a two-stage pipeline
deployment, one chip per stage, no tensor split.  Weights are random
bf16 from ``--seed``.  Every Pallas kernel is compiled by Mosaic.

    python chip_smoke.py            # one chip: kernels, serve, logits
    python chip_smoke.py --chips 4  # four chips: the sharded forward only

Phases (one process; nothing here starts a child that touches JAX):

* kernels — flash_attention, decode_attention, paged_decode_attention
  and mfma_gemm at the model's widths with ``interpret=False``, each
  against its ``repro.kernels.ref`` oracle and checked to hold a
  ``tpu_custom_call``;
* serve — eight seeded requests (prompts of 100-1500 tokens, 16-64
  output tokens) through the engine; every request must end ``OK`` and
  every kernel dispatch decision must take the kernel;
* logits — a 512-token prompt through the full-sequence forward with
  the Pallas kernels against the XLA path on the same weights at
  ``highest`` matmul precision;
* sharded (``--chips 4`` only) — the same forward with its params placed
  by the logical-axis rules on a (1, 4) ("data", "model") mesh, against
  the forward on one chip.

Compile and wall seconds printed on the way are set-up facts, not
benchmark numbers.  The last line is one JSON object naming the device;
it is printed only when every phase passed.  Without a TPU the script
exits non-zero: there is no CPU fallback.  The persistent compile cache
lives in ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else in
``.jax_cache`` beside this file.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.arch import device_for_kind  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels import dispatch, ops, ref  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.models.model import forward, param_axes_rule  # noqa: E402
from repro.parallel.api import logical_to_spec, set_mesh  # noqa: E402
from repro.serve import OK, PagedServeEngine, Request  # noqa: E402

N_LAYERS = 14            # one stage of two: the depth one chip holds
MAX_BATCH, MAX_LEN, PREFILL_CHUNK = 8, 2048, 512
PROMPT = 512             # logit-comparison prompt length
KERNEL_TOL = 2e-2        # max |kernel - oracle| / max |oracle|, bf16 I/O
LOGIT_TOL = 5e-2         # max |pallas - xla| / max |xla| over all logits
MIN_TOP1 = 0.9           # share of positions whose argmax agrees


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def timed(fn, *args):
    """(result, seconds) of ``fn(*args)`` run to completion."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def report(phase: str, compile_s: float, wall_s: float, extra: str = ""):
    print(f"[{phase}] compile {compile_s:.2f} s, wall {wall_s:.2f} s"
          + (f", {extra}" if extra else ""), flush=True)


def setup_compile_cache() -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_tpu(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, found platform {devs[0].platform!r} "
            "(no CPU fallback)")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX sees "
                         f"{len(devs)} device(s)")
    return devs


def model_config(pallas_device: str):
    return dataclasses.replace(get_config("qwen2-7b"), n_layers=N_LAYERS,
                               use_pallas=True, pallas_device=pallas_device)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                     1e-30))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def kernel_phase(cfg, seed: int) -> None:
    """Each main-path kernel compiled by Mosaic, against its oracle."""
    H, KV, hd, bf = cfg.n_heads, cfg.n_kv_heads, cfg.hd, jnp.bfloat16
    B, T, page = MAX_BATCH, MAX_LEN, 512
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def rnd(*shape, dtype=bf):
        return jax.random.normal(next(ks), shape, jnp.float32).astype(dtype)

    nb = T // page
    tables = (1 + jax.random.permutation(next(ks), B * nb)).reshape(B, nb)
    lens = jax.random.randint(next(ks), (B,), 1, T + 1, jnp.int32)
    kw = dict(device=cfg.pallas_device, interpret=False)
    q_pf, k_pf, v_pf = (rnd(1, PROMPT, H, hd), rnd(1, PROMPT, KV, hd),
                        rnd(1, PROMPT, KV, hd))
    q_dec, k_dec, v_dec = rnd(B, H, hd), rnd(B, T, KV, hd), rnd(B, T, KV, hd)
    k_pool, v_pool = (rnd(B * nb + 1, KV, page, hd),
                      rnd(B * nb + 1, KV, page, hd))
    a, b, c = (rnd(PROMPT, cfg.d_model), rnd(cfg.d_model, cfg.d_ff),
               rnd(PROMPT, cfg.d_ff, dtype=jnp.float32))
    cases = {
        "flash_attention": (
            lambda q, k, v: ops.flash_attention(q, k, v, **kw),
            ref.flash_attention_ref, (q_pf, k_pf, v_pf)),
        "decode_attention": (
            lambda q, k, v, n: ops.decode_attention(q, k, v, n, **kw),
            ref.decode_attention_ref, (q_dec, k_dec, v_dec, lens)),
        "paged_decode_attention": (
            lambda q, k, v, t, n: ops.paged_decode_attention(
                q, k, v, t, n, **kw),
            ref.paged_decode_attention_ref,
            (q_dec, k_pool, v_pool, tables, lens)),
        "mfma_gemm": (
            lambda a, b, c: ops.mfma_gemm(a, b, c, **kw),
            ref.mfma_gemm_ref, (a, b, c)),
    }
    total_c = total_w = 0.0
    for name, (fn, oracle, args) in cases.items():
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        comp = time.perf_counter() - t0
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no tpu_custom_call in the compiled program")
        out, wall = timed(compiled, *args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        err = rel_err(out, want)
        check(bool(np.all(np.isfinite(np.asarray(out, np.float32)))),
              f"{name}: non-finite output")
        check(err <= KERNEL_TOL,
              f"{name}: max rel error {err:.3e} > {KERNEL_TOL:g}")
        report(f"kernel {name}", comp, wall, f"max rel err {err:.3e}")
        total_c += comp
        total_w += wall
    report("kernels", total_c, total_w)


def serve_phase(cfg, params, seed: int) -> None:
    """Eight requests through PagedServeEngine; every kernel decision
    must take the kernel and every request must end OK."""
    rng = np.random.default_rng(seed)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (int(s),))
                    .astype(np.int32), n_steps=int(n))
            for s, n in zip(rng.integers(100, 1501, MAX_BATCH),
                            rng.integers(16, 65, MAX_BATCH))]
    eng = PagedServeEngine(cfg, params, max_len=MAX_LEN, max_batch=MAX_BATCH,
                           device=cfg.pallas_device,
                           prefill_chunk=PREFILL_CHUNK)
    print(f"[serve] page {eng.page}, pool {eng.cache.n_blocks} blocks, "
          f"prefill chunk {PREFILL_CHUNK}, prompts "
          f"{[len(r.prompt) for r in reqs]}, outputs "
          f"{[r.n_steps for r in reqs]}", flush=True)
    with dispatch.decision_scope() as decs:
        # a short warm-up request compiles the prefill and decode steps
        warm = [Request(prompt=reqs[0].prompt[:8], n_steps=2)]
        (_, _), comp = timed(eng.run, warm)
        t0 = time.perf_counter()
        results, stats = eng.run(reqs)
        wall = time.perf_counter() - t0
    for name, dec in decs.items():
        print(f"[serve] dispatch {name}: use_kernel={dec.use_kernel} "
              f"({dec.reason})", flush=True)
        check(dec.use_kernel, f"{name} fell back to XLA: {dec.reason}")
    check("paged_decode_attention" in decs,
          "the decode step made no paged_decode_attention decision")
    bad = [(i, r.status, r.detail) for i, r in enumerate(results)
           if r.status != OK or len(r.tokens) != reqs[i].n_steps]
    check(not bad, f"requests not served OK: {bad}")
    toks = np.concatenate([r.tokens for r in results])
    check(bool(np.all((toks >= 0) & (toks < cfg.vocab_size))),
          "token ids out of the vocabulary")
    report("serve", comp, wall,
           f"{stats.tokens} tokens in {stats.ticks} ticks, "
           f"{stats.tokens / wall:.1f} tokens/s")


def prompt_tokens(cfg, seed: int):
    return jax.random.randint(jax.random.PRNGKey(seed + 1), (1, PROMPT), 0,
                              cfg.vocab_size, jnp.int32)


def logits_fn(cfg):
    return jax.jit(lambda p, t: forward(cfg, p, {"tokens": t},
                                        mode="train")[0])


def compare_logits(phase: str, got, want) -> None:
    got = np.asarray(got, np.float32)[0]
    want = np.asarray(want, np.float32)[0]
    check(bool(np.all(np.isfinite(got))), f"{phase}: non-finite logits")
    err = float(np.max(np.abs(got - want)))
    rel = err / float(np.max(np.abs(want)))
    top1 = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    print(f"[{phase}] logits {got.shape}: max abs err {err:.4f}, "
          f"max rel err {rel:.3e}, top-1 agreement {top1:.4f}", flush=True)
    check(rel <= LOGIT_TOL, f"{phase}: max rel err {rel:.3e} > {LOGIT_TOL:g}")
    check(top1 >= MIN_TOP1, f"{phase}: top-1 agreement {top1:.4f} < "
                            f"{MIN_TOP1:g}")


def logits_phase(cfg, params, seed: int) -> None:
    """The Pallas forward against the XLA path on the same weights."""
    toks = prompt_tokens(cfg, seed)
    xla_cfg = dataclasses.replace(cfg, use_pallas=False)
    t0 = time.perf_counter()
    with dispatch.decision_scope() as decs:
        f_pallas = logits_fn(cfg).lower(params, toks).compile()
    with jax.default_matmul_precision("highest"):
        f_xla = logits_fn(xla_cfg).lower(params, toks).compile()
    comp = time.perf_counter() - t0
    dec = decs.get("flash_attention")
    check(dec is not None and dec.use_kernel,
          f"prefill forward did not take flash_attention: {dec}")
    got, w1 = timed(f_pallas, params, toks)
    want, w2 = timed(f_xla, params, toks)
    compare_logits("logits", got, want)
    report("logits", comp, w1 + w2)


def sharded_phase(cfg, seed: int, devices) -> None:
    """The forward with params placed by the logical-axis rules on a
    (1, 4) mesh, against the same forward on one chip."""
    mesh = make_mesh((1, 4), ("data", "model"), devices=devices[:4])
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(seed))
    # the same leaf rules models.model.param_logical_axes applies
    shardings = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jax.sharding.NamedSharding(
            mesh, logical_to_spec(leaf.shape, param_axes_rule(path, leaf),
                                  mesh)), shapes)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(lambda k: init_params(cfg, k), out_shardings=shardings)(
            jax.random.PRNGKey(seed)))
    print(f"[sharded] params placed in {time.perf_counter() - t0:.2f} s; "
          f"wq spec {params['layers'][0]['mixer']['wq'].sharding.spec}",
          flush=True)
    toks = prompt_tokens(cfg, seed)
    t0 = time.perf_counter()
    with set_mesh(mesh), dispatch.decision_scope() as decs:
        f_mesh = logits_fn(cfg).lower(params, toks).compile()
    comp = time.perf_counter() - t0
    dec = decs.get("flash_attention")
    check(dec is not None and dec.use_kernel and dec.sharded,
          f"flash_attention did not run sharded: {dec}")
    local = dict(dec.local_dims)
    print(f"[sharded] flash_attention sharded={dec.sharded} local dims "
          f"{local}", flush=True)
    check(local["H"] == cfg.n_heads // 4 and local["KV"] == cfg.n_kv_heads // 4,
          f"unexpected local head split {local}")
    got, wall = timed(f_mesh, params, toks)
    one = jax.device_put(params, devices[0])
    t0 = time.perf_counter()
    f_one = logits_fn(cfg).lower(one, toks).compile()
    comp += time.perf_counter() - t0
    want, w1 = timed(f_one, one, toks)
    compare_logits("sharded", got, want)
    report("sharded", comp, wall + w1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded forward on a (1, 4) mesh")
    args = ap.parse_args(argv)

    cache = setup_compile_cache()
    devices = require_tpu(args.chips)
    kind = devices[0].device_kind
    spec = device_for_kind(kind)
    cfg = model_config(spec.name)
    print(f"device: {kind} -> {spec.name}, {len(devices)} visible; compile "
          f"cache {cache}", flush=True)
    print(f"config: {cfg.name} at published widths (d_model {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads of {cfg.hd}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}, qkv_bias {cfg.qkv_bias}); "
          f"{N_LAYERS} of 28 layers = one stage of a two-stage pipeline "
          "deployment, one chip per stage, no tensor split; random bf16 "
          f"weights from seed {args.seed}", flush=True)

    if args.chips == 4:
        sharded_phase(cfg, args.seed, devices)
    else:
        kernel_phase(cfg, args.seed)
        t0 = time.perf_counter()
        params = jax.block_until_ready(
            jax.jit(lambda k: init_params(cfg, k))(
                jax.random.PRNGKey(args.seed)))
        n = sum(x.size for x in jax.tree.leaves(params))
        print(f"[params] {n / 1e9:.2f} B parameters in "
              f"{time.perf_counter() - t0:.2f} s", flush=True)
        serve_phase(cfg, params, args.seed)
        logits_phase(cfg, params, args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
