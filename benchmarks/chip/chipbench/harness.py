"""One run of one cell: set-up, the measured window, the check, the line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration file, ``traffic/<mix>.json``, ``cells/<cell>.json`` (the
correctness limit and sample size), the family module the configuration
names under ``reference`` (``families/<family>.py``: weights, reference,
work counts), ``metrics/<metric>.py`` for each per-layer metric, and
``peaks.json`` for the chip.

Set-up (``setup_s``, from process start to the window's opening):
weights made on the device from the seed in one jitted call, the engine
built and both of its programs compiled by a two-token warm-up request,
then the run itself until every request of the first wave has emitted
its first token.  The window lasts ``--seconds``; the load then goes on
until every request admitted in the window has its first token, the
probe cancels every request, the engine retires them at its next tick,
and only events inside the window count (TTFT: every request admitted
in it).  After it, ``memory_peak_bytes`` is read, the
program's state is freed, and the float32 reference checks a sample of
the served requests.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import check, traffic, window
from chipbench import trace as trace_mod
from chipbench.probe import Probe

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
CACHE_DIR = BENCH_DIR / ".jax_cache"
TRACE_DIR = BENCH_DIR / ".trace"
TRACE_SECONDS = 10.0
WARM_PROMPT = 8
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class RunFailure(RuntimeError):
    """The run cannot report: no chip, a drained queue, a broken mapping."""


def log(msg: str) -> None:
    print(msg, flush=True)


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise RunFailure(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    conf: Dict
    mix: Dict
    limits: Dict
    family: object
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: Dict, name: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RunFailure(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    conf = json.loads((ROOT / cfg["file"]).read_text())
    mix = traffic.load(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    limits = json.loads((BENCH_DIR / "cells" / f"{name}.json").read_text())
    family = load_module(BENCH_DIR / "families" / f"{conf['reference']}.py",
                         f"chipbench_family_{conf['reference']}")
    return Cell(name, int(w["chips"]), w["config"], conf, mix, limits, family,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


def setup_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def require_chip(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RunFailure(f"needs a TPU, JAX found platform "
                         f"{devs[0].platform!r}; there is no CPU fallback")
    if len(devs) < chips:
        raise RunFailure(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader sees."""
    cell: Cell
    d: object                      # the family's Dims
    peaks: Dict
    max_batch: int
    lo: float                      # host window the metric covers
    hi: float
    prefills: tuple                # (times, starts, n_valid)
    decodes: tuple                 # (times, lens (n, B))
    emits: List[np.ndarray]        # each request's emit times
    trace: Optional[trace_mod.Trace]
    notes: List[str]

    def note(self, msg: str) -> None:
        self.notes.append(msg)


class GcWatch:
    """Notes when the interpreter's garbage collector ran, and how long."""

    def __init__(self):
        self.pauses: List[tuple] = []      # (start, seconds, generation)
        self._t0 = 0.0

    def __call__(self, phase: str, info: Dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._t0 = now
        else:
            self.pauses.append((self._t0, now - self._t0, info["generation"]))


def log_host_time(calls, gcw: GcWatch, open_t: float, close_t: float) -> None:
    """Where the window's wall time went beyond the device calls: the gap
    from each dispatch to the next, by the kind of call it follows (the
    engine waits for every call, so a gap is that call's device time and
    the host's time up to the next), and the collector's pauses."""
    (pt, _, _), (dt, _) = calls
    t = np.concatenate([pt, dt])
    kind = np.concatenate([np.zeros(len(pt), int), np.ones(len(dt), int)])
    order = np.argsort(t)
    t, kind = t[order], kind[order]
    inside = (t > open_t) & (t <= close_t)
    t, kind = t[inside], kind[inside]
    gaps, after = np.diff(t), kind[:-1]
    for k, name in ((1, "decode"), (0, "prefill")):
        g = gaps[after == k] * 1e3
        if len(g):
            med = float(np.median(g))
            at = t[:-1][after == k][np.argmax(g)] - open_t
            log(f"[host] after {name}: n={len(g)} gap median {med:.3f} ms, "
                f"p99 {np.percentile(g, 99):.3f}, max {g.max():.3f} "
                f"({at:.3f} s into the window), "
                f"time over 2x median {np.sum(np.maximum(g - 2 * med, 0)):.3f}"
                " ms")
    inw = [(s, d, gen) for s, d, gen in gcw.pauses if open_t < s <= close_t]
    for gen in (0, 1, 2):
        d = [x for _, x, g in inw if g == gen]
        if d:
            log(f"[host] gc generation {gen}: {len(d)} pauses in the window, "
                f"{sum(d) * 1e3:.3f} ms in all, longest {max(d) * 1e3:.3f} ms")


def _warm_up(eng, Request, vocab: int):
    from repro.kernels import dispatch
    with dispatch.decision_scope() as decs:
        eng.run([Request(prompt=np.arange(WARM_PROMPT, dtype=np.int32)
                         % vocab, n_steps=2)])
    for name, dec in sorted(decs.items()):
        log(f"[dispatch] {name}: use_kernel={dec.use_kernel} ({dec.reason})")


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, *, peaks: Optional[Dict] = None,
        pallas_device: Optional[str] = "from-kind",
        breaker: Optional[Callable] = None, control: bool = False) -> Dict:
    """Serve one window and return the result line as a dict.

    ``peaks``, ``pallas_device`` and ``breaker`` are for the tests, which
    run at a tiny size on the CPU: the chip's entries otherwise come
    from ``peaks.json`` and ``repro.arch.device_for_kind``, and
    ``breaker(engine)`` plants a fault in the timed path.  ``control``
    (for ``calibrate.py``) also reads the control's gap on the same
    sample, the reference computed from fp8 operands, and puts it
    through the same verdict as the program's (``control_correct``)."""
    import jax
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.models.config import ModelConfig
    from repro.serve import CANCELLED, OK, PagedServeEngine, Request

    fam, conf = cell.family, cell.conf
    d = fam.dims(conf)
    kind = devices[0].device_kind
    if peaks is None:
        table = json.loads((BENCH_DIR / "peaks.json").read_text())
        if kind not in table:
            raise RunFailure(f"no peaks for device_kind {kind!r} in "
                             "peaks.json")
        peaks = table[kind]
    if pallas_device == "from-kind":
        from repro.arch import device_for_kind
        pallas_device = device_for_kind(kind).name
    B, max_len = conf["max_batch"], conf["max_len"]
    mc = ModelConfig(name=cell.config_name, **fam.program_config(conf),
                     use_pallas=True, pallas_device=pallas_device)
    log(f"[cell] {cell.name}: {cell.config_name} ({d.L} layers, d_model "
        f"{d.D}, {d.H}/{d.KV} heads of {d.hd}, d_ff {d.F}, vocab {d.V}, "
        f"qkv_bias {d.qkv_bias}), mix {cell.mix['name']}, seed {seed}, "
        f"{seconds:g} s window, trace {int(trace)}; device {kind} x"
        f"{len(devices)} -> {pallas_device}")

    key = fam.seed_key(seed)
    params = jax.block_until_ready(
        jax.jit(functools.partial(fam.program_params, d))(key))
    eng = PagedServeEngine(mc, params, max_len=max_len, max_batch=B,
                           device=pallas_device,
                           prefill_chunk=conf["prefill_chunk"])
    if (eng.page, eng.cache.n_blocks) != (conf["page"], conf["n_blocks"]):
        raise RunFailure(f"engine picked page {eng.page}, {eng.cache.n_blocks}"
                         f" blocks; the configuration states {conf['page']},"
                         f" {conf['n_blocks']}")
    _warm_up(eng, Request, d.V)
    stream = traffic.generate(cell.mix, seed, B, d.V, max_len)
    reqs = [Request(prompt=p, n_steps=n)
            for p, n in zip(stream.prompts, stream.outputs)]
    if breaker is not None:
        breaker(eng)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    compiles: List[float] = []

    def on_compile(event: str, duration: float, **kw) -> None:
        if event in COMPILE_EVENTS:
            compiles.append(time.perf_counter())
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    gcw = GcWatch()
    gc.callbacks.append(gcw)
    probe = Probe(eng, reqs, stream.first, seconds,
                  trace_dir=str(TRACE_DIR) if trace else None,
                  trace_seconds=TRACE_SECONDS)
    log(f"[setup] weights and warm-up done at "
        f"{time.perf_counter() - t_start:.3f} s; {len(reqs)} requests "
        f"queued, {stream.first} under way")
    try:
        results, stats = eng.run(reqs)
        end_t = probe.ended if probe.ended is not None else \
            time.perf_counter()
    finally:
        probe.uninstall()
        jax.monitoring.unregister_event_duration_listener(on_compile)
        gc.callbacks.remove(gcw)
    if probe.closed is None:
        raise RunFailure(
            f"the queue ran dry before the window closed: {len(reqs)} "
            f"requests served in {stats.ticks} ticks (opened "
            f"{probe.opened is not None}); give the mix more requests")
    open_t = max(results[j].emit_times[0] for j in range(stream.first))
    close_t = open_t + seconds
    if not (probe.opened >= open_t and probe.closed >= close_t):
        raise RunFailure(f"window bookkeeping: opened {probe.opened}, "
                         f"closed {probe.closed}, first wave done {open_t}")
    setup_s = open_t - t_start

    ws = window.measure(results, open_t, close_t, end_t)
    ended = [r for r in results if r.status != CANCELLED]
    failed = [r for r in ended if r.status != OK]
    log(f"[window] {ws.tokens} tokens in {ws.seconds:g} s; ticks "
        f"{stats.ticks}, decode steps {stats.decode_steps}, prefill chunks "
        f"{stats.prefill_chunks} in the whole run; {len(ended)} requests "
        f"ended, {len(failed)} not OK, "
        f"{sum(r.status == CANCELLED for r in results)} cancelled at the "
        "close")
    log(f"[window] inter-token gap: median {window.pct(ws.gaps_s, 50) * 1e3:.3f}"
        f" ms, p95 {window.pct(ws.gaps_s, 95) * 1e3:.3f} ms, n={len(ws.gaps_s)}")
    log(f"[window] ttft: median {window.pct(ws.ttft_s, 50) * 1e3:.3f} ms, "
        f"p90 {window.pct(ws.ttft_s, 90) * 1e3:.3f} ms, "
        f"p95 {window.pct(ws.ttft_s, 95) * 1e3:.3f} ms, n={len(ws.ttft_s)} "
        f"admitted in the window, {ws.censored} without a first token "
        f"{end_t - close_t:.3f} s after the close (counted to then)")
    log(f"[setup] setup_s {setup_s:.3f} (first wave prefilled and emitting)")
    log(f"[window] compilations inside the window: "
        f"{sum(open_t < t <= close_t for t in compiles)}")

    stats_mem = devices[0].memory_stats() or {}
    mem_peak = int(stats_mem.get("peak_bytes_in_use", 0))
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}

    metrics: Dict[str, Dict] = {}
    breakdown = None
    values = {"output_tokens_per_s": ws.tokens_per_s,
              "itl_p95_ms": window.pct(ws.gaps_s, 95) * 1e3,
              "ttft_p90_ms": window.pct(ws.ttft_s, 90) * 1e3,
              "setup_s": setup_s}
    calls = probe.calls()
    log_host_time(calls, gcw, open_t, close_t)
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        tr = None
        if probe.trace_t0 is None:
            raise RunFailure("the traced run never started the profiler")
        tr = trace_mod.read(trace_mod.find_xplane(str(TRACE_DIR)),
                            probe.clock_mark, probe.trace_t0, probe.trace_t1)
        ctx = Context(cell, d, peaks, B, probe.trace_t0, probe.trace_t1,
                      calls[0], calls[1],
                      [np.asarray(r.emit_times) for r in results], tr, [])
        for m in cell.per_layer:
            reader = load_module(BENCH_DIR / "metrics" / f"{m['name']}.py",
                                 f"chipbench_metric_{m['name']}")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        for n in ctx.notes:
            log(f"[trace] {n}")
        busy = trace_mod.busy_s(tr)
        device.update(busy_s=busy, window_s=tr.window_s)
        breakdown = {"device_ops": trace_mod.top_ops(tr),
                     "idle_gaps": trace_mod.longest_gaps(tr)}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    # the reference runs on a chip that holds none of the program's state
    prompts = [r.prompt for r in reqs]
    del eng, params, probe
    gc.collect()
    lim = cell.limits
    sample = check.draw(results, prompts, seed, lim["sample_tokens"],
                        lim["sample_requests"])
    t0 = time.perf_counter()
    ref = fam.reference_logits(d, key, sample.seqs, sample.rows,
                               row_len=max_len)
    gap = check.served_gap(ref, sample.served)
    log(f"[check] reference over {len(sample.ids)} requests "
        f"({sum(len(s) for s in sample.served)} served tokens, "
        f"{sum(len(s) for s in sample.seqs)} positions) in "
        f"{time.perf_counter() - t0:.2f} s")
    checks = {"logit_gap": {"value": gap, "limit": lim["logit_gap"]},
              "failed_requests": {"value": len(failed), "limit": 0}}
    correct = check.verdict(gap, lim["logit_gap"], len(failed))
    out = {"correct": bool(correct), "attempted": len(ended),
           "failed": len(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        ctl = fam.reference_logits(d, key, sample.seqs, sample.rows,
                                   row_len=max_len,
                                   quant="fp8")
        out["control_gap"] = check.picked_gap(ref, ctl)
        out["control_correct"] = check.verdict(
            out["control_gap"], lim["logit_gap"], len(failed))
    out["checks"] = checks
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="chip benchmark: one run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        cell = resolve(bench, args.workload)
        setup_compile_cache()
        devices = require_chip(cell.chips)
        out = run(cell, args.seed, args.seconds, bool(args.trace), devices,
                  t_start)
    except RunFailure as e:
        print(f"run failed: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
