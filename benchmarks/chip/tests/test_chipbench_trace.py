"""The trace reduction, on a small recorded CPU profile and on
hand-made intervals."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench_tiny import harness  # noqa: F401  (puts chipbench on the path)
from chipbench import trace as tm


def _trace(ops, modules, host, t0, t1):
    return tm.Trace([tm.Events.of(ops)], [tm.Events.of(modules)],
                    tm.Events.of(host), t0, t1)


OPS = [("%while.5 = (s32[]) while(...)", 0, 15),      # encloses the next two
       ("%fusion.1 = bf16[8] fusion(...)", 0, 10),
       ("%fusion.2 = bf16[8] fusion(...)", 10, 5),
       ("%k = bf16[8] custom-call(...)", 30, 20),
       ("%k = bf16[8] custom-call(...)", 80, 5)]
MODULES = [("jit__step", 0, 50), ("jit__pstep", 80, 5)]


def test_busy_union_idle_gaps_and_labels():
    host = [("bench.decode_dispatch", 20, 5)]
    tr = _trace(OPS, MODULES, host, 0, 100)
    assert tm.union(tr.ops[0].start, tr.ops[0].end) == [(0, 15), (30, 50),
                                                        (80, 85)]
    assert abs(tm.busy_s(tr) - 40e-9) < 1e-15
    assert tm.idle_gaps(tr) == [(15, 30), (50, 80), (85, 100)]
    gaps = tm.longest_gaps(tr, 2)
    assert [round(g * 1e9) for _, g in gaps] == [30, 15]
    assert gaps[1][0] == "bench.decode_dispatch"   # 15..30 midpoint 22.5
    assert gaps[0][0] == "scheduler"
    # the enclosing while is not an op of its own; ops carry their program
    top = tm.top_ops(tr)
    assert top[0] == ("jit__step/k", 20e-9)
    assert dict(top) == {"jit__step/k": 20e-9, "jit__step/fusion.1": 10e-9,
                         "jit__pstep/k": 5e-9, "jit__step/fusion.2": 5e-9}
    assert np.allclose(tm.op_times(tr, "custom-call"), [20e-9, 5e-9])
    assert np.allclose(tm.program_times(tr, "^jit__step$"), [50e-9])
    # the window clips: only what lies inside counts
    tr2 = _trace(OPS, MODULES, host, 10, 40)
    assert abs(tm.busy_s(tr2) - 15e-9) < 1e-15


def test_reads_a_recorded_cpu_profile(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    mark = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.clock"):
        pass
    t0 = time.perf_counter()
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.decode_dispatch"):
            y = f(x)
        y.block_until_ready()
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    tr = tm.read(tm.find_xplane(str(tmp_path)), mark, t0, t1)
    assert tr.window_s == np.float64(t1 - t0) or abs(tr.window_s - (t1 - t0)) < 1e-6
    spans = tr.host.within(tr.t0_ns - 1e6, tr.t1_ns + 1e6)
    assert spans.names.count("bench.decode_dispatch") == 3
    # host spans land inside the window once the clock is mapped
    assert (spans.start >= tr.t0_ns - 1e5).all()
    assert (spans.end <= tr.t1_ns + 1e5).all()
    assert tr.ops == [] and tm.busy_s(tr) is None   # the CPU has no device plane
