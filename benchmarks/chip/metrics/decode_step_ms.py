"""decode_step_ms: mean device time of one execution of the engine's
jitted decode program (module ``jit__step``, the name it carries until
the program names its steps).  Layer: model step."""

from chipbench import trace

PROGRAM = r"^jit__step$"


def read(ctx):
    if ctx.trace is None:
        return None
    t = trace.program_times(ctx.trace, PROGRAM)
    if not len(t):
        return None
    ctx.note(f"decode_step_ms: {len(t)} executions, median "
             f"{1e3 * float(sorted(t)[len(t) // 2]):.4f} ms")
    return 1e3 * float(t.mean())
