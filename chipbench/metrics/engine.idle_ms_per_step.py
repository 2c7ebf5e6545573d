"""Device idle milliseconds per step while the engine's host code runs:
idle gaps of the window at whose midpoint an engine step, reset or
prefill span is open, over the window's steps."""
from chipbench import program_trace


def read(run, trace, peaks):
    prog, n = program_trace.load(run, trace), program_trace.steps(run)
    if prog is None or not n or not prog.named(program_trace.ENGINE):
        return None
    return prog.idle_ns(program_trace.ENGINE) / 1e6 / n
