"""Programs the engine dispatches per step: the profiler's dispatch events
(``PjitFunction``, one per call into a jitted function, counted on the
host, since a program may run on the device after its span has closed)
that start inside the engine's step, reset and prefill spans, over the
window's steps; nothing when the window holds no dispatch event at all
(a trace taken without them)."""
from chipbench import program_trace


def read(run, trace, peaks):
    prog, n = program_trace.load(run, trace), program_trace.steps(run)
    if (prog is None or not n or not prog.dispatches
            or not prog.named(program_trace.ENGINE)):
        return None
    return len(prog.inside(prog.dispatches, program_trace.ENGINE)) / n
