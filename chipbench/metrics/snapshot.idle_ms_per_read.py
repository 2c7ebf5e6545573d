"""Device idle milliseconds per snapshot read while the engine pins,
unpins or reads a snapshot: idle gaps of the window at whose midpoint a
``repro.snapshot.*`` span is open, over the window's
``repro.snapshot.read`` spans."""
from chipbench import program_trace


def read(run, trace, peaks):
    prog = program_trace.load(run, trace)
    reads = prog.count("repro.snapshot.read") if prog is not None else 0
    if not reads:
        return None
    return prog.idle_ns(program_trace.SNAPSHOT) / 1e6 / reads
