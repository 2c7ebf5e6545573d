"""Blocking device-to-host reads of the engine per step: its
``repro.engine.sync`` spans (one per `repro.core.telemetry.fetch`) in the
window, over the window's steps."""
from chipbench import program_trace


def read(run, trace, peaks):
    prog, n = program_trace.load(run, trace), program_trace.steps(run)
    if prog is None or not n or not prog.named(program_trace.ENGINE):
        return None
    return prog.count("repro.engine.sync") / n
