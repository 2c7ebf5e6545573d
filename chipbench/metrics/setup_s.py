"""Process start to the first timed step: imports, weights or pool, warm-up
and, in a run that compiles, compilation."""


def read(run, trace, peaks):
    return run.setup_s
