"""Tokens produced in the window over the window: generated tokens of a
served model, appended tokens of the paged cache (acknowledged ones)."""


def read(run, trace, peaks):
    return run.obs["tokens"] / run.obs["window_s"]
