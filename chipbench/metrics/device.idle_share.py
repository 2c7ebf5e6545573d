"""Share of the traced window in which no operation ran on the device,
averaged over the chips used."""


def read(run, trace, peaks):
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)
