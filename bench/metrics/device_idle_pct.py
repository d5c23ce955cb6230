"""Share of the traced window in which no operation ran on the device."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
