"""Share of the traced window in which no operation ran on the cell's
devices: 1 - busy / window, busy the union of op intervals per device,
averaged over devices (profiler trace)."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
