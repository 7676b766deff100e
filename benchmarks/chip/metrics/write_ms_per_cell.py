"""Milliseconds per window cell in ``TsvWriter.write``, from the benchmark's
span around each call."""


def read(run):
    d = run.spans.durations("write", *run.window)
    return 1e3 * sum(d) / len(d) if d else None
