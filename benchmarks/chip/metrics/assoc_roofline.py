"""The least time the chip needs for the window's tests (the larger of
operations over the int8 peak and bytes over HBM bandwidth, as the
configuration's deployment counts them: ``least_seconds``), over the
device busy time in the traced window, summed over the cell's devices."""


def read(run):
    if run.trace is None or run.peak is None:
        return None
    busy = sum(run.trace.busy_s.values())
    n = run.cell.config["n_samples"]
    least_seconds = run.cell.deployment.least_seconds
    least = sum(least_seconds(m, n, p, run.peak)[0] for m, p in run.window_cells)
    return 100.0 * least / busy if busy > 0 and least > 0 else None
