"""The least time the chip needs for the window's tests (the larger of
operations over the int8 peak and bytes over HBM bandwidth, from
``work.py``), over the device busy time in the traced window, summed over
the cell's devices."""

import work


def read(run):
    if run.trace is None or run.peak is None:
        return None
    busy = sum(run.trace.busy_s.values())
    n = run.cell.config["n_samples"]
    least = sum(work.least_seconds(m, n, p, run.peak)[0] for m, p in run.window_cells)
    return 100.0 * least / busy if busy > 0 and least > 0 else None
