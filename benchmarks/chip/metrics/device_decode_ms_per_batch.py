"""Device milliseconds under the program's ``gwas.device_decode`` scope
(packed-byte decode or repack, prolog standardization) per marker batch
that arrived in the window, summed over the cell's devices (profiler
trace)."""

import trace_scopes


def read(run):
    cells_ms = trace_scopes.per_cell_ms(run, "gwas.device_decode")
    a, b = run.scan_before, run.scan_after
    batches = (b["markers"] - a["markers"]) / run.cell.config["scan"]["batch_markers"]
    if cells_ms is None or batches <= 0:
        return None
    return cells_ms * len(run.window_cells) / batches
