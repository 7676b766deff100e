"""Device milliseconds under the program's ``gwas.epilogue`` scope (the
validity mask, the per-trait winners and the hit compaction) per window
cell, summed over the cell's devices (profiler trace, union of the scope's
op intervals per device)."""

import trace_scopes


def read(run):
    return trace_scopes.per_cell_ms(run, "gwas.epilogue")
