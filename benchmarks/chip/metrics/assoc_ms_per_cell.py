"""Device milliseconds under the program's ``gwas.assoc`` scope (the fused
kernel or the association GEMM, with the panel pad and trait slice) per
window cell, summed over the cell's devices (profiler trace, union of the
scope's op intervals per device)."""

import trace_scopes


def read(run):
    return trace_scopes.per_cell_ms(run, "gwas.assoc")
