"""Host milliseconds the compute threads of every slot waited for their
next decoded and staged batch (the program's ``gwas.wait_input`` spans in
the window), per window cell."""

import trace_scopes


def read(run):
    tr = trace_scopes.of(run)
    if tr is None or not run.window_cells or not trace_scopes.spans_in_window(tr, "wait_input"):
        return None
    return 1e3 * trace_scopes.span_seconds(tr, "wait_input") / len(run.window_cells)
