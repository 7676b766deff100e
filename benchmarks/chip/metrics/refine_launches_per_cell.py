"""Launches of the canonical refine executable per window cell: the
``refine_launches`` counter each cell's ``gwas.sinks`` span carries
(``CellTiming``, counted where ``refine_neglog10p`` launches), averaged
over the window's cells."""

import trace_scopes


def read(run):
    tr = trace_scopes.of(run)
    if tr is None:
        return None
    counts = [s.args["refine_launches"] for s in trace_scopes.spans_in_window(tr, "sinks")
              if "refine_launches" in s.args]
    return float(sum(counts)) / len(counts) if counts else None
