"""Host milliseconds per window cell of refine and hit extraction
(``_live_cell``: D2H pulls, canonical refine, globalization), from
``ScanMetrics``' extract total."""


def read(run):
    a, b = run.scan_before, run.scan_after
    cells = b["cells"] - a["cells"]
    return 1e3 * (b["extract_s"] - a["extract_s"]) / cells if cells > 0 else None
