"""Host milliseconds of ``ScanEngine.prepare_batch`` per marker batch in the
window (``ScanMetrics.decode_s_total`` over the batches that arrived)."""


def read(run):
    a, b = run.scan_before, run.scan_after
    batches = (b["markers"] - a["markers"]) / run.cell.config["scan"]["batch_markers"]
    return 1e3 * (b["decode_s"] - a["decode_s"]) / batches if batches > 0 else None
