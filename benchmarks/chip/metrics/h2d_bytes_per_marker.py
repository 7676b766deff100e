"""Bytes staged host to device per distinct marker
(``ScanMetrics.h2d_bytes_per_marker``): a count, which repeats exactly."""


def read(run):
    return run.scan_after["h2d_bytes_per_marker"]
