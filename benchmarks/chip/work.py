"""What the association work needs, counted from a cell's shapes, and the
least time a chip could take for it.

Work is the statistic the paper computes, not whatever implements it:
``2 M N P`` operations for M markers, N real samples (padding is waste,
not work) and P traits; bytes are the packed genotypes ``M ceil(N/4)``
plus one read of the float32 panel ``4 N P`` per marker-batch sweep.  The
rate is the chip's highest published matrix-unit rate (int8): genotypes
are exact small integers, so no implementation of this work, int8 limbs
included, can beat it.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = PEAKS) -> dict:
    """The published peaks of one chip; an unknown ``device_kind`` is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def assoc_ops(markers: int, samples: int, traits: int) -> float:
    return 2.0 * markers * samples * traits


def assoc_bytes(markers: int, samples: int, traits: int) -> float:
    return float(markers * -(-samples // 4) + 4 * samples * traits)


def least_seconds(markers: int, samples: int, traits: int, peak: dict) -> tuple[float, str]:
    """(least time, the bound that sets it: "compute" or "memory")."""
    compute = assoc_ops(markers, samples, traits) / peak["int8_ops_per_s"]
    memory = assoc_bytes(markers, samples, traits) / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
