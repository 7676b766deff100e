"""A virtual genome over a pool of distinct packed markers.

The scan reads a genome of ``n_markers`` markers; marker ``i`` is pool row
``i mod pool_markers``.  Each ``read_packed`` returns a fresh copy, as a
read of a page-cache-hot ``.bed`` would, and the source has no
``packed_cache_key``, so the program's packed-slab cache never hits, as in
a genome scan that reads each batch once.  Marker ids are formatted on
demand: an 8.9M-string list would cost seconds of set-up and gigabytes.
"""
from __future__ import annotations

from collections.abc import Sequence

import numpy as np

# PLINK 2-bit codes (sample i in byte i//4, bits 2*(i%4), LSB first):
# 0b00 -> 2, 0b01 -> missing, 0b10 -> 1, 0b11 -> 0.
MISSING = -9
_CODE_TO_DOSAGE = np.array([2, MISSING, 1, 0], np.int8)
BYTE_TO_DOSAGES = _CODE_TO_DOSAGE[(np.arange(256)[:, None] >> (2 * np.arange(4))) & 3]


def decode(packed: np.ndarray, n_samples: int) -> np.ndarray:
    """``(M, ceil(N/4)) uint8 -> (M, N) int8`` dosages, -9 missing."""
    return BYTE_TO_DOSAGES[packed].reshape(packed.shape[0], -1)[:, :n_samples]


class MarkerIds(Sequence):
    """``vm0000000``-style ids, formatted when asked for."""

    def __init__(self, n: int):
        self._n = n

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(self._n))]
        i = int(i)
        if not -self._n <= i < self._n:
            raise IndexError(i)
        return f"vm{i % self._n:07d}"


class VirtualGenome:
    """``GenotypeSource`` of ``n_markers`` markers recycling ``pool``."""

    supports_packed = True

    def __init__(self, pool: np.ndarray, n_samples: int, n_markers: int):
        if pool.dtype != np.uint8 or pool.ndim != 2:
            raise ValueError("pool must be a 2-D uint8 array of packed markers")
        if pool.shape[1] != -(-n_samples // 4):
            raise ValueError(f"pool rows hold {pool.shape[1]} bytes, not ceil({n_samples}/4)")
        self.pool = pool
        self.n_samples = int(n_samples)
        self.n_markers = int(n_markers)
        self.sample_ids = [f"S{i:06d}" for i in range(self.n_samples)]
        self.marker_ids = MarkerIds(self.n_markers)

    @property
    def pool_markers(self) -> int:
        return int(self.pool.shape[0])

    def pool_rows(self, lo: int, hi: int) -> np.ndarray:
        return np.arange(lo, hi, dtype=np.int64) % self.pool_markers

    def read_packed(self, lo: int, hi: int) -> np.ndarray:
        if not 0 <= lo <= hi <= self.n_markers:
            raise IndexError(f"markers [{lo}, {hi}) outside [0, {self.n_markers})")
        a = lo % self.pool_markers
        if a + (hi - lo) <= self.pool_markers:
            return self.pool[a:a + hi - lo].copy()
        return np.take(self.pool, self.pool_rows(lo, hi), axis=0)

    def read_dosages(self, lo: int, hi: int) -> np.ndarray:
        return decode(self.read_packed(lo, hi), self.n_samples)
