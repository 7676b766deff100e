"""The comparison that decides ``correct``.

What the writer received for the window's cells is held against the plain
reference of the configuration's deployment (``reference(cohort, config)``
of ``deployments/<name>.py``), which is asked about genome marker indices
as the scan reports them.  Six numbers, each against the limit its
configuration file states:

  r_gap          max |r - r_ref| over every hit row of every window cell
  nlp_gap        max |nlp - nlp_ref| / nlp_ref over every hit row, and over
                 the per-trait best of the checked traits
  hits_missing   reference hits (nlp_ref >= threshold (1 + band)) of the
                 checked traits that no hit row of their cell carries
  hits_spurious  hit rows whose reference nlp is under threshold (1 - band),
                 that lie outside their cell, or that repeat
  best_wrong     checked (cell, trait) pairs whose best marker's reference
                 nlp is short of the reference best by more than the band
  cells_bad      cells whose extent is not a whole batch of the grid and
                 the whole panel, or that repeat

``band`` is twice the ``nlp_gap`` limit, relative: a pair that close to a
line is a tie at the precision the limit allows.  The checked traits, and
the checked cells among the window's, are drawn from the seed; hit rows
are checked in every cell, so a four-chip window covers every chip.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

ORDER = ("r_gap", "nlp_gap", "hits_missing", "hits_spurious", "best_wrong", "cells_bad")
WORST = ("r_gap", "nlp_gap")    # a cell's worst value counts; the rest are counts


@dataclass
class Answer:
    """One cell as the writer received it."""

    lo: int
    hi: int
    t_lo: int
    t_hi: int
    hits: np.ndarray        # (H, 2) global (marker, trait)
    hit_stats: np.ndarray   # (H, 3) r, t, -log10 p
    best_nlp: np.ndarray    # (t_hi - t_lo,)
    best_row: np.ndarray    # (t_hi - t_lo,) batch-local marker row

    @classmethod
    def of(cls, cell) -> "Answer":
        return cls(cell.lo, cell.hi, cell.t_lo, cell.t_hi, cell.hits, cell.hit_stats,
                   cell.best_nlp, cell.best_row)


def t_at(ref, nlp: float) -> float:
    """The |t| at which the reference's -log10 p reaches ``nlp``."""
    lo, hi = 0.0, 100.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if ref.nlp(np.array([mid]))[0] < nlp else (lo, mid)
    return lo


def compare(answers: list[Answer], ref, *, n_traits: int, batch_markers: int,
            n_markers: int, threshold: float, check_traits: np.ndarray,
            check_cells: int, rng: np.random.Generator,
            limits: dict) -> tuple[dict[str, float], int]:
    """The six numbers, and how many cells failed any of them."""
    band = 2.0 * limits["nlp_gap"]
    per_cell = [dict.fromkeys(ORDER, 0.0) for _ in answers]

    def note(i: int, key: str, value: float) -> None:
        c = per_cell[i]
        c[key] = max(c[key], value) if key in WORST else c[key] + value

    seen, whole_cells = set(), []
    for i, a in enumerate(answers):
        whole = (a.lo % batch_markers == 0
                 and a.hi - a.lo == min(batch_markers, n_markers - a.lo)
                 and (a.t_lo, a.t_hi) == (0, n_traits)
                 and len(a.best_nlp) == len(a.best_row) == n_traits)
        if not whole or (a.lo, a.t_lo) in seen:
            note(i, "cells_bad", 1)
        else:
            whole_cells.append(i)
        seen.add((a.lo, a.t_lo))

    # Every hit row of every cell, against the reference's r of its (marker, trait).
    rows, stats, owner = [], [], []
    for i, a in enumerate(answers):
        h = np.asarray(a.hits, np.int64).reshape(-1, 2)
        inside = (h[:, 0] >= a.lo) & (h[:, 0] < a.hi) & (h[:, 1] >= a.t_lo) & (h[:, 1] < a.t_hi)
        note(i, "hits_spurious", int((~inside).sum()) + len(h) - len(np.unique(h, axis=0)))
        rows.append(h[inside])
        stats.append(np.asarray(a.hit_stats, np.float64).reshape(-1, 3)[inside])
        owner.append(np.full(int(inside.sum()), i))
    hits = np.concatenate(rows) if rows else np.zeros((0, 2), np.int64)
    if len(hits):
        hit_stats, owner = np.concatenate(stats), np.concatenate(owner)
        r_ref = ref.r_pairs(hits[:, 0], hits[:, 1])
        nlp_ref = ref.nlp(ref.t(r_ref))
        r_gap = np.abs(hit_stats[:, 0] - r_ref)
        nlp_gap = np.abs(hit_stats[:, 2] - nlp_ref) / np.maximum(nlp_ref, 1.0)
        low = nlp_ref < threshold * (1.0 - band)
        for i in np.unique(owner):
            mine = owner == i
            note(i, "r_gap", float(r_gap[mine].max()))
            note(i, "nlp_gap", float(nlp_gap[mine].max()))
            note(i, "hits_spurious", int(low[mine].sum()))

    # The checked traits in full, cell by cell: best marker and completeness.
    y = ref.panel(check_traits)
    hit_set = {(int(m), int(t)) for m, t in hits}
    t_line = t_at(ref, threshold * (1.0 + band))
    cols = np.arange(len(check_traits))
    picked = rng.choice(whole_cells, size=min(check_cells, len(whole_cells)), replace=False)
    for i in sorted(picked):
        a = answers[i]
        t = ref.t(ref.r_block(a.lo + np.arange(a.hi - a.lo), y))   # (markers, checked traits)
        prog = np.clip(np.asarray(a.best_row, np.int64)[check_traits], 0, len(t) - 1)
        nlp_top = ref.nlp(t[np.argmax(np.abs(t), axis=0), cols])
        nlp_prog = ref.nlp(t[prog, cols])
        note(i, "best_wrong", int(np.sum(nlp_top - nlp_prog > band * nlp_top)))
        best = np.asarray(a.best_nlp, np.float64)[check_traits]
        note(i, "nlp_gap", float(np.max(np.abs(best - nlp_prog) / np.maximum(nlp_prog, 1.0))))
        cand_m, cand_j = np.nonzero(np.abs(t) >= t_line)
        clear = ref.nlp(t[cand_m, cand_j]) >= threshold * (1.0 + band)
        note(i, "hits_missing", sum((a.lo + int(m), int(check_traits[j])) not in hit_set
                                    for m, j in zip(cand_m[clear], cand_j[clear])))

    out = {}
    for key in ORDER:
        values = [c[key] for c in per_cell]
        out[key] = float(max(values, default=0.0) if key in WORST else sum(values))
    failed = sum(any(c[k] > limits[k] for k in ORDER) for c in per_cell)
    return out, failed


def verdict(numbers: dict[str, float], limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in ORDER)
