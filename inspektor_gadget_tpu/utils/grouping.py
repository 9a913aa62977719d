"""Grouping a batch by the ids of a column, one array pass a batch.

The open window's slice store (history/window.py `_cell_codes`), the
per-container distributions of the anomaly scorer (operators/tpusketch.py)
and the seccomp recorder's bitmap (gadgets/advise/seccomp_profile.py) all
need the same thing of a batch: the distinct ids of a column and each
event's index into them. Ids that lie close together (containers numbered
by one counter, a handful of event kinds) are coded through a table over
their span: one pass, where the sort behind `np.unique` would be the
dearest step of a batch (0.35 ms against 3-4 ms for 65,536 events on one
CPU core).
"""

from __future__ import annotations

import numpy as np


def table_codes(ids: np.ndarray, span: int) -> tuple[np.ndarray, np.ndarray]:
    """The distinct values of `ids` (non-negative integers under `span`,
    an index dtype), ascending, and each element's index into them: what
    `np.unique(ids, return_inverse=True)` gives, through a table of `span`
    entries."""
    seen = np.zeros(span, dtype=bool)
    seen[ids] = True
    vals = np.flatnonzero(seen)
    code = np.empty(span, dtype=np.intp)
    code[vals] = np.arange(len(vals))
    return vals, code[ids]


def group_codes(ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The distinct values of a non-empty integer column, ascending, and
    each element's index into them: through the table where the values'
    span is at most four times the column's length, else `np.unique`."""
    lo = ids.min()
    span = int(ids.max()) - int(lo) + 1
    if span > 4 * len(ids):
        return np.unique(ids, return_inverse=True)
    vals, code = table_codes((ids - lo).astype(np.intp), span)
    return vals.astype(ids.dtype) + lo, code


def find_sorted(known: np.ndarray, vals: np.ndarray
                ) -> tuple[np.ndarray, np.ndarray]:
    """Where each of `vals` stands in the ascending `known` (an index into
    it, clipped to its last element) and whether it is there."""
    if not len(known):
        return (np.zeros(len(vals), dtype=np.intp),
                np.zeros(len(vals), dtype=bool))
    at = np.minimum(np.searchsorted(known, vals), len(known) - 1)
    return at, known[at] == vals


class SlotTable:
    """Ids given dense slots in the order they first appear: a batch's new
    ids ascending, as a `dict` filled from `np.unique` of each batch would
    order them. The slots index the rows of an array the owner keeps. The
    lookup is an array pass: the ids seen are kept sorted beside their
    slots, a batch's distinct ids are found among them by one
    `searchsorted`, and only a batch that brings new ids rebuilds the pair
    (a node's containers are all seen within its first batches)."""

    def __init__(self):
        self._ids: list[int] = []                      # by slot
        self._sorted = np.zeros(0, dtype=np.uint64)    # the ids, ascending
        self._slots = np.zeros(0, dtype=np.intp)       # their slots

    def __len__(self) -> int:
        return len(self._ids)

    def ids(self) -> list[int]:
        """The ids seen, by slot."""
        return list(self._ids)

    def slots_of(self, ids: np.ndarray) -> np.ndarray:
        """Each element's slot; ids not seen before take the next ones."""
        vals, code = group_codes(ids)
        vals = vals.astype(np.uint64, copy=False)
        at, found = find_sorted(self._sorted, vals)
        if not found.all():
            fresh = vals[~found]
            slots = np.arange(len(self._ids), len(self._ids) + len(fresh),
                              dtype=np.intp)
            self._ids.extend(fresh.tolist())
            # both runs ascend: one stable merge keeps the pair sorted
            merged = np.concatenate([self._sorted, fresh])
            order = np.argsort(merged, kind="stable")
            self._sorted = merged[order]
            self._slots = np.concatenate([self._slots, slots])[order]
            at = np.searchsorted(self._sorted, vals)
        return self._slots[at][code]
