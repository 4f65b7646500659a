"""The plain reference: ``np.searchsorted`` over the sorted key set.

Each stored key carries the epoch from which it is visible (0 for the
loaded keys, the acknowledging ingest's epoch for inserted keys, never
for keys not yet inserted), so one table answers a lookup as of any
epoch the served path reports.  Imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

NEVER = np.iinfo(np.int64).max


class Reference:
    """Exact-match lookup as of an epoch.  ``dtype`` is the key type the
    comparison runs in: float64 for the reference, float32 for the
    control that computes it one precision lower."""

    def __init__(self, keys: np.ndarray, payloads: np.ndarray,
                 since: np.ndarray | None = None, dtype=np.float64):
        keys = np.asarray(keys, np.float64).astype(dtype)
        order = np.argsort(keys, kind="stable")
        self.dtype = dtype
        self.keys = keys[order]
        self.payloads = np.asarray(payloads, np.int64)[order]
        self.since = (np.zeros(keys.size, np.int64) if since is None
                      else np.asarray(since, np.int64)[order])

    def _pos(self, q: np.ndarray) -> np.ndarray:
        q = np.asarray(q, np.float64).astype(self.dtype)
        pos = np.minimum(np.searchsorted(self.keys, q), self.keys.size - 1)
        return pos, self.keys[pos] == q

    def mark(self, keys: np.ndarray, epoch: int) -> None:
        """Make stored ``keys`` visible from ``epoch`` on."""
        pos, hit = self._pos(keys)
        if not bool(np.all(hit)):
            raise KeyError("mark: a key is not in the reference")
        self.since[pos] = int(epoch)

    def lookup(self, q: np.ndarray, epoch) -> tuple:
        """(payloads, found) as of ``epoch`` (a scalar or one per query);
        payload -1 where not found."""
        pos, hit = self._pos(q)
        found = hit & (self.since[pos] <= np.asarray(epoch, np.int64))
        return np.where(found, self.payloads[pos], -1), found

    def count_wrong(self, q, payloads, found, epoch) -> int:
        """Answers that differ from the reference in the found flag or
        the payload."""
        pay, fnd = self.lookup(q, epoch)
        bad = (np.asarray(found, bool) != fnd) | (
            np.asarray(payloads, np.int64) != pay)
        return int(np.count_nonzero(bad))
