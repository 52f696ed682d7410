"""The plain reference of the churn cells: the driver's own BOOK of who
is alive, and what ``check`` holds the program's table and answers to.

Independent of the code under test: the book is a numpy array of the
live ids that a schedule of (slots, arrivals) rewrites tick by tick —
the node in a chosen slot departs and an arrival takes the slot — with
no table, no tombstone and no delta; the fingerprint of a set of ids is
``drivers/sim_tp.checksum``'s arithmetic written again in numpy; the
exact XOR top-k over the live ids is ``reference_blocks.BlockIndex``."""

from __future__ import annotations

import numpy as np

from dhtbench import reference_blocks


def make_schedule(rng, n_live: int, ticks: int, per_tick: int):
    """``(slots [T, E], arrivals [T, E, 5])``: tick ``t`` takes the nodes
    in ``slots[t]`` — drawn uniformly, without repeats, from the book's
    ``n_live`` slots, which are always all alive — out of the network
    and puts ``arrivals[t]``, uniform 160-bit ids, in their place.  A
    node that arrived may be drawn later like any other."""
    slots = np.stack([rng.choice(n_live, per_tick, replace=False,
                                 shuffle=False) for _ in range(ticks)])
    arrivals = rng.integers(0, 2 ** 32, size=(ticks, per_tick, 5),
                            dtype=np.uint32)
    return slots.astype(np.int64), arrivals


def departures(book: np.ndarray, slots: np.ndarray,
               arrivals: np.ndarray) -> np.ndarray:
    """The ids that depart tick by tick, ``[T, E, 5]``, by playing the
    schedule over a COPY of ``book`` (the network as built)."""
    book = book.copy()
    out = np.empty_like(arrivals)
    for t in range(slots.shape[0]):
        out[t] = book[slots[t]]
        book[slots[t]] = arrivals[t]
    return out


def book_after(book: np.ndarray, slots: np.ndarray, arrivals: np.ndarray,
               ticks: int) -> np.ndarray:
    """The live ids after the first ``ticks`` ticks."""
    book = book.copy()
    for t in range(ticks):
        book[slots[t]] = arrivals[t]
    return book


def _mix(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finalizer on uint32 arrays (wrapping)."""
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x85EBCA6B)
    x = (x ^ (x >> np.uint32(13))) * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def checksum(ids: np.ndarray) -> np.ndarray:
    """Seven uint32 of the rows of ``ids`` [n, 5], whatever their order:
    the row count, each limb's sum and the sum of a hash that chains a
    row's five limbs, all wrapping (``drivers/sim_tp.checksum``)."""
    h = ids[:, 0].copy()
    for limb in range(1, 5):
        h = _mix(h ^ ids[:, limb]) + np.uint32(limb)
    parts = [np.uint32(ids.shape[0] & 0xFFFFFFFF),
             *(ids[:, l].sum(dtype=np.uint32) for l in range(5)),
             _mix(h).sum(dtype=np.uint32)]
    return np.array(parts, dtype=np.uint32)


class LiveSet:
    """The live ids of one moment, indexed once: exact XOR top-k
    (:class:`reference_blocks.BlockIndex`, numpy's own ordering) and
    membership by the same order."""

    def __init__(self, book: np.ndarray):
        self.index = reference_blocks.BlockIndex(book)

    def closest_ids(self, target: np.ndarray, k: int) -> np.ndarray:
        """The ``k`` live ids XOR-closest to ``target``, nearest first."""
        return self.index.ids[self.index.closest(target, k)]

    def holds(self, ids: np.ndarray) -> np.ndarray:
        """Which rows of ``ids`` [m, 5] are live ids.  Rows that share
        their top 64 bits with a live id are compared with every live id
        of that run on all 160 bits."""
        key = (ids[:, 0].astype(np.uint64) << np.uint64(32)) \
            | ids[:, 1].astype(np.uint64)
        lo = np.searchsorted(self.index.key, key, "left")
        hi = np.searchsorted(self.index.key, key, "right")
        out = np.zeros(ids.shape[0], bool)
        for step in range(int((hi - lo).max(initial=0))):
            at = lo + step
            ok = at < hi
            rows = self.index.order[np.where(ok, at, 0)]
            out |= ok & (self.index.ids[rows] == ids).all(axis=1)
        return out
