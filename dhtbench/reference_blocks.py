"""The plain reference of ``reference.py`` over an id set too large to
hold whole: exact XOR top-k for a few targets over ids that arrive in
blocks (the shards of a row-sharded table, one at a time).

The k closest of the whole set are among the k closest of each block,
so every block answers for itself (:class:`BlockIndex`: numpy's own
ordering of the block's rows, or numpy's own look that they are in
order, and a walk down the trie of their keys) and the blocks' answers
— at most ``blocks × k`` rows a target, kept with their 160-bit ids —
are ranked once more by ``reference.xor_closest`` at the end.  The host
holds one block and its index at a time, never the table."""

from __future__ import annotations

import numpy as np

from dhtbench import reference


class BlockIndex:
    """Exact XOR top-k over ONE block of ids, for targets that may lie
    anywhere — also far outside the block's own range of keys, which is
    the rule when the blocks are the shards of a range-partitioned
    table: three targets in four share not even their first bit with
    the rows of a given shard.  ``reference.XorIndex`` answers such a
    target from the whole block (its longest prefix with ``k`` members
    is the empty one: a second a target at 25M rows, eight minutes a
    run, PERF.md section 6, PR 28), so this index walks the binary trie
    of the block's 64-bit keys instead.

    The rows are ordered by their top 64 bits by numpy, or left as they
    are where numpy finds them in order already.  :meth:`closest` walks
    down from the root, one bit of the target a step, over the
    contiguous run of rows that share the path so far: where the child
    on the target's side holds at least as many rows as are still
    needed, it descends there (every row of it is closer than any row
    of the other child); where it holds fewer, those rows are all taken
    and the walk goes on in the other child for the rest, whose rows
    all differ from the target in this bit and so rank by the bits
    below.  What is left after 64 bits share the whole key.  The
    candidates, a few more than ``k``, are ranked on all 160 bits by
    ``reference.xor_closest``."""

    def __init__(self, ids: np.ndarray):
        key = (ids[:, 0].astype(np.uint64) << np.uint64(32)) \
            | ids[:, 1].astype(np.uint64)
        self.ids = ids
        if (key[:-1] <= key[1:]).all():
            self.order, self.key = np.arange(key.shape[0]), key
        else:
            self.order = np.argsort(key, kind="stable")
            self.key = key[self.order]

    def candidates(self, target: np.ndarray, k: int) -> np.ndarray:
        """Positions in the key order of a superset of the ``k`` closest
        rows: every row closer in its top 64 bits than the k-th, and
        every row that ties with it there."""
        t = (int(target[0]) << 32) | int(target[1])
        lo, hi = 0, self.key.shape[0]
        need, taken = k, []
        for depth in range(64):
            if hi - lo <= need:
                break
            bit = 1 << (63 - depth)
            # rows of [lo, hi) share the path's first `depth` bits; those
            # with this bit clear come first
            split = ((t >> (64 - depth)) << (64 - depth) if depth else 0) | bit
            mid = lo + int(np.searchsorted(self.key[lo:hi], np.uint64(split),
                                           "left"))
            near, far = ((mid, hi), (lo, mid)) if t & bit else \
                ((lo, mid), (mid, hi))
            if near[1] - near[0] >= need:
                lo, hi = near
            else:
                taken.append(np.arange(*near))
                need -= near[1] - near[0]
                lo, hi = far
                t ^= bit                  # the path goes on in the other child
        taken.append(np.arange(lo, hi))
        return np.concatenate(taken)

    def closest(self, target: np.ndarray, k: int) -> np.ndarray:
        rows = self.order[self.candidates(target, k)]
        return rows[reference.xor_closest(self.ids[rows], target, k)]


def closest_over_blocks(blocks, targets: np.ndarray, k: int) -> list:
    """For each of ``targets`` [T,5] the GLOBAL rows of the ``k`` ids
    XOR-closest to it, nearest first, over every block of ``blocks``: an
    iterable of ``(base, ids)`` with ``ids`` [n,5] uint32 the block's
    rows and ``base`` the global row of its first.  A block may be
    empty, and a generator is consumed block by block."""
    rows: list = [[] for _ in targets]
    ids: list = [[] for _ in targets]
    for base, block in blocks:
        if not block.shape[0]:
            continue
        index = BlockIndex(block)
        for j, target in enumerate(targets):
            near = index.closest(target, min(k, block.shape[0]))
            rows[j].append(near.astype(np.int64) + int(base))
            ids[j].append(block[near])
        del index
    out = []
    for j, target in enumerate(targets):
        if not rows[j]:
            out.append(np.zeros((0,), np.int64))
            continue
        cand_rows, cand_ids = np.concatenate(rows[j]), np.concatenate(ids[j])
        out.append(cand_rows[reference.xor_closest(cand_ids, target, k)])
    return out
