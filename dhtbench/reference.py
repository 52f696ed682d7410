"""The plain references that decide ``correct``: a numpy XOR top-k over
160-bit ids, and the checks built on it.  Independent of the code under
test (``xor_closest`` is ``chip_smoke.xor_closest``, copied)."""

from __future__ import annotations

import numpy as np


def xor_closest(ids: np.ndarray, target: np.ndarray, k: int) -> np.ndarray:
    """Indices of the ``k`` rows of ``ids`` [N,5] (uint32 big-endian limbs)
    XOR-closest to ``target`` [5], nearest first, by full 160-bit
    lexicographic order.  A 64-bit pre-filter keeps every row whose top
    two limbs do not exceed the k-th smallest (a superset of the answer),
    then the survivors are ordered on all five limbs."""
    d0 = ids[:, 0] ^ target[0]
    d1 = ids[:, 1] ^ target[1]
    key = (d0.astype(np.uint64) << np.uint64(32)) | d1.astype(np.uint64)
    if key.shape[0] > k:
        kth = np.partition(key, k - 1)[k - 1]
        cand = np.nonzero(key <= kth)[0]
    else:
        cand = np.arange(key.shape[0])
    d = ids[cand] ^ target[None, :]
    order = np.lexsort((d[:, 4], d[:, 3], d[:, 2], d[:, 1], d[:, 0]))[:k]
    return cand[order]


class XorIndex:
    """Exact XOR top-k for many targets over one large ``ids`` [N,5]: the
    rows are ordered ONCE (numpy's own argsort, not the program's sort) by
    their top 64 bits.  The rows that share the target's longest prefix
    with at least ``k`` members are contiguous in that order, and every
    one of them is closer than any row outside (which differs from the
    target at a higher bit), so :func:`xor_closest` over that range alone
    is the answer over all of ``ids``."""

    def __init__(self, ids: np.ndarray):
        key = (ids[:, 0].astype(np.uint64) << np.uint64(32)) \
            | ids[:, 1].astype(np.uint64)
        self.ids = ids
        self.order = np.argsort(key, kind="stable")
        self.key = key[self.order]

    def closest(self, target: np.ndarray, k: int) -> np.ndarray:
        t = (int(target[0]) << 32) | int(target[1])
        lo, hi = 0, len(self.key)
        for shared in range(64, 0, -1):     # bits of prefix shared with t
            span = 1 << (64 - shared)
            base = t & ~(span - 1)
            a = int(np.searchsorted(self.key, np.uint64(base), "left"))
            b = int(np.searchsorted(self.key, np.uint64(base + span - 1),
                                    "right"))
            if b - a >= k:
                lo, hi = a, b
                break
        rows = self.order[lo:hi]
        return rows[xor_closest(self.ids[rows], target, k)]


def limbs(raw: bytes) -> np.ndarray:
    """20-byte ids, concatenated -> [n,5] uint32 big-endian limbs."""
    return np.frombuffer(raw, dtype=">u4").reshape(-1, 5).astype(np.uint32)


def reply_is_right(target: bytes, reply: list, asker: bytes, *,
                   live_start: set, live_end: np.ndarray, peers: list,
                   k: int) -> bool:
    """A served ``find`` reply, held to what the node could know while it
    answered.  ``reply``: the ids returned, nearest first.  ``live_start``:
    the loaded ids (bytes) not expired when the window began;
    ``live_end`` [n,5]: those not expired when it ended (a subset);
    ``peers``: the ids of the asking peers, which the node inserts.

    Right means: every returned id was live at the start or is a peer's,
    none is the asker's own (the node leaves the requester out), and the
    reply equals the XOR top-k of (live at the end ∪ peers ∪ the reply)
    without the asker — so a row that expired after this reply was sent
    may appear, and no row live throughout may be missing."""
    if len(reply) != len(set(reply)) or asker in reply:
        return False
    if any(r not in live_start and r not in peers for r in reply):
        return False
    extra = [p for p in peers if p != asker] + list(reply)
    pool = np.concatenate([live_end, limbs(b"".join(extra))])
    want = pool[xor_closest(pool, limbs(target)[0], k + len(reply))]
    seen, top = set(), []
    for row in want:
        b = row.astype(">u4").tobytes()
        if b not in seen:               # the reply's ids may be in live_end too
            seen.add(b)
            top.append(b)
    return top[:k] == list(reply)
