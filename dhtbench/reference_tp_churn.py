"""The plain reference of the churn cell whose table is sharded over the
chips (``host4-100m-churn``): a BOOK of who is alive that is never the
table, and never 2 GB of ids on the host.

The network's ids are a function of an INDEX — id ``i`` is
:func:`ids_of` ``(i)``, five limbs mixed from ``i`` and the seed's keys —
so the book is an array of indices: slot ``s`` holds index ``s`` as the
network is built, and tick ``t`` puts the indices ``n + t·J …`` of its
arrivals into the slots it draws (:func:`play`).  What departs, who is
alive and the fingerprint of any key range follow from indices alone;
ids are made, in numpy, only for the few million live nodes that lie
NEAR something the check asks about (:func:`live_near`), and the exact
answers over them are ``reference_churn.LiveSet``'s — numpy's own
ordering, independent of the program under test.

Ownership is written out again here (:func:`key_range`): the check
holds the program's placement to it, it does not ask the program.
"""

from __future__ import annotations

import numpy as np

from dhtbench import reference_churn

GOLDEN = np.uint32(0x9E3779B1)


def seed_keys(seed: int) -> np.ndarray:
    """The ten uint32 that make a seed's network: two a limb."""
    return np.random.default_rng([seed, 0x1D5]).integers(
        0, 2 ** 32, size=(5, 2), dtype=np.uint32)


def limb_of(index: np.ndarray, keys: np.ndarray, limb: int) -> np.ndarray:
    """Limb ``limb`` of the ids of ``index`` (uint32, any shape): the XOR
    of two bijections of the index (murmur3's finalizer over the index
    keyed two ways), so limbs collide as random ones do and no id is
    a function of another's."""
    mix = reference_churn._mix
    with np.errstate(over="ignore"):
        return mix(index ^ keys[limb, 0]) ^ mix(index * GOLDEN + keys[limb, 1])


def ids_of(index: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """``[..., 5]`` uint32: the ids of ``index``."""
    index = np.asarray(index, np.uint32)
    return np.stack([limb_of(index, keys, l) for l in range(5)], axis=-1)


def key_range(limb0: np.ndarray, n_ranges: int) -> np.ndarray:
    """Which of ``n_ranges`` equal ranges of the key space holds an id
    with first limb ``limb0``: the owner the configuration states."""
    return ((limb0 >> np.uint32(8)).astype(np.uint64) * np.uint64(n_ranges)
            >> np.uint64(24)).astype(np.int64)


def make_slots(rng, n_live: int, ticks: int, per_tick: int) -> np.ndarray:
    """``[T, E]`` uint32: the book's slots each tick empties, drawn
    uniformly and without repeats from its ``n_live`` slots, which are
    always all alive (an arrival takes a departure's slot)."""
    return np.stack([rng.choice(n_live, per_tick, replace=False,
                                shuffle=False)
                     for _ in range(ticks)]).astype(np.uint32)


def play(n_live: int, slots: np.ndarray, ticks: int):
    """The book after the first ``ticks`` ticks, as the uint32 index in
    every slot, and the indices that departed, tick by tick ``[ticks,
    E]``: tick ``t`` takes what lies in ``slots[t]`` and puts the
    indices ``n_live + t·E + (0 … E-1)`` there."""
    book = np.arange(n_live, dtype=np.uint32)
    per_tick = slots.shape[1]
    left = np.empty((ticks, per_tick), np.uint32)
    for t in range(ticks):
        left[t] = book[slots[t]]
        book[slots[t]] = arrivals(n_live, t, per_tick)
    return book, left


def arrivals(n_live: int, tick: int, per_tick: int) -> np.ndarray:
    """The indices that arrive at ``tick``."""
    return (n_live + tick * per_tick
            + np.arange(per_tick, dtype=np.uint32)).astype(np.uint32)


def prefix_bits(n_live: int, k: int) -> int:
    """Top bits a target shares with the candidates :func:`live_near`
    keeps for it: as many as leave 64·k live ids to a prefix on
    average."""
    return max(0, int(np.log2(max(n_live / (64 * k), 1))))


def near_buckets(found0: np.ndarray, target0: np.ndarray, bits: int):
    """``[2^24]`` bool: the 24-bit prefixes of the first limbs
    ``found0`` (returned ids) and every 24-bit prefix under the
    ``bits``-bit prefixes of ``target0`` (sampled targets).  A live id
    EQUAL to a returned one shares its 24 bits, and the k live ids
    closest to a target share its ``bits`` whenever that prefix holds k
    of them (:func:`closest_ids` checks that it does), so the live ids
    in these buckets answer both questions exactly."""
    hit = np.zeros(1 << 24, bool)
    hit[found0 >> np.uint32(8)] = True
    span = 1 << (24 - bits) if bits <= 24 else 1
    for t0 in target0.tolist():
        first = (t0 >> 8) & ~(span - 1)
        hit[first:first + span] = True
    return hit


def closest_ids(live: "reference_churn.LiveSet", target: np.ndarray, k: int,
                bits: int) -> np.ndarray:
    """The ``k`` live ids XOR-closest to ``target`` over the WHOLE live
    set, given the candidates of :func:`near_buckets`: those that share
    the target's ``bits``-bit prefix are all there, and any of them is
    closer than every id that does not share it."""
    shift = np.uint32(32 - bits)
    ids = live.index.ids
    share = int(((ids[:, 0] >> shift) == (target[0] >> shift)).sum()) \
        if bits else ids.shape[0]
    if share < k:
        raise RuntimeError(f"{share} live ids share {bits} bits with a "
                           f"sampled target, {k} are needed")
    return live.closest_ids(target, k)
