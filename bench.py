"""Headline benchmark — batched findClosestNodes on one chip.

BASELINE.json config 2: Q InfoHash queries × N node ids → exact top-16
XOR-closest, via the expanded-table row-gather lookup
(opendht_tpu/ops/sorted_table.py: expand_table + expanded_topk).  The
baseline is the reference's scalar algorithm — walk a lexicographically
sorted map outward from lower_bound picking the XOR-closer side each
step (NodeCache::getCachedNodes, /root/reference/src/node_cache.cpp:41-74) —
timed in-process on the host CPU over the same table.

Timing methodology: the per-batch time is the *slope* of a
device-serialized rep chain — one jitted program runs the full lookup R
times in a lax.while_loop whose trip count is a traced scalar (one
executable serves every R; the dynamic bound rules out unrolling and
cross-rep CSE), each rep's queries perturbed by the loop index so XLA
cannot elide or overlap reps, and the per-batch time is
(t[R2] - t[R1]) / (R2 - R1).  This cancels every constant cost
(dispatch, completion-poll quantum) and counts only device execution
of the kernel: a kernel-layer timing, not what a user of the served
node waits for.
"""

import bisect
import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from opendht_tpu.compile_cache import ensure_compile_cache
from opendht_tpu.ops.sorted_table import (sort_table, build_prefix_lut,
                                          cascade_topk, default_lut_bits,
                                          expand_table, expanded_topk)
from opendht_tpu.ops.xor_topk import xor_topk

K = 16


def scalar_closest(sorted_ints, q, k):
    """Reference algorithm: outward walk from the insertion point,
    XOR-closer side first (node_cache.cpp:41-74)."""
    n = len(sorted_ints)
    i = bisect.bisect_left(sorted_ints, q)
    lo, hi = i - 1, i
    out = []
    while len(out) < k and (lo >= 0 or hi < n):
        if lo < 0:
            out.append(sorted_ints[hi]); hi += 1
        elif hi >= n:
            out.append(sorted_ints[lo]); lo -= 1
        elif (sorted_ints[lo] ^ q) < (sorted_ints[hi] ^ q):
            out.append(sorted_ints[lo]); lo -= 1
        else:
            out.append(sorted_ints[hi]); hi += 1
    return out


def best_of(fn, tries: int = 3):
    """Best wall-clock of ``tries`` calls to ``fn()`` — only valid for
    host-side work (the native baseline) or already-slope-timed chains;
    never for timing raw device dispatches (see module docstring)."""
    best = None
    for _ in range(tries):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


# body function object -> jitted rep chain.  Bounded FIFO: the jitted
# chain g closes over `body`, so a WeakKeyDictionary would never
# collect (value → key strong ref); instead old entries are evicted
# once the cache exceeds the cap, which frees per-call lambdas (e.g. a
# sweep loop creating a fresh body per width) and their executables in
# long-running bench processes.
_CHAIN_CACHE: dict = {}
_CHAIN_CACHE_MAX = 32


def chain_slope(body, example, *consts, r1: int = 2, r2: int = 8,
                tries: int = 3, samples: int = 0):
    """Per-rep device time of ``body`` via the serialized-chain slope:
    jit a dynamic-trip-count rep loop and return
    (t[r2] - t[r1]) / (r2 - r1).  Cancels dispatch and completion-poll
    constants — see module docstring.

    With ``samples`` > 0, measures that many independent slope samples
    on the SAME compiled chain and returns ``(median, lo, hi)`` —
    the run-to-run range the docs quote (README/PARITY numbers must sit
    inside the captured range; ci/check_docs.py enforces it).

    ``body(x, *consts) -> f32 scalar`` must consume its result into the
    returned scalar; ``example`` is the input batch (uint32 limbs).  The
    input is XORed with the full rep index here, so every rep is a
    distinct computation XLA cannot elide or CSE.

    Pass every large array the body reads (tables, LUTs, …) through
    ``consts``, so it is an argument of the one cached executable.

    The jitted rep chain is cached per ``body`` IDENTITY: repeated
    calls with the same body function object (e.g. a per-wave latency
    histogram sweeping many same-shape inputs) reuse one executable —
    a fresh inner ``jax.jit`` per call would retrace and recompile
    every time.
    """
    g = _CHAIN_CACHE.get(body)
    if g is None:
        @jax.jit
        def g(x, reps, *a):
            def cond(c):
                return c[0] < reps
            def step(c):
                i, acc = c
                return i + 1, acc + body(x ^ i.astype(x.dtype), *a)
            # while_loop with a *traced* trip count: one executable
            # serves every rep count, and the dynamic bound forbids
            # unrolling/CSE across reps by construction
            return lax.while_loop(cond, step,
                                  (jnp.int32(0),
                                   jnp.zeros((), jnp.float32)))[1]
        while len(_CHAIN_CACHE) >= _CHAIN_CACHE_MAX:
            _CHAIN_CACHE.pop(next(iter(_CHAIN_CACHE)))
        _CHAIN_CACHE[body] = g

    float(g(example, jnp.int32(r2), *consts))     # compile + warm
    def timed(reps):
        return best_of(lambda: float(g(example, jnp.int32(reps), *consts)),
                       tries)

    if samples:
        def collect(a, b):
            vals = []
            for _ in range(samples):
                s = (timed(b) - timed(a)) / (b - a)
                if s > 0:
                    vals.append(s)
            return vals

        vals = collect(r1, r2)
        if not vals:
            # widen once (same escape hatch as the scalar path) before
            # failing: noisy hosts can swamp a shallow separation
            vals = collect(4 * r1, 4 * r2)
        if not vals:
            raise RuntimeError("chain_slope: no positive slope sample even "
                               f"at reps {4 * r1}/{4 * r2}; workload below "
                               "noise floor — raise r1/r2")
        vals.sort()
        return vals[len(vals) // 2], vals[0], vals[-1]

    per = (timed(r2) - timed(r1)) / (r2 - r1)
    if per <= 0:
        # jitter swamped the rep separation — widen once, then fail
        # loudly rather than publish a nonsensical number
        per = (timed(4 * r2) - timed(4 * r1)) / (4 * (r2 - r1))
        if per <= 0:
            raise RuntimeError(
                f"chain_slope non-positive ({per!r}) even at reps "
                f"{4 * r1}/{4 * r2}; workload too small for the noise "
                f"floor — raise r1/r2")
    return per


# Headline kernel geometry, selected by the round-3 per-stage profile
# (python bench.py --profile on the v5e; all chain-slope, N=1M Q=131K,
# cascade totals include the on-device stage-2 repair):
#   stride 64 (192-window, pads to 256 lanes in the sort): 23.6 ms
#   stride 42 (126-window, pads to 128 — half the comparator traffic
#              AND half the row-gather bytes): 9.3 ms, stage-1 cert
#              0.99997 (4 repairs/batch)
#   stride 32 (96-window, SAME 128-lane padded sort, smaller gather):
#              cascade 6.97 ms, stage-1 cert 0.9987 (164 repairs ≤ cap)
#   stride 24 (72-window): stage-1 cert 0.974 → 3.4K repairs swamp
#              stage 2; cascade 8.3 ms — past the optimum (recorded
#              negative result)
#   positioning: LUT-only (0 search steps) loses nothing at 20 LUT bits
#              on 1M rows (max bucket ~8 ≪ the window margin) and
#              removes ~2.5 ms of serialized element-gather steps.
# Round 5 (2-plane expansions — expand_table limbs=2 — cut the row
# gather 60% and moved the headline 17.86M → 21.6M) re-swept the
# strides hunting the verdict's ≥25M:
#   stride 16 (48-window, 64-lane sorts): stage-1 alone 2.9 ms BUT
#              cert 0.798 at k=16 — 26K misses/batch flood the repair
#              stage, cascade 32.7 ms.  NEGATIVE.
#   stride 24: cert 0.974, cascade 9.3 ms.  NEGATIVE (as in round 3).
#   stride 32: cascade 5.7 ms — still the optimum.  The k=16 result
#              set needs ~full stride-32 margins to certify, so the
#              remaining cost is irreducibly the 128-lane in-window
#              sort + gather; ≥25M was not reached and the measured
#              reason is this certification/sort-width trade.
# The timed kernel is cascade_topk at stride 32 with a 256-row repair
# cap: uncertified rows are selected on device and re-looked-up against
# the wide stride-64 expansion in the same call (a full-scan fallback
# at Q=128 costs 520 ms — the tiled scan serializes ~245 tiny sorts —
# so the cascade is both the honest and the fast design).
HEADLINE_STRIDE = 32
HEADLINE_CAP = 256


def measure(samples: int = 5) -> dict:
    platform = jax.devices()[0].platform
    on_accel = platform not in ("cpu",)
    N = 1_000_000 if on_accel else 100_000
    Q = 131_072 if on_accel else 8_192
    lut_bits = default_lut_bits(N)

    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    table = jax.random.bits(k1, (N, 5), dtype=jnp.uint32)
    queries = jax.random.bits(k2, (Q, 5), dtype=jnp.uint32)

    sorted_ids, perm, n_valid = jax.block_until_ready(sort_table(table))
    lut = jax.block_until_ready(
        build_prefix_lut(sorted_ids, n_valid, bits=lut_bits))
    # 2-PLANE expansions (round 5): the fast2 sort + clamped certificate
    # consume limb planes 0-1 only, so the gathered row carries 2 planes
    # instead of 5 — 60% off the dominant row-gather traffic,
    # bit-identical results (tests/test_topk.py pins it)
    exp_fast = jax.block_until_ready(
        expand_table(sorted_ids, stride=HEADLINE_STRIDE, limbs=2))
    exp_wide = jax.block_until_ready(expand_table(sorted_ids, limbs=2))

    def lookup(q, sorted_ids, exp_fast, exp_wide, n_valid, lut):
        # fast2 = the findClosestNodes contract (nodes, not distances):
        # the sort carries 4 operands instead of 7 (sort cost is linear
        # in operand count); cascade_topk includes the on-device repair
        # of the ~164/131K rows the stride-32 window fails to certify
        # (HEADLINE_CAP bounds the repair batch)
        d, idx, c = cascade_topk(sorted_ids, exp_fast, exp_wide, n_valid,
                                 q, lut, k=K, select="fast2",
                                 cap=HEADLINE_CAP, planes=2)
        return (jnp.sum(c.astype(jnp.float32))
                + jnp.sum(idx[:, 0].astype(jnp.float32)) * 1e-9)

    if not on_accel:               # CI smoke: shallow chain, fewer samples
        samples = min(samples, 2)
    r1, r2 = (8, 64) if on_accel else (2, 8)
    per_batch, dt_lo, dt_hi = chain_slope(
        lookup, queries, sorted_ids, exp_fast, exp_wide, n_valid, lut,
        r1=r1, r2=r2, samples=samples)
    rate = Q / per_batch

    # certificate fraction: stage 1 alone, and after the cascade (the
    # timed path); any residual uncertified row would go to the host
    # exact fallback — count it honestly
    _, _, cert1 = jax.block_until_ready(
        expanded_topk(sorted_ids, exp_fast, n_valid, queries, k=K,
                      select="fast2", lut=lut, lut_steps=0, planes=2))
    _, i2, cert = jax.block_until_ready(
        cascade_topk(sorted_ids, exp_fast, exp_wide, n_valid, queries,
                     lut, k=K, select="fast2", cap=HEADLINE_CAP, planes=2))
    cert_np = np.asarray(cert)
    cert_frac = float(cert_np.mean())
    stage2_rows = int((~np.asarray(cert1)).sum())
    n_uncert = int((~cert_np).sum())

    # exactness vs the full-scan oracle: the timed cascade must return
    # the oracle's node order on every certified row (residual
    # uncertified rows go to lookup_topk's host fallback — none occur on
    # uniform tables), and the fuller fast3 path the distances too
    # (fast3 needs all 5 planes — built transiently for the check only)
    exp_fast5 = expand_table(sorted_ids, stride=HEADLINE_STRIDE)
    d3, i3, _ = jax.block_until_ready(
        expanded_topk(sorted_ids, exp_fast5, n_valid, queries[:256], k=K,
                      lut=lut, lut_steps=0))
    del exp_fast5
    d_ref, i_ref = xor_topk(queries[:256], sorted_ids, k=K,
                            valid=jnp.arange(N) < n_valid)
    c256 = cert_np[:256]
    exact = bool(np.array_equal(np.asarray(i2[:256])[c256],
                                np.asarray(i_ref)[c256])
                 and np.array_equal(np.asarray(i3), np.asarray(i_ref))
                 and np.array_equal(np.asarray(d3), np.asarray(d_ref)))
    if stage2_rows:
        # the cascade-repaired rows specifically must match the oracle
        bad_rows = np.nonzero(~np.asarray(cert1))[0]
        _, i_bad = xor_topk(queries[bad_rows], sorted_ids, k=K,
                            valid=jnp.arange(N) < n_valid)
        exact = exact and bool(np.array_equal(
            np.asarray(i2)[bad_rows][cert_np[bad_rows]],
            np.asarray(i_bad)[cert_np[bad_rows]]))

    # scalar CPU baseline on the same sorted table
    def pack160(rows):
        """uint32[...,5] limb rows (big-endian limb order) → python ints."""
        return [
            (int(r[0]) << 128) | (int(r[1]) << 96) | (int(r[2]) << 64)
            | (int(r[3]) << 32) | int(r[4])
            for r in np.asarray(rows)
        ]

    sorted_ints = pack160(sorted_ids)
    q_ints = pack160(queries[:64])
    t0 = time.perf_counter()
    for q in q_ints:
        scalar_closest(sorted_ints, q, K)
    scalar_rate = len(q_ints) / (time.perf_counter() - t0)

    out = {
        "metric": f"batched findClosestNodes top-{K}, {Q} queries x {N} ids "
                  f"({platform}); two-stage cascade, device-serialized "
                  f"chain slope (median of {samples}), "
                  f"{per_batch * 1e3:.1f} ms/batch incl. on-device repair "
                  f"of {stage2_rows} rows, certified {cert_frac:.5f}, "
                  f"exact={exact}",
        "value": round(rate, 1),
        "unit": "lookups/s/chip",
        "vs_baseline": round(rate / scalar_rate, 2),
    }
    # full capture (value + run-to-run range) for the docs: README/PARITY
    # quote this file verbatim and ci/check_docs.py enforces agreement
    capture = dict(out)
    capture.update({
        "ms_per_batch": round(per_batch * 1e3, 2),
        "ms_range": [round(dt_lo * 1e3, 2), round(dt_hi * 1e3, 2)],
        "rate_range": [round(Q / dt_hi, 1), round(Q / dt_lo, 1)],
        "certified": cert_frac,
        "stage2_rows": stage2_rows,
        "residual_uncertified": n_uncert,
        "stride": HEADLINE_STRIDE,
        "planes": 2,
        "lut_bits": lut_bits,
        "N": N, "Q": Q, "k": K,
    })
    try:
        if on_accel:
            with open(os.path.join(os.path.dirname(
                    os.path.abspath(__file__)), "bench_capture.json"),
                    "w") as f:
                json.dump(capture, f, indent=1)
    except OSError:
        pass
    return out


def profile(N: int = None, Q: int = None) -> list:
    """Per-stage chain-slope breakdown of the headline lookup kernel,
    plus candidate variants (window stride, positioning depth).  Each
    stage is timed as its own device-serialized rep chain; stage deltas
    locate the wall-clock (positioning / row gather / in-window select /
    certificate).  Prints one JSON line per measurement.
    """
    from opendht_tpu.ops.sorted_table import _lower_bound

    platform = jax.devices()[0].platform
    on_accel = platform not in ("cpu",)
    N = N or (1_000_000 if on_accel else 100_000)
    Q = Q or (131_072 if on_accel else 8_192)
    lut_bits = default_lut_bits(N)

    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    table = jax.random.bits(k1, (N, 5), dtype=jnp.uint32)
    queries = jax.random.bits(k2, (Q, 5), dtype=jnp.uint32)
    sorted_ids, perm, n_valid = jax.block_until_ready(sort_table(table))
    lut = jax.block_until_ready(
        build_prefix_lut(sorted_ids, n_valid, bits=lut_bits))
    # 2-plane expansions — the shipped headline geometry (round 5)
    exp64 = jax.block_until_ready(expand_table(sorted_ids, limbs=2))
    exp32 = jax.block_until_ready(
        expand_table(sorted_ids, stride=32, limbs=2))
    exp32_5 = jax.block_until_ready(expand_table(sorted_ids, stride=32))

    out = []

    def stage(name, body, *consts, r1=2, r2=8):
        dt = chain_slope(body, queries, *consts, r1=r1, r2=r2)
        rec = {"stage": name, "ms_per_batch": round(dt * 1e3, 3),
               "lookups_per_s": round(Q / dt, 1)}
        print(json.dumps(rec), flush=True)
        out.append(rec)
        return dt

    def pos_body(steps):
        def body(q, sorted_ids, n_valid, lut):
            p = _lower_bound(sorted_ids, q, n_valid, lut=lut,
                             lut_steps=steps)
            return jnp.sum(p.astype(jnp.float32))
        return body

    stage("pos lut%d steps=6" % lut_bits, pos_body(6),
          sorted_ids, n_valid, lut)
    stage("pos lut%d steps=0" % lut_bits, pos_body(0),
          sorted_ids, n_valid, lut)

    def gather_body(stride):
        def body(q, sorted_ids, n_valid, lut, expanded):
            p = _lower_bound(sorted_ids, q, n_valid, lut=lut, lut_steps=0)
            NB = expanded.shape[0]
            j = jnp.clip((p - stride) // stride, 0, NB - 1)
            rows = jnp.take(expanded, j, axis=0)
            return jnp.sum(rows, dtype=jnp.uint32).astype(jnp.float32)
        return body

    stage("pos0 + row gather s=64", gather_body(64),
          sorted_ids, n_valid, lut, exp64)
    stage("pos0 + row gather s=32", gather_body(32),
          sorted_ids, n_valid, lut, exp32)

    def full_body(select, steps, planes):
        def body(q, sorted_ids, expanded, n_valid, lut):
            d, idx, c = expanded_topk(sorted_ids, expanded, n_valid, q, k=K,
                                      select=select, lut=lut,
                                      lut_steps=steps, planes=planes)
            return (jnp.sum(c.astype(jnp.float32))
                    + jnp.sum(idx[:, 0].astype(jnp.float32)) * 1e-9)
        return body

    for name, expd, steps, select, planes in [
        ("full fast2 s=64 steps=0 planes=2", exp64, 0, "fast2", 2),
        ("full fast2 s=32 steps=6 planes=2", exp32, 6, "fast2", 2),
        ("full fast2 s=32 steps=0 planes=2", exp32, 0, "fast2", 2),
        ("full fast2 s=32 steps=0 planes=5 (pre-r5)", exp32_5, 0,
         "fast2", 5),
        ("full fast3 s=32 steps=0", exp32_5, 0, "fast3", 5),
    ]:
        stage(name, full_body(select, steps, planes), sorted_ids, expd,
              n_valid, lut)
        _, _, c = jax.block_until_ready(
            expanded_topk(sorted_ids, expd, n_valid, queries, k=K,
                          select=select, lut=lut, lut_steps=steps,
                          planes=planes))
        rec = {"stage": "certified fraction", "value":
               float(np.asarray(c).mean())}
        print(json.dumps(rec), flush=True)
        out.append(rec)

    # the full headline pipeline (stage-1 fast path + on-device repair)
    def casc_body(q, sorted_ids, e32, e64, n_valid, lut):
        d, idx, c = cascade_topk(sorted_ids, e32, e64, n_valid, q, lut,
                                 k=K, select="fast2", cap=HEADLINE_CAP,
                                 planes=2)
        return (jnp.sum(c.astype(jnp.float32))
                + jnp.sum(idx[:, 0].astype(jnp.float32)) * 1e-9)

    r1c, r2c = (8, 64) if on_accel else (2, 8)
    stage("cascade s=32 cap=%d (headline)" % HEADLINE_CAP, casc_body,
          sorted_ids, exp32, exp64, n_valid, lut, r1=r1c, r2=r2c)
    return out


def main(argv=None):
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--profile", action="store_true",
                   help="per-stage kernel breakdown instead of the headline")
    p.add_argument("-N", type=int, default=0)
    p.add_argument("-Q", type=int, default=0)
    args = p.parse_args(argv)
    ensure_compile_cache()
    if args.profile:
        profile(args.N or None, args.Q or None)
    else:
        print(json.dumps(measure()))


if __name__ == "__main__":
    main()
