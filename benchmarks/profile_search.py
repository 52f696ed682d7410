"""Per-stage chain-slope profile of the ROUND-FUSED iterative engine.

The config-3 wave (core/search.py simulate_lookups) is a while-loop of
rounds; this driver times each round *component* as its own
device-serialized chain so the next optimization targets the measured
dominator — the method that produced round 3's 63K→171K (profile →
rebuild the dominant stage).  Stages mirror the ROUND-6 fused round
body (core/search.py _lookup_engine): the per-round positioning search
the pre-round-5 engine carried (85% of the round, exp_round_r5.py) is
GONE — reply blocks are positioned from the carried candidate distance
limb through one stacked LUT read — so the decomposition is now

    s1  lower(targets)            once per wave (bootstrap positioning)
    s2  alpha-select + carried-d0 masked max-reductions (per round)
    s3  stacked LUT block-bounds  one [2,...] take for both edges
    s4  fused reply gather        ONE [W·α·k] × NL-plane table gather —
                                  the round's only table access
    s5  merge sorts               2× [W, S+R] lax.sort (insert + dedupe)
    wave                          full simulate_lookups (ties the
                                  decomposition back to config 3)

Stages use the same primitives the engine injects (built inside each
stage body from argument arrays, as bench.chain_slope's ``consts``
contract asks).  ``--smoke`` (the ci/run_ci.sh
entry) runs the full decomposition at a small shape and fails on any
stage erroring or the wave slope exceeding a generous ceiling — a
stage-level compile break or order-of-magnitude stall fails CI without
the full bench.  The cost-model complement (deterministic per-kernel
flops/bytes this driver's stages move) is the kernel ledger:
``python -c "from opendht_tpu import profiling;
print(profiling.get_ledger().compute())"`` or the ``kernels`` REPL
command; ``ci/perf_gate.py`` gates it.

Usage::  python benchmarks/profile_search.py [-N 10000000] [-W 16384]
         python benchmarks/profile_search.py --smoke     # CI entry
         python benchmarks/profile_search.py --profile /tmp/prof
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import driver_common as dc         # noqa: E402  (puts the repo root on sys.path)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("-N", type=int, default=0)
    p.add_argument("-W", type=int, default=0, help="wave width")
    p.add_argument("--stages", type=str, default="",
                   help="comma-separated subset (s1,s2,s3,s4,s5,wave); "
                        "empty = all")
    p.add_argument("--smoke", action="store_true",
                   help="small-shape CI smoke: every stage must produce "
                        "a slope and the wave must stay under a generous "
                        "ceiling")
    dc.add_profile_arg(p)
    args = p.parse_args(argv)
    want = set(args.stages.split(",")) if args.stages else None

    import jax
    import jax.numpy as jnp
    from jax import lax
    from bench import chain_slope
    from opendht_tpu.ops.ids import N_LIMBS, clz32
    from opendht_tpu.ops.sorted_table import (sort_table, build_prefix_lut,
                                              default_lut_bits,
                                              fused_gather_planar)
    from opendht_tpu.core import search as SE

    on_accel = jax.devices()[0].platform != "cpu"
    if args.smoke:
        N = args.N or 65_536
        W = args.W or 1_024
    else:
        N = args.N or (10_000_000 if on_accel else 100_000)
        W = args.W or (16_384 if on_accel else 1_024)
    NL = 2                                  # state_limbs=2 (config3 default)
    ALPHA, S, K = 3, 14, 8
    R = ALPHA * K
    _U32 = jnp.uint32

    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    table = jax.random.bits(k1, (N, 5), dtype=jnp.uint32)
    targets = jax.random.bits(k2, (W, 5), dtype=jnp.uint32)
    sorted_ids, _p, n_valid = jax.block_until_ready(sort_table(table))
    lut = jax.block_until_ready(build_prefix_lut(
        sorted_ids, n_valid, bits=default_lut_bits(N)))
    del table
    n = jnp.asarray(n_valid, jnp.int32)

    # The primitives simulate_lookups injects are built INSIDE each
    # stage body from argument arrays (chain_slope's ``consts``
    # contract): the table and LUT stay arguments of the executable.
    def make_prims(si, l):
        lower = SE._guarded_lower_bound(si, n, l)
        st = si.T

        def gather_planar(rows, limbs=N_LIMBS):
            return fused_gather_planar(st, rows, limbs)
        return lower, gather_planar

    failures = []
    results = {}

    def stage(name, body, *consts, r1=2, r2=8):
        """One chain-slope measurement; a failing stage is recorded and
        must not kill the remaining stages (but --smoke fails on it)."""
        sid = name.split()[0]
        if want is not None and sid not in want:
            return None
        try:
            dt = chain_slope(body, targets, *consts, r1=r1, r2=r2)
        except Exception as e:                      # record and continue
            dc.emit({"stage": name, "error": str(e)[:200]},
                    name="profile_search")
            failures.append(sid)
            return None
        results[sid] = dt
        dc.emit(dc.slope_record(name, dt), name="profile_search")
        return dt

    # representative per-round operands
    rng = np.random.default_rng(0)
    new_rows = jnp.asarray(rng.integers(0, N, size=(W, R), dtype=np.int32))
    cand_node = jnp.asarray(rng.integers(0, N, size=(W, S), dtype=np.int32))
    cand_l = [jax.random.bits(jax.random.PRNGKey(7 + l), (W, S),
                              dtype=jnp.uint32) for l in range(NL)]
    queried = jnp.asarray((rng.random((W, S)) < 0.5).astype(np.int32))

    with dc.profile_ctx(args.profile):
        # s1: positioning of the full wave (runs ONCE per wave — the
        # bootstrap; the fused round body has no positioning search)
        def s1(q, si, l):
            lower, _ = make_prims(si, l)
            return jnp.sum(lower(q).astype(jnp.float32))
        stage("s1 lower(targets) [once/wave]", s1, sorted_ids, lut,
              r1=4, r2=16)

        # s2: alpha-selection + the carried-d0 reductions (the round-6
        # fusion: the queried peers' top distance limb rides the same
        # masked max-reductions instead of a table gather); cn is
        # perturbed by q — chain_slope's anti-elision contract
        def s2(q, cn, ql, *cl):
            cn = cn + (q[:, :1].astype(jnp.int32) & 1)
            can = (cn >= 0) & (ql == 0)
            rank = jnp.cumsum(can.astype(jnp.int32), axis=1)
            sel = can & (rank <= ALPHA)
            xr = jnp.stack([jnp.max(jnp.where(sel & (rank == j + 1), cn, -1),
                                    axis=1) for j in range(ALPHA)], axis=1)
            xd = jnp.stack([jnp.max(jnp.where(sel & (rank == j + 1), cl[0],
                                              _U32(0)), axis=1)
                            for j in range(ALPHA)], axis=1)
            return (jnp.sum(xr.astype(jnp.float32))
                    + jnp.sum(xd.astype(jnp.float32))) * 1e-9
        stage("s2 alpha-select + carried-d0 reductions", s2, cand_node,
              queried, *cand_l, r1=8, r2=64)

        # s3: the stacked LUT block-bounds read — BOTH edges of every
        # queried peer's prefix block in one [2, ...] take
        # (search.py _lut_block_bounds), all the positioning the fused
        # round does.  The carried d0 stands in for the candidate state,
        # perturbed by q (anti-elision).
        def s3(q, l, *cl):
            x_d0 = cl[0][:, :ALPHA] + (q[:, :1] & _U32(1))
            b = clz32(x_d0)
            lo, ub = SE._lut_block_bounds(l, q[:, 0:1], b + 1)
            return jnp.sum((ub - lo).astype(jnp.float32))
        stage("s3 stacked LUT block-bounds read", s3, lut, *cand_l,
              r1=8, r2=64)

        # s4: the fused reply gather — ONE [W·R] × NL-plane take through
        # the transposed table, the round's only table access.  Indices
        # perturbed by q so the stage consumes the rep-perturbed input.
        def s4(q, nr, si, l):
            _, gather_planar = make_prims(si, l)
            nr2 = (nr + (q[:, :1].astype(jnp.int32) & 1)) % N
            g = gather_planar(nr2, NL)
            return sum(jnp.sum(x.astype(jnp.float32)) * 1e-9 for x in g)
        stage("s4 fused reply gather [W,%d] x %d limbs" % (R, NL), s4,
              new_rows, sorted_ids, lut)

        # s5: the two merge sorts (insert + dedupe — search.py merge())
        def s5(q, cn, ql, nr, si, l, *cl):
            _, gather_planar = make_prims(si, l)
            cl = list(cl)
            new_l = gather_planar(nr, NL)
            node = jnp.concatenate([cn, nr], axis=1)
            d_l = [jnp.concatenate([cl[i], new_l[i] ^ q[:, i:i + 1]], axis=1)
                   for i in range(NL)]
            qd = jnp.concatenate([ql, jnp.zeros((W, R), jnp.int32)], axis=1)
            inv = (node < 0).astype(jnp.int32)
            out = lax.sort((inv,) + tuple(d_l) + (node, 1 - qd),
                           dimension=1, num_keys=3 + NL)
            node_s = out[1 + NL]
            dup = jnp.concatenate(
                [jnp.zeros((W, 1), bool),
                 (node_s[:, 1:] == node_s[:, :-1]) & (node_s[:, 1:] >= 0)],
                axis=1)
            inv2 = jnp.where(dup, 1, out[0])
            out2 = lax.sort((inv2,) + tuple(out[1:1 + NL])
                            + (node_s, out[2 + NL]),
                            dimension=1, num_keys=2 + NL)
            return jnp.sum(out2[1 + NL][:, :S].astype(jnp.float32)) * 1e-9
        stage("s5 merge sorts (2x [W,%d])" % (S + R), s5, cand_node,
              queried, new_rows, sorted_ids, lut, *cand_l)

        # full wave for reference (ties the decomposition to config 3)
        def wave(q, si, nv, l):
            o = SE.simulate_lookups(si, nv, q, alpha=ALPHA, k=K, lut=l,
                                    state_limbs=NL)
            return (jnp.sum(o["hops"].astype(jnp.float32))
                    + jnp.sum(o["converged"].astype(jnp.float32)))
        dt = stage("wave simulate_lookups [W=%d]" % W, wave, sorted_ids,
                   n_valid, lut, r1=1, r2=4)

    if dt is not None:
        hops_out = jax.block_until_ready(SE.simulate_lookups(
            sorted_ids, n_valid, targets, alpha=ALPHA, k=K, lut=lut,
            state_limbs=NL))
        p50 = int(np.percentile(np.asarray(hops_out["hops"]), 50))
        dc.emit({"stage": "summary", "wave_ms": round(dt * 1e3, 2),
                 "p50_hops": p50, "N": N, "W": W,
                 "lookups_per_s": round(W / dt, 1)},
                name="profile_search")

    if args.smoke:
        ran = set(results)
        need = ({"s1", "s2", "s3", "s4", "s5", "wave"} if want is None
                else want)
        missing = sorted((need - ran) | set(failures))
        if missing:
            print("SMOKE FAIL: stages errored or missing: %s" % missing,
                  file=sys.stderr)
            return 1
        if "wave" in results and results["wave"] * 1e3 > 3000.0:
            print("SMOKE FAIL: wave slope %.0f ms exceeds the 3000 ms "
                  "smoke ceiling" % (results["wave"] * 1e3),
                  file=sys.stderr)
            return 1
        print("profile_search smoke ok (%d stages)" % len(results))
    return 0


if __name__ == "__main__":
    sys.exit(main())
