"""Churn-round attribution for the LANE-PACKED merge (round 7
tentpole) + the CI churn-merge smoke.

Same fixed-composition (full − variant) methodology as
exp_churn2_r5.py: each variant runs the REAL churn round body — one
device call absorbing E tombstone word writes + E delta appends, the
delta re-sort/expand/LUT, and a Q-query wave through
``churn_lookup_topk`` — with one piece changed, so differences
attribute cost with fusion effects included.  The variants:

  packed      the round at the forced packed width (128//k queries per
              128-lane physical row, ops/sorted_table.
              packed_churn_merge — what merge_pack="auto" resolves to
              on TPU; forced here so the packing is measured on every
              platform)
  unpacked    merge_pack=1 — the pre-round-7 row-per-query merge;
              (unpacked − packed) is the measured lane-packing win at
              this shape, the number the round-5 review asked for
  no_merge    base lookup + delta cascade, results consumed but never
              merged; (full − no_merge) bounds the whole merge stage
  no_rebuild  pre-built delta structures; (full − no_rebuild) is the
              per-round delta re-sort/expand/LUT cost
  static      same-shape plain lookup, no churn structures — the
              denominator of the churny/static ratio

Unlike exp_round_r6.py's hand-mirrored engine body, the merge under
test here IS the shipping kernel — ``--smoke`` asserts
BIT-IDENTITY of merge_pack="auto" vs merge_pack=1 through
``churn_lookup_topk`` itself (fast3 full-limb keys AND the fast2
top-64 + tie-repair form, on a ragged Q), then a generous 1.5×
regression band on the packed round (min of 2 chain-slope samples per
side, the exp_round_r6 flake filter).  The committed property sweep
(tests/test_table_churn.py::test_packed_merge_bit_identical_sweep)
covers pack width × tombstone density × n_valid edges; the smoke
re-proves the shipping default at CI time and gates the round's
latency.

A full run's numbers feed ``captures/churn_packed.json`` (--capture):
per-variant ms, the packed-vs-unpacked delta, and churny/static under
both merge modes on this platform.  The accelerator target
(churny/static ≥ 0.6×, ISSUE 2) is settled only by an accelerator
session running:

  python benchmarks/exp_churn_r7.py --capture churn_packed
  python benchmarks/baseline_configs.py -c 6     # auto-saves config6

(the second auto-saves captures/config6.json on accelerator runs and
the README/PARITY churn quotes then update from the artifacts).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)          # churn_fixtures + driver_common
import driver_common as dc         # noqa: E402  (puts the repo root on sys.path)

VARIANTS = ("packed", "unpacked", "no_merge", "no_rebuild")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--smoke", action="store_true",
                   help="small-shape CI smoke: packed-vs-unpacked "
                        "bit-identity + regression band only")
    p.add_argument("-N", type=int, default=0, help="base table rows")
    p.add_argument("-Q", type=int, default=0, help="lookup wave width")
    p.add_argument("--dcap", type=int, default=0, help="delta capacity")
    p.add_argument("-E", type=int, default=0, help="mutations per round")
    p.add_argument("--capture", default="",
                   help="write captures/<name>.json with the attribution")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax
    from bench import chain_slope
    from opendht_tpu.ops.sorted_table import (
        sort_table, build_prefix_lut, default_lut_bits, expand_table,
        churn_lookup_topk, expanded_topk, cascade_topk)
    import churn_fixtures as FX

    on_accel = jax.devices()[0].platform != "cpu"
    if args.smoke:
        # ragged Q on purpose: Q % 16 != 0 exercises the sentinel-slot
        # tail of the packed rows through the compiled kernel
        N, Q, DCAP, E = (args.N or 65_536), (args.Q or 4_097), \
            (args.dcap or 4_096), (args.E or 128)
    else:
        N, Q, DCAP = FX.sizes(on_accel, dcap=args.dcap)
        if args.N:
            N = args.N
        if args.Q:
            Q = args.Q
        E = args.E or 256
    K = 8
    d_bits = default_lut_bits(DCAP)

    base = FX.build_base(N, Q, limbs=2)
    sorted_ids, expanded = base["sorted_ids"], base["expanded"]
    lut, n_valid, queries = base["lut"], base["n_valid"], base["queries"]

    mut = FX.build_mutations(N, DCAP, E)
    tomb_base, widx, wval = mut["tomb_base"], mut["widx"], mut["wval"]
    dslab, new_ids = mut["dslab"], mut["new_ids"]
    nd0, nd_after = mut["nd0"], mut["nd_after"]

    ds0, (de0, dew0), dlut0, _dnv0 = FX.build_delta_structs(
        dslab.at[nd0:nd0 + E].set(new_ids), nd0 + E, strides=(16, 64))

    def make_round(variant):
        def round_body(q, sorted_ids, expanded, lut, n_valid, tomb_base,
                       widx, wval, dslab, new_ids, nd_after,
                       ds0, de0, dew0, dlut0):
            tomb = tomb_base.at[widx].set(wval)
            if variant == "no_rebuild":
                ds, de, dew, dlut, dnv = ds0, de0, dew0, dlut0, nd_after
            else:
                ds_slab = lax.dynamic_update_slice(
                    dslab, new_ids, (jnp.int32(nd0), 0))
                dvalid = jnp.arange(DCAP) < nd_after
                ds, _dp, dnv = sort_table(ds_slab, dvalid)
                de = expand_table(ds, stride=16, limbs=2)
                dew = expand_table(ds, stride=64, limbs=2)
                dlut = build_prefix_lut(ds, dnv, bits=d_bits)
            if variant == "no_merge":
                # both sides' lookups run and are consumed, but the
                # merge (the packed sort + unpack) never happens
                _d, enc_b, cert_b = expanded_topk(
                    sorted_ids, expanded, n_valid, q, k=K, select="fast2",
                    lut=lut, lut_steps=0, planes=2, tomb_bits=tomb)
                _dd, enc_d, cert_d = cascade_topk(
                    ds, de, dew, dnv, q, dlut, k=K, select="fast2",
                    cap=4096, planes=2, fast2_limbs=True)
                return (jnp.sum(cert_b.astype(jnp.float32))
                        + jnp.sum(cert_d.astype(jnp.float32))
                        + jnp.sum(enc_b[:, 0].astype(jnp.float32)) * 1e-9
                        + jnp.sum(enc_d[:, 0].astype(jnp.float32)) * 1e-9)
            # force the packed width so the attribution measures the
            # packing on EVERY platform ("auto" resolves to unpacked
            # off-TPU — the backend split this driver's numbers set)
            mp = 1 if variant == "unpacked" else 128 // K
            _dist, enc, cert = churn_lookup_topk(
                sorted_ids, expanded, n_valid, tomb, ds, de, dnv, q,
                lut=lut, d_lut=dlut, d_exp_wide=dew, k=K, select="fast2",
                lut_steps=0, planes=2, d_cap=4096, merge_pack=mp)
            return (jnp.sum(cert.astype(jnp.float32))
                    + jnp.sum(enc[:, 0].astype(jnp.float32)) * 1e-9)
        return round_body

    def static_body(q, sorted_ids, expanded, lut, n_valid):
        d, idx, c = expanded_topk(sorted_ids, expanded, n_valid, q, k=K,
                                  select="fast2", lut=lut, lut_steps=0,
                                  planes=2)
        return (jnp.sum(c.astype(jnp.float32))
                + jnp.sum(idx[:, 0].astype(jnp.float32)) * 1e-9)

    if args.smoke:
        # 1) packed vs unpacked bit-identity through the SHIPPING
        # kernel, both merge key forms, ragged Q, live tombstones
        tomb = tomb_base.at[widx].set(wval)
        common = dict(lut=lut, d_lut=dlut0, k=K)
        for sel, kw in (("fast2", dict(d_exp_wide=dew0, lut_steps=0,
                                       planes=2, d_cap=4096)),
                        ("fast3", dict())):
            exp_sel = expanded if sel == "fast2" else expand_table(sorted_ids)
            de_sel = de0 if sel == "fast2" else expand_table(ds0, stride=32)
            d1, e1, _ = churn_lookup_topk(
                sorted_ids, exp_sel, n_valid, tomb, ds0, de_sel, nd_after,
                queries, select=sel, merge_pack=1, **common, **kw)
            d2, e2, _ = churn_lookup_topk(
                sorted_ids, exp_sel, n_valid, tomb, ds0, de_sel, nd_after,
                queries, select=sel, merge_pack=128 // K, **common, **kw)
            if not np.array_equal(np.asarray(e1), np.asarray(e2)) or (
                    d1 is not None
                    and not np.array_equal(np.asarray(d1), np.asarray(d2))):
                print(f"SMOKE FAIL: packed merge diverges from unpacked "
                      f"({sel}, Q={Q})")
                return 1
        # 2) regression band: min of 2 slope samples per side filters
        # one-sided host-load stalls (the exp_round_r6 pattern)
        wp, wu = make_round("packed"), make_round("unpacked")
        cargs = (queries, sorted_ids, expanded, lut, n_valid, tomb_base,
                 widx, wval, dslab, new_ids, nd_after, ds0, de0, dew0,
                 dlut0)
        dts_p = [chain_slope(wp, *cargs, r1=1, r2=3) for _ in range(2)]
        dts_u = [chain_slope(wu, *cargs, r1=1, r2=3) for _ in range(2)]
        dt_p, dt_u = min(dts_p), min(dts_u)
        print(json.dumps({
            "smoke": True, "N": N, "Q": Q, "DCAP": DCAP,
            "packed_ms": round(dt_p * 1e3, 3),
            "unpacked_ms": round(dt_u * 1e3, 3),
            "samples_ms": [round(d * 1e3, 2) for d in dts_p + dts_u],
            "bit_identical": True}), flush=True)
        if dt_p > 1.5 * dt_u:
            print(f"SMOKE FAIL: packed churn round {dt_p * 1e3:.2f} ms > "
                  f"1.5x unpacked {dt_u * 1e3:.2f} ms (min of 2 each)")
            return 1
        print("churn-merge smoke ok")
        return 0

    cargs = (queries, sorted_ids, expanded, lut, n_valid, tomb_base,
             widx, wval, dslab, new_ids, nd_after, ds0, de0, dew0, dlut0)
    r1, r2 = (2, 8) if on_accel else (2, 6)
    recs = []
    for v in VARIANTS:
        dt = chain_slope(make_round(v), *cargs, r1=r1, r2=r2)
        recs.append({"variant": v, "ms": round(dt * 1e3, 3)})
        print(json.dumps(recs[-1]), flush=True)
    static_dt = chain_slope(static_body, queries, sorted_ids, expanded,
                            lut, n_valid, r1=r1, r2=r2)
    recs.append({"variant": "static", "ms": round(static_dt * 1e3, 3)})
    print(json.dumps(recs[-1]), flush=True)

    by = {r["variant"]: r["ms"] for r in recs}
    bound = {
        "platform": jax.devices()[0].platform,
        "N": N, "Q": Q, "DCAP": DCAP, "E": E, "k": K,
        "merge_pack_auto": 128 // K,
        # the tentpole's number: what the lane packing saves per round
        "packing_saves_ms": round(by["unpacked"] - by["packed"], 3),
        "merge_stage_ms": round(by["packed"] - by["no_merge"], 3),
        "delta_rebuild_ms": round(by["packed"] - by["no_rebuild"], 3),
        "churny_vs_static_packed": round(by["static"] / by["packed"], 4),
        "churny_vs_static_unpacked": round(by["static"] / by["unpacked"],
                                           4),
    }
    print(json.dumps({"bound": bound}), flush=True)
    if args.capture:
        out = {
            "metric": ("lane-packed churn merge attribution, full-minus-"
                       "variant over the real round body (tombstone "
                       "writes + delta rebuild + churn_lookup_topk), "
                       "Q=%d x N=%d, DCAP=%d, E=%d, k=%d, platform=%s; "
                       "packed vs unpacked merge bit-identity asserted "
                       "through the shipping kernel; value = packed "
                       "round ms (device round only — host prep and "
                       "amortized compaction excluded, unlike config6's "
                       "sustained figure)"
                       % (Q, N, DCAP, E, K, jax.devices()[0].platform)),
            "value": by["packed"],
            "unit": "ms/round (%s)" % jax.devices()[0].platform,
            "vs_baseline": bound["churny_vs_static_packed"],
            "variants": recs,
            "bound": bound,
        }
        if not on_accel:
            out["accelerator_target"] = (
                "churny/static >= 0.6x (ISSUE 2) is OPEN: this capture "
                "is cpu, and the 128-lane padding tax the packed merge "
                "amortizes exists only in TPU tiled layout — on cpu the "
                "slot-segmented sort is expected ~neutral (the "
                "packing_saves_ms field records the measured value).  "
                "Settle it with the two commands in this driver's "
                "docstring on an accelerator session.")
        dc.write_capture(args.capture, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
