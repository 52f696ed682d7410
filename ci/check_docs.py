"""Docs-vs-capture consistency check.

EVERY quoted perf number in README.md / PARITY.md must agree with a
committed capture artifact — the checker exists to catch stale quotes
(2x-class drift, the round-1/round-2 failure mode), not day-to-day
variance.  Two artifact kinds:

- ``bench_capture.json`` (written by bench.measure on accelerator
  hardware): the headline.  Docs lines carrying the invisible marker
  ``<!-- bench:headline -->`` are checked against it, inside the
  captured run-to-run range widened by 10% (15% for ms/batch).
- ``captures/<name>.json`` (written by benchmarks/baseline_configs.py
  save_capture, one per BASELINE config): docs lines carrying
  ``<!-- capture:<name> -->`` are checked against that file's
  ``value`` within ±15% (single-slope configs have no captured range;
  15% covers run-to-run wander while still catching stale quotes).  Extra structured fields are checked where quoted:
  ``p50 X ms`` vs ``wave_ms_p50`` (±30%) and ``XK mutations/s`` vs
  ``mutations_per_s`` (±15%).  Captures with ``unit: "percent"`` (the
  telemetry/tracing overhead artifacts) check ``measures X%`` quotes
  against ``value`` and ``X% with sampling off`` against
  ``sampling_off_pct``, within max(1 percentage point, 50% relative)
  — overhead numbers are noise-level, so the band is absolute-floored
  while still catching the 2x-class drift this checker exists for.

For every capture artifact that exists, at least one tagged line must
exist in README.md — a quote cannot silently disappear.  Usage:
``python ci/check_docs.py`` (exit 1 on drift).
"""

import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SUFFIX = {"K": 1e3, "M": 1e6, "B": 1e9}

# capture name -> whether README must carry a tagged quote.  Exploration
# artifacts (``*_custom``) and redundant shapes are never doc-enforced.
_OPTIONAL = ("config3_tp",)


def _para_at(lines, idx):
    """The markdown paragraph (contiguous non-blank lines) containing
    line ``idx``, joined with spaces — wrapped prose puts a tag's
    quoted figures on neighboring lines.  The ONE copy of the
    boundary scan every paragraph-scoped rule uses."""
    lo = idx
    while lo > 0 and lines[lo - 1].strip():
        lo -= 1
    hi = idx
    while hi + 1 < len(lines) and lines[hi + 1].strip():
        hi += 1
    return " ".join(lines[lo:hi + 1])


def _rate_quotes(line):
    """All 'X.XX[KMB] <unit>/s' figures on a doc line."""
    return [(float(v) * _SUFFIX[s], v + s)
            for v, s in re.findall(
                r"(\d+(?:\.\d+)?)([KMB]) (?:converged )?"
                r"(?:lookups|ids)/s", line)]


def check_headline(failures):
    cap_path = os.path.join(ROOT, "bench_capture.json")
    if not os.path.exists(cap_path):
        print("check_docs: no bench_capture.json (no accelerator capture "
              "yet) — skipping headline")
        return None
    with open(cap_path) as f:
        cap = json.load(f)
    lo, hi = cap["rate_range"]
    for name in ("README.md", "PARITY.md"):
        path = os.path.join(ROOT, name)
        if not os.path.exists(path):
            continue
        tagged = [ln for ln in open(path).read().splitlines()
                  if "bench:headline" in ln]
        if not tagged:
            failures.append(f"{name}: no '<!-- bench:headline -->'-tagged "
                            f"headline quote found")
            continue
        for ln in tagged:
            quoted = re.findall(r"(\d+(?:\.\d+)?)M lookups/s", ln)
            if not quoted:
                failures.append(f"{name}: tagged line quotes no "
                                f"'X.XXM lookups/s' figure: {ln.strip()!r}")
            for q in quoted:
                rate = float(q) * 1e6
                if not (lo * 0.90 <= rate <= hi * 1.10):
                    failures.append(
                        f"{name}: quotes {q}M lookups/s — outside the "
                        f"captured run-to-run range [{lo / 1e6:.2f}M, "
                        f"{hi / 1e6:.2f}M] +/-10% "
                        f"(median {cap['value'] / 1e6:.2f}M)")
            for q in re.findall(r"(\d+(?:\.\d+)?) ?ms/batch", ln):
                if abs(float(q) - cap["ms_per_batch"]) > 0.1 + 0.15 * cap[
                        "ms_per_batch"]:
                    failures.append(
                        f"{name}: quotes {q} ms/batch vs captured "
                        f"{cap['ms_per_batch']:.1f}")
    return cap


def check_config_captures(failures):
    """Each captures/<name>.json must back at least one tagged README
    quote, every tagged quote must sit within its band, and — the
    other direction — every ``<!-- capture:name -->`` tag in the docs
    must have its artifact on disk (a tag whose artifact is missing
    would otherwise be silently unenforced)."""
    checked = []
    readme = os.path.join(ROOT, "README.md")
    docs = {}
    for name in ("README.md", "PARITY.md"):
        path = os.path.join(ROOT, name)
        if os.path.exists(path):
            docs[name] = open(path).read().splitlines()
    for doc, lines in docs.items():
        for ln in lines:
            for tag in re.findall(r"<!-- capture:([\w-]+) -->", ln):
                if not os.path.exists(os.path.join(ROOT, "captures",
                                                   tag + ".json")):
                    failures.append(
                        f"{doc}: tagged quote 'capture:{tag}' has no "
                        f"captures/{tag}.json artifact — the quote is "
                        f"unenforced")
    for cap_path in sorted(glob.glob(os.path.join(ROOT, "captures",
                                                  "*.json"))):
        cname = os.path.splitext(os.path.basename(cap_path))[0]
        if cname.endswith("_custom"):
            continue                      # exploration shape, not quotable
        with open(cap_path) as f:
            cap = json.load(f)
        # full marker, not substring: 'capture:config3' must not match
        # lines tagged capture:config3_star / _tp / _latency
        tag = f"<!-- capture:{cname} -->"
        any_tagged = False
        for doc, lines in docs.items():
            for li, ln in enumerate(lines):
                if tag not in ln:
                    continue
                any_tagged = True
                para = _para_at(lines, li)
                # only the line's FIRST rate figure is the artifact's
                # primary value; later figures on the same line quote
                # secondary fields (e.g. the latency sweep's per-wave
                # rates), each checked by its own field rule below
                for rate, txt in _rate_quotes(ln)[:1]:
                    if not (0.85 * cap["value"] <= rate
                            <= 1.15 * cap["value"]):
                        failures.append(
                            f"{doc}: [{tag}] quotes {txt} vs captured "
                            f"{cap['value']:.1f} {cap.get('unit', '')} "
                            f"(±15%)")
                if "wave_ms_p50" in cap:
                    for q in re.findall(r"p50 (\d+(?:\.\d+)?) ?ms", ln):
                        if not (0.7 * cap["wave_ms_p50"] <= float(q)
                                <= 1.3 * cap["wave_ms_p50"]):
                            failures.append(
                                f"{doc}: [{tag}] quotes p50 {q} ms vs "
                                f"captured {cap['wave_ms_p50']} (±30%)")
                if "mutations_per_s" in cap:
                    for q in re.findall(
                            r"(\d+(?:\.\d+)?)K mutations/s", ln):
                        if not (0.85 * cap["mutations_per_s"]
                                <= float(q) * 1e3
                                <= 1.15 * cap["mutations_per_s"]):
                            failures.append(
                                f"{doc}: [{tag}] quotes {q}K mutations/s "
                                f"vs captured {cap['mutations_per_s']:.0f} "
                                f"(±15%)")
                bound = cap.get("bound", {})
                # round-10 maintenance attribution: the amortization
                # factor and the per-stage ms figures quoted in the
                # docs must track the committed capture
                if "republish_amortization_x" in bound:
                    for q in re.findall(r"(\d+(?:\.\d+)?)× amortization",
                                        para):
                        w = bound["republish_amortization_x"]
                        if not (0.85 * w <= float(q) <= 1.15 * w):
                            failures.append(
                                f"{doc}: [{tag}] quotes {q}x amortization "
                                f"vs captured {w} (±15%)")
                    for pat, field in (
                            (r"republish resolve (?:at )?(\d+(?:\.\d+)?) ms",
                             "republish_batched_ms"),
                            (r"(\d+(?:\.\d+)?) ms(?:/key| per batch-1)",
                             "republish_per_key_ms_each"),
                            (r"fused sweep (?:at )?(\d+(?:\.\d+)?) ms",
                             "sweep_fused_ms"),
                            (r"(\d+(?:\.\d+)?) ms split",
                             "sweep_split_ms")):
                        for q in re.findall(pat, para):
                            w = bound[field]
                            if not (0.85 * w <= float(q) <= 1.15 * w):
                                failures.append(
                                    f"{doc}: [{tag}] quotes {q} ms vs "
                                    f"captured {field}={w} (±15%)")
                # round-12 ingest attribution: the per-op amortization
                # factor and both per-op µs figures quoted in the docs
                # must track captures/ingest_wave.json
                if "ingest_amortization_x" in bound:
                    for q in re.findall(
                            r"(\d+(?:\.\d+)?)× per-op amortization", para):
                        w = bound["ingest_amortization_x"]
                        if not (0.85 * w <= float(q) <= 1.15 * w):
                            failures.append(
                                f"{doc}: [{tag}] quotes {q}x per-op "
                                f"amortization vs captured {w} (±15%)")
                    for pat, field in (
                            (r"(\d+(?:\.\d+)?) ?µs/op per-op",
                             "per_op_us"),
                            (r"(\d+(?:\.\d+)?) ?µs/op coalesced",
                             "coalesced_us_per_op")):
                        for q in re.findall(pat, para):
                            w = bound[field]
                            if not (0.85 * w <= float(q) <= 1.15 * w):
                                failures.append(
                                    f"{doc}: [{tag}] quotes {q} µs/op vs "
                                    f"captured {field}={w} (±15%)")
                if cap.get("unit") == "percent":
                    def _pct_band(quoted, captured, what):
                        tol = max(1.0, 0.5 * abs(captured))
                        if abs(quoted - captured) > tol:
                            failures.append(
                                f"{doc}: [{tag}] quotes {what} "
                                f"{quoted}% vs captured {captured} "
                                f"(±{tol:.1f}pp)")
                    for q in re.findall(r"measures (\d+(?:\.\d+)?)%", ln):
                        _pct_band(float(q), cap["value"], "overhead")
                    if "sampling_off_pct" in cap:
                        for q in re.findall(
                                r"(-?\d+(?:\.\d+)?)% with sampling off",
                                ln):
                            _pct_band(float(q), cap["sampling_off_pct"],
                                      "sampling-off overhead")
        if not any_tagged and os.path.exists(readme) \
                and cname not in _OPTIONAL:
            failures.append(f"README.md: no '{tag}'-tagged quote "
                            f"for committed capture {cname}.json")
        checked.append(cname)
    return checked


def check_tp_wire(failures):
    """Round-13 rule, BOTH directions: README and PARITY must each
    carry a ``<!-- tp:wire -->``-tagged paragraph quoting the
    t-sharded engine's in-loop collective budget — the per-hop
    bytes/query figure ('NNN B per query per hop') and the in-loop
    site count ('N in-loop collective') — and every quoted figure must
    EQUAL the committed TP_SCALING.json (the values are read off the
    compiled HLO, deterministic, so the band is exact).  A regenerated
    artifact with stale quotes fails; a quote with no artifact backing
    fails via the missing-tag branch."""
    tp_path = os.path.join(ROOT, "TP_SCALING.json")
    if not os.path.exists(tp_path):
        failures.append("TP_SCALING.json missing — regenerate with "
                        "python benchmarks/tp_scaling.py")
        return
    with open(tp_path) as f:
        rows = json.load(f).get("rows") or []
    if not rows:
        failures.append("TP_SCALING.json has no rows")
        return
    want_bytes = rows[0]["bytes_per_local_query_per_hop"]
    want_sites = rows[0]["collective_sites_in_loop"]
    for name in ("README.md", "PARITY.md"):
        path = os.path.join(ROOT, name)
        if not os.path.exists(path):
            continue
        lines = open(path).read().splitlines()
        tagged = [i for i, ln in enumerate(lines) if "<!-- tp:wire -->" in ln]
        if not tagged:
            failures.append(f"{name}: no '<!-- tp:wire -->'-tagged "
                            f"paragraph quoting the t-sharded collective "
                            f"budget (TP_SCALING.json)")
            continue
        for li in tagged:
            para = _para_at(lines, li)
            quoted_b = [float(v) for v in re.findall(
                r"(\d+(?:\.\d+)?) ?B(?:ytes)? per query per hop", para)]
            quoted_s = [int(v) for v in re.findall(
                r"(\d+) in-loop collective", para)]
            if not quoted_b:
                failures.append(f"{name}: [tp:wire] paragraph quotes no "
                                f"'NNN B per query per hop' figure")
            for qb in quoted_b:
                if qb != float(want_bytes):
                    failures.append(
                        f"{name}: [tp:wire] quotes {qb:g} B per query per "
                        f"hop vs TP_SCALING.json {want_bytes} (exact match "
                        f"required — the value is read off the HLO)")
            if not quoted_s:
                failures.append(f"{name}: [tp:wire] paragraph quotes no "
                                f"'N in-loop collective' count")
            for qs in quoted_s:
                if qs != int(want_sites):
                    failures.append(
                        f"{name}: [tp:wire] quotes {qs} in-loop "
                        f"collective(s) vs TP_SCALING.json {want_sites}")


#: overhead-acceptance artifacts (the round-14 health rule, extended
#: round 15 to the keyspace observatory and round 16 to the hot-cache
#: probe): each capture must beat its own recorded acceptance bound,
#: and both docs must state the bound
_OVERHEAD_CAPS = ("health_overhead", "keyspace_overhead",
                  "cache_overhead", "history_overhead",
                  "waterfall_overhead", "pipeutil_overhead",
                  "peers_overhead", "listener_overhead")


def check_overhead_captures(failures):
    """Rounds 14/15 rule, BOTH directions and for EVERY overhead
    artifact in :data:`_OVERHEAD_CAPS`: the measured on-cost
    acceptance (<1% on the 8192-wave round) is quote-enforced against
    ``captures/<name>.json`` — (1) the artifact itself must satisfy
    the acceptance bound it records (``value`` < ``acceptance_pct``: a
    regression that pushes the instrumented path past its budget fails
    CI here even before the docs drift), and (2) README *and* PARITY
    must each carry a ``<!-- capture:<name> -->``-tagged paragraph
    stating the ``<{acceptance}%`` bound next to the measured quote
    (the generic percent rule in check_config_captures checks the
    measured value; this rule checks the *claim* survives in both
    docs)."""
    for cname in _OVERHEAD_CAPS:
        cap_path = os.path.join(ROOT, "captures", cname + ".json")
        if not os.path.exists(cap_path):
            continue
        with open(cap_path) as f:
            cap = json.load(f)
        acc = float(cap.get("acceptance_pct", 1.0))
        if cap["value"] >= acc:
            failures.append(
                f"captures/{cname}.json: measured overhead "
                f"{cap['value']}% breaks its own <{acc:g}% acceptance "
                f"bound — the instrumented path got expensive")
        tag = f"<!-- capture:{cname} -->"
        for name in ("README.md", "PARITY.md"):
            path = os.path.join(ROOT, name)
            if not os.path.exists(path):
                continue
            lines = open(path).read().splitlines()
            tagged = [i for i, ln in enumerate(lines) if tag in ln]
            if not tagged:
                failures.append(f"{name}: no '{tag}'-tagged paragraph "
                                f"quoting the {cname} measurement")
                continue
            for li in tagged:
                para = _para_at(lines, li)
                quoted = re.findall(r"<(\d+(?:\.\d+)?)% acceptance", para)
                if not quoted:
                    failures.append(
                        f"{name}: [capture:{cname}] paragraph "
                        f"states no '<N% acceptance' bound")
                for q in quoted:
                    if float(q) != acc:
                        failures.append(
                            f"{name}: [capture:{cname}] states a "
                            f"<{q}% acceptance vs the artifact's "
                            f"acceptance_pct={acc:g}")


def check_swarm_storm(failures):
    """Round-18 rule, BOTH directions: the committed swarm-storm
    acceptance artifact (``captures/swarm_storm.json``) must itself
    satisfy the ISSUE-13 acceptance — a >=50k-node swarm with both
    invariants restored (>=0.95) after healing — and README *and*
    PARITY must each carry a ``<!-- capture:swarm_storm -->``-tagged
    paragraph quoting the node count and the mid-cut coverage
    collapse; a tagged claim without the artifact (or vice versa)
    fails."""
    cap_path = os.path.join(ROOT, "captures", "swarm_storm.json")
    cap = None
    if os.path.exists(cap_path):
        with open(cap_path) as f:
            cap = json.load(f)
        if cap.get("n_nodes", 0) < 50_000:
            failures.append(
                "captures/swarm_storm.json: n_nodes=%r is under the "
                "50000-node acceptance floor" % cap.get("n_nodes"))
        for inv in ("final_lookup_success", "final_replica_coverage"):
            if cap.get(inv, 0.0) < 0.95:
                failures.append(
                    f"captures/swarm_storm.json: {inv}={cap.get(inv)} — "
                    f"invariants not restored after healing")
    tag = "<!-- capture:swarm_storm -->"
    for name in ("README.md", "PARITY.md"):
        path = os.path.join(ROOT, name)
        if not os.path.exists(path):
            continue
        lines = open(path).read().splitlines()
        tagged = [i for i, ln in enumerate(lines) if tag in ln]
        if cap is None:
            if tagged:
                failures.append(f"{name}: '{tag}' claim with no "
                                f"captures/swarm_storm.json artifact")
            continue
        if not tagged:
            failures.append(f"{name}: no '{tag}'-tagged paragraph "
                            f"quoting the swarm-storm acceptance run")
            continue
        want_nodes = "%d-node" % cap.get("n_nodes", 0)
        want_cov = "%.2f" % cap.get("min_coverage_during_cut", -1.0)
        for li in tagged:
            para = _para_at(lines, li)
            if want_nodes not in para:
                failures.append(
                    f"{name}: [capture:swarm_storm] paragraph does not "
                    f"quote the {want_nodes} scale")
            if want_cov not in para:
                failures.append(
                    f"{name}: [capture:swarm_storm] paragraph does not "
                    f"quote the {want_cov} mid-cut coverage collapse")


def check_pipeline_overlap(failures):
    """Round-20 rule, BOTH directions: the committed wave-pipeline
    acceptance artifact (``captures/pipeline_overlap.json``) must
    itself record the two non-negotiables — depth-2 bit-identical to
    depth-1 and >=2 waves held in flight — and README *and* PARITY
    must each carry a ``<!-- capture:pipeline_overlap -->``-tagged
    paragraph quoting the measured overlap figure and the in-flight
    peak; a tagged claim without the artifact (or vice versa) fails."""
    cap_path = os.path.join(ROOT, "captures", "pipeline_overlap.json")
    cap = None
    if os.path.exists(cap_path):
        with open(cap_path) as f:
            cap = json.load(f)
        bound = cap.get("bound", {})
        if not bound.get("bit_identical"):
            failures.append(
                "captures/pipeline_overlap.json: bit_identical is not "
                "true — the pipeline's results diverged from depth 1")
        if bound.get("inflight_peak", 0) < 2:
            failures.append(
                "captures/pipeline_overlap.json: inflight_peak=%r — the "
                "double-buffer never held 2 waves in flight"
                % bound.get("inflight_peak"))
    tag = "<!-- capture:pipeline_overlap -->"
    for name in ("README.md", "PARITY.md"):
        path = os.path.join(ROOT, name)
        if not os.path.exists(path):
            continue
        lines = open(path).read().splitlines()
        tagged = [i for i, ln in enumerate(lines) if tag in ln]
        if cap is None:
            if tagged:
                failures.append(f"{name}: '{tag}' claim with no "
                                f"captures/pipeline_overlap.json artifact")
            continue
        if not tagged:
            failures.append(f"{name}: no '{tag}'-tagged paragraph "
                            f"quoting the wave-pipeline measurement")
            continue
        want_val = "%.1f%%" % cap.get("value", 0.0)
        want_peak = "%d waves in flight" % cap.get(
            "bound", {}).get("inflight_peak", 0)
        dev1 = cap.get("stages_depth1", {}).get("device_launch", {})
        dev2 = cap.get("stages_depth2", {}).get("device_launch", {})
        for li in tagged:
            para = _para_at(lines, li)
            if want_val not in para:
                failures.append(
                    f"{name}: [capture:pipeline_overlap] paragraph does "
                    f"not quote the measured {want_val} overlap delta")
            if want_peak not in para:
                failures.append(
                    f"{name}: [capture:pipeline_overlap] paragraph does "
                    f"not quote the '{want_peak}' pipeline peak")
            # the stage-histogram evidence: the quoted device-stage
            # shrink must track the artifact's dht_stage_seconds deltas
            if dev1 and dev2:
                quoted = re.findall(
                    r"device stage mean (\d+(?:\.\d+)?) → "
                    r"(\d+(?:\.\d+)?) ms", para)
                if not quoted:
                    failures.append(
                        f"{name}: [capture:pipeline_overlap] paragraph "
                        f"does not quote the 'device stage mean A → B "
                        f"ms' histogram shrink")
                for q1, q2 in quoted:
                    for q, w, which in ((q1, dev1["mean_ms"], "depth-1"),
                                        (q2, dev2["mean_ms"], "depth-2")):
                        if not (0.85 * w <= float(q) <= 1.15 * w):
                            failures.append(
                                f"{name}: [capture:pipeline_overlap] "
                                f"quotes {q} ms vs the artifact's "
                                f"{which} device-stage mean {w} (±15%)")


def check_reshard_balance(failures):
    """Round-21 rule, BOTH directions: the committed load-aware
    resharding artifact (``captures/reshard_balance.json``) must
    itself record the acceptance — the Zipf(1.1) flood at t=4 reads
    >2.0 imbalanced on the uniform split and <1.3 at the solved
    traffic-weighted edges, with lookups bit-identical including a
    wave in flight across the swap — and README *and* PARITY must
    each carry a ``<!-- capture:reshard_balance -->``-tagged
    paragraph quoting the measured before/after figures; a tagged
    claim without the artifact (or vice versa) fails."""
    cap_path = os.path.join(ROOT, "captures", "reshard_balance.json")
    cap = None
    if os.path.exists(cap_path):
        with open(cap_path) as f:
            cap = json.load(f)
        t4 = cap.get("t4", {})
        if not t4.get("imbalance_before", 0.0) > 2.0:
            failures.append(
                "captures/reshard_balance.json: t4 imbalance_before=%r "
                "— the Zipf flood did not skew the uniform split past "
                "2.0, so the capture proves nothing"
                % t4.get("imbalance_before"))
        if not t4.get("imbalance_after", 99.0) < 1.3:
            failures.append(
                "captures/reshard_balance.json: t4 imbalance_after=%r "
                "— the solved boundaries left the load imbalanced"
                % t4.get("imbalance_after"))
        for tk in ("t2", "t4"):
            sec = cap.get(tk, {})
            if not sec.get("bit_identical"):
                failures.append(
                    "captures/reshard_balance.json: %s bit_identical is "
                    "not true — the weighted layout diverged from the "
                    "single-device engine" % tk)
            if not sec.get("inflight_identical"):
                failures.append(
                    "captures/reshard_balance.json: %s "
                    "inflight_identical is not true — a wave launched "
                    "before the swap was remapped" % tk)
    tag = "<!-- capture:reshard_balance -->"
    for name in ("README.md", "PARITY.md"):
        path = os.path.join(ROOT, name)
        if not os.path.exists(path):
            continue
        lines = open(path).read().splitlines()
        tagged = [i for i, ln in enumerate(lines) if tag in ln]
        if cap is None:
            if tagged:
                failures.append(f"{name}: '{tag}' claim with no "
                                f"captures/reshard_balance.json artifact")
            continue
        if not tagged:
            failures.append(f"{name}: no '{tag}'-tagged paragraph "
                            f"quoting the resharding measurement")
            continue
        t4 = cap.get("t4", {})
        want_before = "%.2f" % t4.get("imbalance_before", -1.0)
        want_after = "%.2f" % t4.get("imbalance_after", -1.0)
        for li in tagged:
            para = _para_at(lines, li)
            if want_before not in para:
                failures.append(
                    f"{name}: [capture:reshard_balance] paragraph does "
                    f"not quote the measured {want_before} pre-swap "
                    f"imbalance")
            if want_after not in para:
                failures.append(
                    f"{name}: [capture:reshard_balance] paragraph does "
                    f"not quote the measured {want_after} post-swap "
                    f"imbalance")


def check_pipeline_util(failures):
    """Round-22 rule, BOTH directions: the committed observatory
    overhead artifact (``captures/pipeutil_overhead.json``) must
    itself record the tentpole invariant — a CLOSED ledger
    (``accounting_closed``: Σ(busy) + Σ(bubbles) == observed window
    on the timed trips) with at least one wave tracked per rep — and
    README *and* PARITY must each carry a
    ``<!-- capture:pipeutil_overhead -->``-tagged paragraph stating
    that closed-accounting claim next to the measured quote (the
    ``<1%`` bound itself rides the generic :func:`check_overhead_captures`
    rule); a tagged claim without the artifact (or vice versa)
    fails."""
    cap_path = os.path.join(ROOT, "captures", "pipeutil_overhead.json")
    cap = None
    if os.path.exists(cap_path):
        with open(cap_path) as f:
            cap = json.load(f)
        if not cap.get("accounting_closed"):
            failures.append(
                "captures/pipeutil_overhead.json: accounting_closed is "
                "not true — the timed trips left an unclosed ledger "
                "(Σ(busy) + Σ(bubbles) != observed window)")
        if cap.get("waves_observed", 0) < cap.get("reps", 1):
            failures.append(
                "captures/pipeutil_overhead.json: waves_observed=%r "
                "under reps=%r — the timed trips were not all tracked"
                % (cap.get("waves_observed"), cap.get("reps")))
    tag = "<!-- capture:pipeutil_overhead -->"
    for name in ("README.md", "PARITY.md"):
        path = os.path.join(ROOT, name)
        if not os.path.exists(path):
            continue
        lines = open(path).read().splitlines()
        tagged = [i for i, ln in enumerate(lines) if tag in ln]
        if cap is None:
            if tagged:
                failures.append(f"{name}: '{tag}' claim with no "
                                f"captures/pipeutil_overhead.json "
                                f"artifact")
            continue
        if not tagged:
            failures.append(f"{name}: no '{tag}'-tagged paragraph "
                            f"quoting the observatory overhead "
                            f"measurement")
            continue
        for li in tagged:
            para = _para_at(lines, li)
            if "Σ(busy)" not in para or "Σ(bubbles)" not in para:
                failures.append(
                    f"{name}: [capture:pipeutil_overhead] paragraph "
                    f"does not state the closed-ledger claim "
                    f"(Σ(busy) + Σ(bubbles) == observed window)")


def check_peer_ledger(failures):
    """Round-23 rule, BOTH directions: the committed per-peer ledger
    overhead artifact (``captures/peers_overhead.json``) must itself
    record a real lifecycle load (at least one full request lifecycle
    per tracked peer per wave — an empty event stream would make the
    <1% quote vacuous), and README *and* PARITY must each carry a
    ``<!-- capture:peers_overhead -->``-tagged paragraph stating the
    pure-observation claim (wave outputs pinned **bit-identical** with
    the ledger on) next to the measured quote (the ``<1%`` bound
    itself rides the generic :func:`check_overhead_captures` rule); a
    tagged claim without the artifact (or vice versa) fails."""
    cap_path = os.path.join(ROOT, "captures", "peers_overhead.json")
    cap = None
    if os.path.exists(cap_path):
        with open(cap_path) as f:
            cap = json.load(f)
        if cap.get("lifecycles_per_wave", 0) < cap.get("peers", 1):
            failures.append(
                "captures/peers_overhead.json: lifecycles_per_wave=%r "
                "under peers=%r — the timed trips did not drive a full "
                "lifecycle per tracked peer, the overhead quote is "
                "vacuous" % (cap.get("lifecycles_per_wave"),
                             cap.get("peers")))
    tag = "<!-- capture:peers_overhead -->"
    for name in ("README.md", "PARITY.md"):
        path = os.path.join(ROOT, name)
        if not os.path.exists(path):
            continue
        lines = open(path).read().splitlines()
        tagged = [i for i, ln in enumerate(lines) if tag in ln]
        if cap is None:
            if tagged:
                failures.append(f"{name}: '{tag}' claim with no "
                                f"captures/peers_overhead.json "
                                f"artifact")
            continue
        if not tagged:
            failures.append(f"{name}: no '{tag}'-tagged paragraph "
                            f"quoting the per-peer ledger overhead "
                            f"measurement")
            continue
        for li in tagged:
            para = _para_at(lines, li)
            if "bit-identical" not in para:
                failures.append(
                    f"{name}: [capture:peers_overhead] paragraph does "
                    f"not state the pure-observation claim (wave "
                    f"outputs bit-identical with the ledger on)")


def check_listener_match(failures):
    """Round-24 rule, BOTH directions: the committed listener
    amortization artifact (``captures/listener_match.json``) must
    itself satisfy the ISSUE-20 acceptance — the batched per-listener
    delivery slope below the host per-put dispatch slope, measured out
    to L=100k listeners — and README *and* PARITY must each carry a
    ``<!-- capture:listener_match -->``-tagged paragraph stating the
    result-equivalence claim (batched deliveries **result-equivalent**
    to the synchronous path) next to a quoted slope ratio that matches
    the artifact (±15%); a tagged claim without the artifact (or vice
    versa) fails."""
    cap_path = os.path.join(ROOT, "captures", "listener_match.json")
    cap = None
    if os.path.exists(cap_path):
        with open(cap_path) as f:
            cap = json.load(f)
        host = float(cap.get("host_slope_ns_per_listener", 0.0))
        bat = float(cap.get("batched_slope_ns_per_listener", 0.0))
        if not bat < host:
            failures.append(
                "captures/listener_match.json: batched slope %r "
                "ns/listener not below the host slope %r — the "
                "amortization claim fails in the artifact itself"
                % (bat, host))
        if max((r.get("L", 0) for r in cap.get("rows", [])),
               default=0) < 100_000:
            failures.append(
                "captures/listener_match.json: rows stop short of the "
                "L=100000 acceptance point")
    tag = "<!-- capture:listener_match -->"
    for name in ("README.md", "PARITY.md"):
        path = os.path.join(ROOT, name)
        if not os.path.exists(path):
            continue
        lines = open(path).read().splitlines()
        tagged = [i for i, ln in enumerate(lines) if tag in ln]
        if cap is None:
            if tagged:
                failures.append(f"{name}: '{tag}' claim with no "
                                f"captures/listener_match.json artifact")
            continue
        if not tagged:
            failures.append(f"{name}: no '{tag}'-tagged paragraph "
                            f"quoting the listener amortization "
                            f"measurement")
            continue
        ratio = float(cap.get("slope_ratio", 0.0))
        for li in tagged:
            para = _para_at(lines, li)
            if "result-equivalent" not in para:
                failures.append(
                    f"{name}: [capture:listener_match] paragraph does "
                    f"not state the result-equivalence claim (batched "
                    f"deliveries result-equivalent to the synchronous "
                    f"path)")
            quoted = [float(q) for q in
                      re.findall(r"(\d+(?:\.\d+)?)[×x]\b", para)]
            if not any(0.85 * ratio <= q <= 1.15 * ratio
                       for q in quoted):
                failures.append(
                    f"{name}: [capture:listener_match] paragraph "
                    f"quotes no slope ratio matching the artifact's "
                    f"{ratio:g}x (±15%): {quoted!r}")


#: the observability index (ISSUE-10 satellite): every serving surface
#: and the reference counterpart(s) it maps to.  BOTH directions: each
#: surface must appear as a row of the tagged table in README AND
#: PARITY, and every row of that table must name a surface registered
#: here — adding a surface without registering it fails CI.
OBS_SURFACES = ("GET /stats", "GET /trace", "GET /healthz",
                "GET /keyspace", "GET /cache", "GET /history",
                "GET /debug/bundle", "GET /profile", "GET /pipeline",
                "GET /peers", "GET /listeners", "kernel ledger",
                "dhtscanner --json")
OBS_REFERENCES = ("getNodesStats", "dumpTables", "STATS /",
                  "DhtRunner::loop_")


def check_observability_index(failures):
    """The ``<!-- obs:index -->``-tagged table in README and PARITY
    must list every surface in :data:`OBS_SURFACES` with at least one
    reference counterpart from :data:`OBS_REFERENCES` on its row, and
    must contain no row naming an unregistered surface (so a new
    surface forces this rule — and hence the mapping — to be
    updated)."""
    for name in ("README.md", "PARITY.md"):
        path = os.path.join(ROOT, name)
        if not os.path.exists(path):
            continue
        lines = open(path).read().splitlines()
        tagged = [i for i, ln in enumerate(lines)
                  if "<!-- obs:index -->" in ln]
        if not tagged:
            failures.append(f"{name}: no '<!-- obs:index -->'-tagged "
                            f"observability-index table mapping the "
                            f"serving surfaces to the reference")
            continue
        # every tagged table is validated (a stale second copy must
        # not escape the unregistered-row direction); the
        # missing-surface direction checks the union across tables
        seen = []
        for ti in tagged:
            # the table: contiguous '|' rows following the tag line
            rows = []
            li = ti + 1
            while li < len(lines) and lines[li].lstrip().startswith("|"):
                cells = [c.strip() for c in lines[li].strip().strip("|")
                         .split("|")]
                if cells and not set(cells[0]) <= set("-: "):
                    rows.append((cells[0], lines[li]))
                li += 1
            body = [r for r in rows[1:]]          # drop the header row
            if not body:
                failures.append(f"{name}: [obs:index] tag has no table "
                                f"rows under it")
                continue
            for surface, raw in body:
                # exact match after stripping markdown formatting — a
                # substring test would let 'GET /keyspace/top' ride the
                # 'GET /keyspace' registration unflagged, defeating the
                # adding-a-surface-forces-this-rule direction (review
                # finding)
                canon = surface.replace("`", "").replace("*", "").strip()
                matched = next((s for s in OBS_SURFACES
                                if canon.lower() == s.lower()), None)
                if matched is None:
                    failures.append(
                        f"{name}: [obs:index] row names unregistered "
                        f"surface {surface!r} — register it in "
                        f"ci/check_docs.py OBS_SURFACES")
                    continue
                seen.append(matched)
                if not any(ref in raw for ref in OBS_REFERENCES):
                    failures.append(
                        f"{name}: [obs:index] row for {matched!r} names "
                        f"no reference counterpart "
                        f"({', '.join(OBS_REFERENCES)})")
        for s in OBS_SURFACES:
            if s not in seen:
                failures.append(
                    f"{name}: [obs:index] table is missing the "
                    f"{s!r} surface")


def main() -> int:
    failures = []
    cap = check_headline(failures)
    checked = check_config_captures(failures)
    check_tp_wire(failures)
    check_overhead_captures(failures)
    check_swarm_storm(failures)
    check_pipeline_overlap(failures)
    check_reshard_balance(failures)
    check_pipeline_util(failures)
    check_peer_ledger(failures)
    check_listener_match(failures)
    check_observability_index(failures)
    if failures:
        print("DOCS DRIFT from capture artifacts:")
        for fmsg in failures:
            print(" -", fmsg)
        return 1
    msg = []
    if cap is not None:
        msg.append(f"{cap['value'] / 1e6:.2f}M lookups/s, "
                   f"{cap['ms_per_batch']:.1f} ms/batch")
    if checked:
        msg.append("configs: " + ", ".join(checked))
    print("docs agree with capture%s: %s"
          % ("s" if checked else "", "; ".join(msg) or "none present"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
