"""Docs consistency check: the one rule a CPU can hold without a
measurement.

README.md and PARITY.md each carry an ``<!-- obs:index -->``-tagged
table mapping every serving surface to its reference counterpart;
:func:`check_observability_index` holds the table and
:data:`OBS_SURFACES` to each other in both directions.  No rule reads a
number: every figure lives in ``PERF_LEDGER.jsonl`` / ``PERF.md``, and
that the docs name only files that exist is
``tests/test_docs_paths.py``'s.  Usage: ``python ci/check_docs.py``
(exit 1 on drift).
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


#: the observability index (ISSUE-10 satellite): every serving surface
#: and the reference counterpart(s) it maps to.  BOTH directions: each
#: surface must appear as a row of the tagged table in README AND
#: PARITY, and every row of that table must name a surface registered
#: here — adding a surface without registering it fails CI.
OBS_SURFACES = ("GET /stats", "GET /trace", "GET /healthz",
                "GET /keyspace", "GET /cache", "GET /history",
                "GET /debug/bundle", "GET /profile", "GET /pipeline",
                "GET /peers", "GET /listeners", "dhtscanner --json")
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
    check_observability_index(failures)
    if failures:
        print("DOCS DRIFT:")
        for fmsg in failures:
            print(" -", fmsg)
        return 1
    print("docs ok: observability index (%d surfaces)" % len(OBS_SURFACES))
    return 0


if __name__ == "__main__":
    sys.exit(main())
