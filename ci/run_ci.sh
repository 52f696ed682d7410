#!/bin/sh
# CI entry point: the doc rule, then Tier-1 as the driver runs it
# (ROADMAP.md "Tier-1 verify").  A CPU tier: every step is its own
# process on the CPU backend, so none holds a chip.  The chip is checked
# by `python chip_smoke.py` and measured by `python3 -m dhtbench.run`,
# each alone, through the chip tool (README.md "Tests & benchmarks").
set -e
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
python ci/check_docs.py
python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p xdist -n 6 --dist loadfile -p no:randomly
