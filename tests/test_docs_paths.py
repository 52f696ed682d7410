"""The documents and the package's comments name only files that exist.

The one doc rule a CPU can hold without a measurement: a
``benchmarks/….py``, ``captures/….json``, ``ci/…``, ``dhtbench/…``,
``tests/test_*.py`` or ``opendht_tpu/….py`` path named in README.md,
PARITY.md or a comment of the package is in the tree.  PERF.md,
ROADMAP.md and CHANGES.md tell history and are exempt; the reference's
own ``src/…``, ``tools/…`` and ``python/…`` paths are not matched.
"""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: a path of this repo, not the tail of a longer one
PATH = re.compile(
    r"(?<![\w/.*>-])(?:"
    r"benchmarks/[\w/.-]+\.py|captures/[\w.-]+\.json|tests/test_\w+\.py|"
    r"opendht_tpu/[\w/]+\.py|(?:ci|dhtbench)/[\w/.-]*\w)")


def missing_paths(text: str) -> list:
    return sorted({m for m in PATH.findall(text)
                   if not os.path.exists(os.path.join(ROOT, m))})


@pytest.mark.parametrize("doc", ["README.md", "PARITY.md"])
def test_docs_cite_only_files_that_exist(doc):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        assert missing_paths(f.read()) == []


def test_package_comments_cite_only_files_that_exist():
    sources = glob.glob(os.path.join(ROOT, "opendht_tpu", "**", "*.py"),
                        recursive=True) + [os.path.join(ROOT,
                                                        "chip_smoke.py")]
    missing = {}
    for path in sources:
        with open(path, encoding="utf-8") as f:
            gone = missing_paths(f.read())
        if gone:
            missing[os.path.relpath(path, ROOT)] = gone
    assert missing == {}
