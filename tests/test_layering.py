"""The kernel layers import downward only.

``opendht_tpu/ops/``, ``core/`` and ``parallel/`` are what a compiled
program is built from; they may know each other, the telemetry spine
and the leaf helpers in :data:`ALLOWED`, and nothing above — not the
runtime, the planes, the tools.  The edges that break the rule today
are listed in :data:`KNOWN_UPWARD`, and the list only shrinks: a new
upward import fails the first test, an entry whose import is gone
fails the second.
"""

import ast
import os

import pytest

PKG = "opendht_tpu"
ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), PKG)
LAYERS = ("ops", "core", "parallel")
ALLOWED = frozenset(LAYERS) | {"telemetry", "compile_cache", "infohash",
                               "sockaddr", "utils"}
#: (module, target) — each a debt (ROADMAP.md Queue 3, debt d)
KNOWN_UPWARD = frozenset({
    ("core/search.py", "tracing"), ("core/search.py", "waterfall"),
    ("core/table.py", "tracing"),
    ("ops/swarm.py", "chaos"), ("ops/swarm.py", "tracing"),
    ("ops/swarm.py", "health"),
})
MODULES = sorted(
    f"{layer}/{name}" for layer in LAYERS
    for name in os.listdir(os.path.join(ROOT, layer))
    if name.endswith(".py") and name != "__init__.py")


def package_imports(module: str) -> set:
    """First component under ``opendht_tpu`` of every module that
    ``module`` imports from the package, at any depth of the file
    (function-level imports included)."""
    with open(os.path.join(ROOT, module), encoding="utf-8") as f:
        tree = ast.parse(f.read(), module)
    here = [PKG] + module.split("/")[:-1]       # the module's package
    targets = set()

    def add(parts):
        if parts[0] == PKG and len(parts) > 1:
            targets.add(parts[1])

    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                add(alias.name.split("."))
        elif isinstance(node, ast.ImportFrom):
            base = (here[:len(here) - node.level + 1] if node.level
                    else [])
            base = base + (node.module.split(".") if node.module else [])
            if len(base) > 1:
                add(base)
            else:                   # ``from .. import a, b``: each a module
                for alias in node.names:
                    add(base + [alias.name])
    return targets


@pytest.mark.parametrize("module", MODULES)
def test_kernel_layers_import_only_downward(module):
    upward = {(module, t) for t in package_imports(module) - ALLOWED}
    assert upward <= KNOWN_UPWARD, (
        f"{module} imports upward: "
        f"{sorted(t for _, t in upward - KNOWN_UPWARD)} — the kernel "
        f"layers know {sorted(ALLOWED)} and nothing above")


def test_known_upward_edges_still_exist():
    """An entry whose import is gone comes off the list with it."""
    gone = {(m, t) for m, t in KNOWN_UPWARD
            if t not in package_imports(m)}
    assert not gone, f"remove from KNOWN_UPWARD: {sorted(gone)}"
