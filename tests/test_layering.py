"""The package graph of ``saturn_tpu/``, written down and held.

``ALLOWED`` is the graph as it stands: for each top-level package, the other
top-level packages its files import, module-level and function-level imports
alike (the walk reads the AST; nothing is imported). ``docs/architecture.md``
("The packages and their arrows") draws the same table as boxes.

A new edge fails its package's case: either the import belongs somewhere
else, or the table gains the edge in the same change and a reviewer sees it.
An edge of the table that is gone fails too, so the table shrinks with the
debts. An edge that points *up* the layers carries the name of the debt in
``ROADMAP.md`` that removes it; every other edge points down.
"""

import ast
import os

import pytest

ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "saturn_tpu")

#: Bottom to top. A package may import what stands on a lower line.
LAYERS = (
    ("native", "utils"),
    ("core", "ops"),
    ("data", "health", "models", "solver", "tenancy"),
    ("parallel",),
    ("library",),
    ("durability", "trial_runner"),
    ("executor",),
    ("resilience",),
    ("service",),
    ("twin",),
    ("analysis",),
    ("__init__",),
)

D12 = "D12"  # one owner for what makes a profile or a compiled program stale
D13 = "D13"  # analysis is the operators' tool and a runtime library in one
D14 = "D14"  # pairs of packages that import each other

#: package -> {imported package: None, or the debt of an upward edge}
ALLOWED = {
    "__init__": dict.fromkeys(
        ("core", "executor", "library", "service", "trial_runner", "utils")),
    "analysis": dict.fromkeys(
        ("core", "data", "durability", "health", "library", "models", "ops",
         "parallel", "service", "solver", "twin", "utils")),
    "core": {"utils": None},
    "data": {"analysis": D13, "native": None},
    "durability": {"analysis": D13, "service": D14, "utils": None},
    "executor": {"analysis": D13, "core": None, "durability": None,
                 "health": None, "parallel": None, "resilience": D14,
                 "solver": None, "utils": None},
    "health": {"analysis": D13, "utils": None},
    "library": {"core": None, "parallel": None},
    "models": {"analysis": D13, "core": None, "ops": None},
    "native": {},
    "ops": {},
    "parallel": {"analysis": D13, "core": None, "data": None, "health": None,
                 "models": None, "ops": None, "utils": None},
    "resilience": {"analysis": D13, "core": None, "durability": None,
                   "executor": None, "health": None, "solver": None,
                   "trial_runner": None, "utils": None},
    "service": {"analysis": D13, "core": None, "durability": None,
                "executor": None, "health": None, "parallel": None,
                "resilience": None, "solver": None, "tenancy": None,
                "trial_runner": None, "utils": None},
    "solver": {"core": None, "native": None, "utils": None},
    "tenancy": {"analysis": D13, "utils": None},
    "trial_runner": {"analysis": D13, "core": None, "library": None,
                     "ops": None, "parallel": None, "utils": None},
    "twin": {"analysis": D13, "core": None, "durability": None,
             "executor": None, "resilience": None, "service": None,
             "solver": None, "utils": None},
    "utils": {"analysis": D12, "core": D14, "ops": D12, "parallel": D12},
}


def _packages():
    return sorted(
        d for d in os.listdir(ROOT)
        if os.path.isfile(os.path.join(ROOT, d, "__init__.py"))
    )


def _imported_modules(tree, here):
    """Absolute dotted names of what a module's AST imports; ``here`` is the
    module's own package as a list of names, for relative imports."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name
        elif isinstance(node, ast.ImportFrom):
            base = here[:len(here) - (node.level - 1)] if node.level else []
            if node.module:
                base = base + node.module.split(".")
            yield ".".join(base)
            # ``from saturn_tpu import analysis`` names a package in a.name
            for a in node.names:
                yield ".".join(base + [a.name])


def _edges(package, packages):
    """Top-level packages that the files of ``package`` import."""
    if package == "__init__":
        files = [os.path.join(ROOT, "__init__.py")]
    else:
        files = [
            os.path.join(d, f)
            for d, _, names in os.walk(os.path.join(ROOT, package))
            for f in names if f.endswith(".py")
        ]
    found = set()
    for path in files:
        with open(path) as fh:
            tree = ast.parse(fh.read(), path)
        rel = os.path.relpath(os.path.dirname(path), ROOT)
        here = ["saturn_tpu"] + ([] if rel == "." else rel.split(os.sep))
        for mod in _imported_modules(tree, here):
            parts = mod.split(".")
            if (len(parts) > 1 and parts[0] == "saturn_tpu"
                    and parts[1] in packages and parts[1] != package):
                found.add(parts[1])
    return found


def test_the_table_names_every_package_once():
    names = [p for layer in LAYERS for p in layer]
    assert sorted(names) == sorted(set(names))
    assert set(names) == set(_packages()) | {"__init__"} == set(ALLOWED)


@pytest.mark.parametrize("package", sorted(ALLOWED))
def test_package_imports_what_the_table_allows(package):
    found = _edges(package, set(_packages()))
    allowed = ALLOWED[package]
    new = sorted(found - set(allowed))
    gone = sorted(set(allowed) - found)
    assert not new, (
        f"saturn_tpu/{package} now imports {new}: move the import, or add "
        "the edge to ALLOWED (and to docs/architecture.md) in this change"
    )
    assert not gone, (
        f"saturn_tpu/{package} no longer imports {gone}: take the edge out "
        "of ALLOWED (and out of docs/architecture.md, and off its debt)"
    )
    rank = {p: i for i, layer in enumerate(LAYERS) for p in layer}
    for dep, debt in allowed.items():
        upward = rank[dep] >= rank[package]
        assert upward == (debt is not None), (
            f"{package} -> {dep} points {'up' if upward else 'down'} the "
            f"layers but carries {debt!r}: an upward edge names its debt in "
            "ROADMAP.md, a downward edge names none"
        )
