"""Which package of ``fks_tpu`` may import which: one case a package.

Every module's imports are read with ``ast``, the deferred ones inside
functions too, and resolved to the package they reach; ``obs`` is kept at
module granularity, because its instrumentation core may be used from
anywhere and the rest of it sits on top of the program. ``ALLOWED`` is
the graph as it stands, written out. An edge that points UP the order of
``RANK`` is a debt: it is listed in ``KNOWN_UPWARD`` with the ROADMAP item
that removes it, and a case fails both on an edge that is in neither list
and on a listed debt that no longer exists, so that list only shrinks.
"""
import ast
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent / "fks_tpu"

#: low to high; a package imports only from lower ranks
RANK = {"utils": 0, "ops": 0, "data": 0, "sim": 1, "models": 2,
        "parallel": 3, "scenarios": 4, "funsearch": 5, "analysis": 6,
        "resilience": 7, "serve": 8, "portfolio": 9, "pipeline": 10,
        "obs": 11}

#: the part of ``obs`` the program is instrumented with ("obs" is the
#: package's own namespace, ``from fks_tpu import obs``); everything else
#: in ``obs`` reads runs and drives engines, and ranks above them
OBS_CORE = {"obs", "obs.spans", "obs.recorder", "obs.trace_ctx",
            "obs.telemetry", "obs.profiler"}

ALLOWED = {
    "utils": set(),
    "ops": set(),
    "data": set(),
    "sim": {"data", "ops", "utils"},
    "models": {"sim"},
    "parallel": {"data", "models", "sim", "utils", "obs.spans"},
    "scenarios": {"data", "models", "ops", "parallel", "sim"},
    "funsearch": {"data", "models", "parallel", "scenarios", "sim", "utils",
                  "obs", "obs.recorder", "obs.trace_ctx"},
    "analysis": {"data", "funsearch", "models", "parallel", "sim",
                 "obs.profiler"},
    "resilience": {"funsearch", "parallel", "obs", "obs.trace_ctx"},
    "serve": {"analysis", "data", "funsearch", "parallel", "resilience",
              "sim", "obs", "obs.trace_ctx"},
    "portfolio": {"data", "funsearch", "parallel", "serve", "sim", "obs"},
    "pipeline": {"data", "funsearch", "portfolio", "resilience",
                 "scenarios", "serve", "obs", "obs.trace_ctx"},
    "obs": {"data", "funsearch", "models", "parallel", "resilience",
            "serve", "sim", "utils"},
}

#: package -> {edge that points up: the ROADMAP item that removes it}
KNOWN_UPWARD = {
    "sim": {"models": "D11: sim.fused reads models.parametric's constants"},
    "models": {"funsearch": "D11: parametric renders funsearch.template"},
    "parallel": {"funsearch": "D11: the code runners close over vm.score"},
    "funsearch": {
        "analysis": "D11: backend / evolution call the pre-flight",
        "resilience": "D11: evolution owns a resilience.wal",
        "obs.memory": "D9: the evolve tier files a footprint"},
    "analysis": {
        "serve": "D11: lint pins a serve bucket",
        "obs.memory": "D9: lint pins the sampled flat step"},
    "resilience": {
        "serve": "D11: degrade / drills build engines and services",
        "pipeline": "D11: drills borrow pipeline.faults",
        "obs.report": "D11: drills read a run directory back"},
    "serve": {
        "portfolio": "D11: artifact picks the portfolio engine",
        "obs.memory": "D9: every bucket files a footprint",
        "obs.workload": "D10: QueryFingerprinter, TenantAccountant",
        "obs.history": "D10: SLOConfig, record_slo_burn",
        "obs.watchdog": "D10: ParitySentinel"},
    "portfolio": {
        "pipeline": "D11: fleet drives pipeline.controller",
        "obs.memory": "D9: every bucket files a footprint",
        "obs.workload": "D10: the router's QueryFingerprinter"},
    "pipeline": {"obs.history": "D10: SLOConfig, slo_burn"},
}


def _dotted(f):
    """``sim/flat.py`` -> ``sim.flat``; a package's ``__init__`` keeps its
    name, which is what a relative import resolves against."""
    return ".".join(f.relative_to(ROOT).with_suffix("").parts)


MODULES = {_dotted(f).removesuffix(".__init__")
           for f in ROOT.rglob("*.py")} - {"__init__"}


def _imported_names(tree, module):
    """Dotted names below ``fks_tpu`` that ``tree`` imports, relative
    imports resolved against ``module`` (its dotted name)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("fks_tpu."):
                    yield a.name[len("fks_tpu."):]
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = module.split(".")[:-node.level]
                stem = ".".join(base + ([node.module] if node.module else []))
            elif node.module == "fks_tpu":
                stem = ""
            elif node.module and node.module.startswith("fks_tpu."):
                stem = node.module[len("fks_tpu."):]
            else:
                continue
            for a in node.names:
                yield f"{stem}.{a.name}".lstrip(".")


def _reached(package):
    """What ``package`` imports of the other packages: their names, and
    ``obs`` / ``obs.<module>`` for what it imports of ``obs``."""
    out = set()
    for f in (ROOT / package).rglob("*.py"):
        for name in _imported_names(ast.parse(f.read_text()), _dotted(f)):
            parts = name.split(".")
            while parts and ".".join(parts) not in MODULES:
                parts.pop()  # the tail was a name inside a module
            if not parts or parts[0] == package:
                continue
            out.add(".".join(parts[:2]) if parts[0] == "obs" else parts[0])
    return out


def test_every_package_has_a_case():
    packages = {p.name for p in ROOT.iterdir()
                if (p / "__init__.py").exists()}
    assert packages == set(RANK) == set(ALLOWED)
    assert set(KNOWN_UPWARD) <= packages


@pytest.mark.parametrize("package", sorted(RANK))
def test_package_imports_only_what_is_listed(package):
    reached = _reached(package)
    debts = KNOWN_UPWARD.get(package, {})
    new = reached - ALLOWED[package] - set(debts)
    assert not new, (
        f"fks_tpu.{package} now imports {sorted(new)}: point the edge "
        "down, or list it with the reason it is allowed")
    gone = set(debts) - reached
    assert not gone, (
        f"fks_tpu.{package} no longer imports {sorted(gone)}: take the "
        "debt out of KNOWN_UPWARD and strike it in ROADMAP.md")
    # the allow-list itself points down; what it admits of obs is the core
    for target in ALLOWED[package]:
        if target.startswith("obs") and package != "obs":
            assert target in OBS_CORE, (package, target)
        else:
            assert RANK[target.split(".")[0]] < RANK[package], (
                package, target)
