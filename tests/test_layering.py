"""Which package of ``fks_tpu`` may import which: two cases a package.

Every module's imports are read with ``ast``, the deferred ones inside
functions too, and resolved to the package they reach; ``obs`` is kept at
module granularity, because its instrumentation core may be used from
anywhere and the rest of it sits on top of the program. A name reached
through the ``obs`` namespace (``obs.span``, ``from fks_tpu.obs import
CompileWatcher``) is looked up in what ``obs/__init__.py`` re-exports and
counts as an import of the module that defines it, so the namespace hides
no edge.

The rule for ``obs`` is stated, not listed: a program package imports
``OBS_CORE`` and nothing else of ``obs``. ``ALLOWED`` is the rest of the
graph as it stands, written out. An edge that points UP the order of
``RANK`` is a debt: it is listed in ``KNOWN_UPWARD`` with the ROADMAP item
that removes it, and a case fails both on an edge that is in neither list
and on a listed debt that no longer exists, so that list only shrinks.
"""
import ast
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent / "fks_tpu"

#: low to high; a package imports only from lower ranks
RANK = {"utils": 0, "ops": 0, "data": 0, "sim": 1, "models": 2,
        "parallel": 3, "scenarios": 4, "funsearch": 5, "analysis": 6,
        "resilience": 7, "serve": 8, "portfolio": 9, "pipeline": 10,
        "obs": 11}

#: the part of ``obs`` the program is instrumented with ("obs" is the
#: package's own namespace, ``from fks_tpu import obs``, which imports and
#: re-exports exactly these); everything else in ``obs`` reads runs and
#: drives services, and ranks above them
OBS_CORE = {"obs", "obs.spans", "obs.recorder", "obs.trace_ctx",
            "obs.telemetry", "obs.profiler", "obs.ledger"}

#: package -> the packages it imports (``obs`` apart: see ``OBS_CORE``)
ALLOWED = {
    "utils": set(),
    "ops": set(),
    "data": set(),
    "sim": {"data", "ops", "utils"},
    "models": {"sim"},
    "parallel": {"data", "models", "sim", "utils"},
    "scenarios": {"data", "models", "ops", "parallel", "sim"},
    "funsearch": {"data", "models", "parallel", "scenarios", "sim", "utils"},
    "analysis": {"data", "funsearch", "models", "parallel", "sim"},
    "resilience": {"funsearch", "parallel"},
    "serve": {"analysis", "data", "funsearch", "parallel", "resilience",
              "sim"},
    "portfolio": {"data", "funsearch", "parallel", "serve", "sim"},
    "pipeline": {"data", "funsearch", "portfolio", "resilience",
                 "scenarios", "serve"},
    "obs": {"data", "funsearch", "parallel", "resilience", "serve", "sim",
            "utils"},
}

#: package -> {edge that points up: the ROADMAP item that removes it}
KNOWN_UPWARD = {
    "sim": {"models": "D11: sim.fused reads models.parametric's constants"},
    "models": {"funsearch": "D11: parametric renders funsearch.template"},
    "parallel": {"funsearch": "D11: the code runners close over vm.score"},
    "funsearch": {
        "analysis": "D11: backend / evolution call the pre-flight",
        "resilience": "D11: evolution owns a resilience.wal"},
    "analysis": {"serve": "D11: lint pins a serve bucket"},
    "resilience": {
        "serve": "D11: degrade / drills build engines and services",
        "pipeline": "D11: drills borrow pipeline.faults",
        "obs.report": "D11: drills read a run directory back"},
    "serve": {"portfolio": "D11: artifact picks the portfolio engine"},
    "portfolio": {"pipeline": "D11: fleet drives pipeline.controller"},
}


def _dotted(f):
    """``sim/flat.py`` -> ``sim.flat``; a package's ``__init__`` keeps its
    name, which is what a relative import resolves against."""
    return ".".join(f.relative_to(ROOT).with_suffix("").parts)


MODULES = {_dotted(f).removesuffix(".__init__")
           for f in ROOT.rglob("*.py")} - {"__init__"}


def _imported_names(tree, module):
    """``(dotted name below fks_tpu, name it is bound to)`` for every
    import in ``tree``, relative imports resolved against ``module`` (its
    dotted name)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("fks_tpu."):
                    yield a.name[len("fks_tpu."):], a.asname
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
                yield f"{stem}.{a.name}".lstrip("."), a.asname or a.name


def _module_of(name):
    """The longest prefix of a dotted name that is a module ("" if none):
    the tail was a name inside it."""
    parts = name.split(".")
    while parts and ".".join(parts) not in MODULES:
        parts.pop()
    return ".".join(parts)


def _obs_exports(init_source):
    """name -> ``obs.<module>`` for what the source of ``obs/__init__.py``
    binds: the module a name reached through the namespace comes from."""
    out = {}
    for name, bound in _imported_names(ast.parse(init_source),
                                       "obs.__init__"):
        module = _module_of(name)
        if module.startswith("obs."):
            out[bound] = ".".join(module.split(".")[:2])
    return out


OBS_EXPORTS = _obs_exports((ROOT / "obs" / "__init__.py").read_text())


def _through_namespace(attr, exports):
    """``obs.<attr>``: a module of ``obs``, or a name it re-exports. A name
    the namespace does not hold comes back as it was written, which is in
    no list."""
    if f"obs.{attr}" in MODULES:
        return f"obs.{attr}"
    return exports.get(attr, f"obs.{attr}")


def _reached_by_source(source, module, exports=None):
    """What the source of ``module`` imports of the packages of
    ``fks_tpu``: their names, and ``obs`` / ``obs.<module>`` for what it
    imports of ``obs``, directly or through the namespace."""
    exports = OBS_EXPORTS if exports is None else exports
    tree = ast.parse(source)
    out, namespaces = set(), set()
    for name, bound in _imported_names(tree, module):
        found = _module_of(name)
        if not found:
            continue
        if found != "obs":
            out.add(".".join(found.split(".")[:2])
                    if found.startswith("obs.") else found.split(".")[0])
        elif name == "obs":
            out.add("obs")
            namespaces.add(bound or "fks_tpu.obs")  # import fks_tpu.obs
        else:  # from fks_tpu.obs import <a name it re-exports>
            out.add(_through_namespace(name.split(".")[1], exports))
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute)
                and ast.unparse(node.value) in namespaces):
            out.add(_through_namespace(node.attr, exports))
    return out


def _reached(package):
    out = set()
    for f in (ROOT / package).rglob("*.py"):
        out |= _reached_by_source(f.read_text(), _dotted(f))
    return {t for t in out if t.split(".")[0] != package}


PROGRAM = sorted(set(RANK) - {"obs"})

#: the modules of ``obs`` that sit on top of the program
OBS_TOOLS = sorted(m for m in MODULES
                   if m.startswith("obs.") and m not in OBS_CORE)

#: (where a name lived, where it lives, the names): what the program
#: cannot run without belongs to the package that owns it, under one name
MOVED = [
    ("obs.workload", "serve.accounting",
     ("QueryFingerprinter", "TenantAccountant", "tenant_of",
      "DEFAULT_TENANT", "jain_fairness")),
    ("obs.history", "serve.accounting",
     ("SLOConfig", "slo_burn", "record_slo_burn")),
    ("obs.watchdog", "funsearch.parity", ("ParitySentinel",)),
    ("obs.watchdog", "sim.guards", ("combined_flags",)),
    ("obs.tracing", "funsearch.tracing",
     ("extract_trace", "align_traces", "replay", "trace_diff",
      "format_diff", "candidate_trace_diff")),
]


def test_every_package_has_a_case():
    packages = {p.name for p in ROOT.iterdir()
                if (p / "__init__.py").exists()}
    assert packages == set(RANK) == set(ALLOWED)
    assert set(KNOWN_UPWARD) <= packages


@pytest.mark.parametrize("package", sorted(RANK))
def test_package_imports_only_what_is_listed(package):
    reached = _reached(package)
    debts = KNOWN_UPWARD.get(package, {})
    # what a program package reaches of obs is the next case's
    packages = {t for t in reached if t.split(".")[0] != "obs"}
    new = packages - ALLOWED[package] - set(debts)
    assert not new, (
        f"fks_tpu.{package} now imports {sorted(new)}: point the edge "
        "down, or list it with the reason it is allowed")
    gone = set(debts) - reached
    assert not gone, (
        f"fks_tpu.{package} no longer imports {sorted(gone)}: take the "
        "debt out of KNOWN_UPWARD and strike it in ROADMAP.md")
    # the allow-list itself points down
    for target in ALLOWED[package]:
        assert RANK[target] < RANK[package], (package, target)


@pytest.mark.parametrize("package", PROGRAM)
def test_program_package_imports_only_the_core_of_obs(package):
    of_obs = {t for t in _reached(package) if t.split(".")[0] == "obs"}
    kept = {t for t in KNOWN_UPWARD.get(package, {}) if t.startswith("obs")}
    beyond = of_obs - OBS_CORE - kept
    assert not beyond, (
        f"fks_tpu.{package} imports {sorted(beyond)}: the program is "
        f"instrumented with {sorted(OBS_CORE)} and with nothing else of "
        "obs; what it cannot run without belongs to the package that "
        "owns it")


def test_the_obs_namespace_holds_only_the_core():
    """``from fks_tpu import obs`` loads what ``obs/__init__.py`` imports:
    were that more than the core, every ``obs.span`` would load it."""
    assert set(OBS_EXPORTS.values()) <= OBS_CORE
    assert OBS_EXPORTS["span"] == "obs.spans"
    assert OBS_EXPORTS["trace_ctx"] == "obs.trace_ctx"


def test_a_name_reached_through_the_namespace_is_an_edge():
    exports = _obs_exports(
        "from fks_tpu.obs.spans import span\n"
        "from fks_tpu.obs.exporter import to_openmetrics as metrics\n")
    assert exports == {"span": "obs.spans", "metrics": "obs.exporter"}
    source = (
        "from fks_tpu import obs as o\n"
        "import fks_tpu.obs\n"
        "from fks_tpu.obs import metrics\n"
        "def f():\n"
        "    with o.span('x'):\n"
        "        return o.report.load_run, fks_tpu.obs.not_there\n")
    assert _reached_by_source(source, "serve.made_up", exports) == {
        "obs", "obs.spans", "obs.exporter", "obs.report", "obs.not_there"}


def _defined(module):
    """The names ``module`` defines at its top level ("" for no module)."""
    f = ROOT / (module.replace(".", "/") + ".py")
    if not f.exists():
        return set()
    out = set()
    for node in ast.parse(f.read_text()).body:
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            out.add(node.name)
        elif isinstance(node, ast.Assign):
            out |= {t.id for t in node.targets if isinstance(t, ast.Name)}
    return out


@pytest.mark.parametrize(
    "old, new, names",
    [pytest.param(*m, id=f"{m[0]}->{m[1]}") for m in MOVED])
def test_a_moved_name_has_one_home(old, new, names):
    assert set(names) <= _defined(new)
    assert not set(names) & _defined(old)


@pytest.fixture(scope="module")
def loaded_by_the_program():
    """The modules of ``obs`` in ``sys.modules`` once the serving and the
    evaluating halves of the program are imported, in a process of its
    own (this one has imported what its tests import)."""
    code = ("import sys, fks_tpu.serve.service, fks_tpu.funsearch.backend\n"
            "print(*sorted(m for m in sys.modules "
            "if m.startswith('fks_tpu.obs.')))")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT.parent,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.split()


def test_the_program_loads_the_core_of_obs(loaded_by_the_program):
    assert set(loaded_by_the_program) == {
        f"fks_tpu.{m}" for m in OBS_CORE - {"obs"}}


@pytest.mark.parametrize("tool", OBS_TOOLS)
def test_the_program_does_not_load_a_tool_of_obs(loaded_by_the_program,
                                                 tool):
    assert f"fks_tpu.{tool}" not in loaded_by_the_program
