"""Repo-wide JAX-invariant linter + jaxpr-fingerprint pinner.

Two gates, both wired into ``cli lint`` (and ``tools/fks_lint.py``):

**AST lints** (``lint_paths``) — stdlib-only static checks over the
repo's own sources for the trace-safety invariants the engine relies on.
The scope is deliberately *syntactic*: a function is "jitted" when its
decorator list contains ``jax.jit`` / ``jit`` / ``partial(jax.jit, ...)``
(the repo's only jit idioms), and only constructs that are wrong under
tracing in every context are flagged, so a clean repo stays clean without
per-site waivers:

- FKS101: a Python ``while`` loop inside a jitted function — its
  condition would be a traced value; use ``jax.lax.while_loop``.
- FKS102: a Python ``if`` whose test reads a *traced argument* of the
  jitted function (``static_argnums``/``static_argnames`` params are
  excluded). Closure reads of Python-static config are the sanctioned
  pattern and are not flagged.
- FKS103: ``.item()`` / ``.tolist()`` inside a jitted function — a
  device->host sync that fails under tracing.
- FKS104: a ``numpy`` call (via any imported alias) inside a jitted
  function — host arrays silently break tracing or constant-fold.
- FKS105: an attribute read of a ``SimConfig``-typed *argument* inside a
  jitted function. SimConfig knobs are Python-static by contract
  (engine.SimConfig docstrings); passing one as a traced jit argument
  would turn every flag read into FKS102. The static pattern — cfg
  captured by closure at build time — is untouched.

**Jaxpr pins** (``compute_pins`` / ``check_pins`` / ``write_pins``) —
the dynamic half of the same contract. Every Python-static SimConfig
flag promises "the disabled path compiles the identical program"; the
pinner makes that falsifiable by lowering the key entry points (flat
step under each flag, the segmented population ``advance``, one serve
bucket) on the micro workload and hashing ``str(jax.make_jaxpr(...))``
into ``tests/fixtures/jaxpr_pins.json``. A refactor that silently
changes a lowered program — e.g. turning a static flag into a traced
read — shows up as pin drift and fails the gate; intentional program
changes re-pin with ``cli lint --write-pins``.

x64 is forced before lowering so the pins are stable across entry
points (tests/conftest.py runs the suite under x64; a subprocess ``cli
lint`` must hash the same programs).
"""
from __future__ import annotations

import ast
import dataclasses
import hashlib
import json
import os
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: the pinned-jaxpr manifest checked by ``cli lint`` and CI
PIN_MANIFEST = os.path.join(REPO_ROOT, "tests", "fixtures",
                            "jaxpr_pins.json")

LINT_CODES = {
    "FKS101": "python while loop inside a jitted function",
    "FKS102": "data-dependent if on a traced jit argument",
    "FKS103": "host sync (.item()/.tolist()) inside a jitted function",
    "FKS104": "numpy usage inside a jitted function",
    "FKS105": "SimConfig passed as a traced jit argument",
}

@dataclasses.dataclass(frozen=True)
class Finding:
    """One lint hit: machine fields plus the gcc-style rendering."""

    path: str
    line: int
    code: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.code} {self.message}"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


# ------------------------------------------------------------- AST lints


def _numpy_aliases(tree: ast.Module) -> Set[str]:
    """Names the module binds to the numpy package (``import numpy as
    np`` -> {"np"}). ``from numpy import x`` is not aliased to the
    package and is caught per-name only if the package itself is."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy" or a.name.startswith("numpy."):
                    out.add(a.asname or a.name.split(".")[0])
    return out


def _is_jit_expr(node: ast.expr) -> bool:
    """``jit`` / ``jax.jit`` as an expression."""
    if isinstance(node, ast.Name):
        return node.id == "jit"
    if isinstance(node, ast.Attribute):
        return (node.attr == "jit" and isinstance(node.value, ast.Name)
                and node.value.id == "jax")
    return False


def _jit_decorator(dec: ast.expr) -> Optional[ast.expr]:
    """The decorator expression when ``dec`` marks the function jitted:
    bare ``jax.jit``, a ``jax.jit(...)`` call, or ``partial(jax.jit,
    ...)``. Returns the *call* node (for static_arg* extraction) or the
    bare expression; None when not a jit decorator."""
    if _is_jit_expr(dec):
        return dec
    if isinstance(dec, ast.Call):
        if _is_jit_expr(dec.func):
            return dec
        fn = dec.func
        is_partial = (isinstance(fn, ast.Name) and fn.id == "partial") or (
            isinstance(fn, ast.Attribute) and fn.attr == "partial")
        if is_partial and dec.args and _is_jit_expr(dec.args[0]):
            return dec
    return None


def _static_params(dec: ast.expr, fn: ast.FunctionDef) -> Set[str]:
    """Parameter names excluded from tracing by ``static_argnums`` /
    ``static_argnames`` literals on the jit decorator call. Non-literal
    specs conservatively mark ALL params static (no false positives on
    code the linter cannot resolve)."""
    if not isinstance(dec, ast.Call):
        return set()
    params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    out: Set[str] = set()
    for kw in dec.keywords:
        if kw.arg not in ("static_argnums", "static_argnames"):
            continue
        try:
            spec = ast.literal_eval(kw.value)
        except ValueError:
            return set(params)
        items = spec if isinstance(spec, (tuple, list)) else (spec,)
        for it in items:
            if isinstance(it, str):
                out.add(it)
            elif isinstance(it, int) and 0 <= it < len(params):
                out.add(params[it])
    return out


def _param_names(fn: ast.FunctionDef) -> List[str]:
    args = fn.args
    names = [a.arg for a in args.posonlyargs + args.args + args.kwonlyargs]
    for extra in (args.vararg, args.kwarg):
        if extra is not None:
            names.append(extra.arg)
    return names


def _simconfig_params(fn: ast.FunctionDef) -> Set[str]:
    """Params annotated SimConfig (``cfg: SimConfig`` / ``sim.SimConfig``)."""
    out: Set[str] = set()
    for a in fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs:
        ann = a.annotation
        name = None
        if isinstance(ann, ast.Name):
            name = ann.id
        elif isinstance(ann, ast.Attribute):
            name = ann.attr
        elif isinstance(ann, ast.Constant) and isinstance(ann.value, str):
            name = ann.value.rsplit(".", 1)[-1]
        if name == "SimConfig":
            out.add(a.arg)
    return out


def _reads(node: ast.AST, names: Set[str]) -> Optional[ast.Name]:
    """The first Name in ``node``'s subtree drawn from ``names``."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in names:
            return sub
    return None


def _lint_jitted(path: str, fn: ast.FunctionDef, np_aliases: Set[str],
                 traced: Set[str], simcfg: Set[str],
                 findings: List[Finding]) -> None:
    """All rule checks over one jitted function's body."""

    def hit(code: str, node: ast.AST, detail: str) -> None:
        findings.append(Finding(path, getattr(node, "lineno", fn.lineno),
                                code, f"{LINT_CODES[code]}: {detail}"))

    for scfg in sorted(simcfg & traced):
        hit("FKS105", fn,
            f"'{scfg}' in '{fn.name}' — SimConfig knobs are Python-static; "
            f"close over the config instead of tracing it")

    for node in ast.walk(fn):
        if isinstance(node, ast.While):
            hit("FKS101", node,
                f"in '{fn.name}' — use jax.lax.while_loop")
        elif isinstance(node, ast.If):
            read = _reads(node.test, traced)
            if read is not None:
                hit("FKS102", node,
                    f"'{read.id}' in '{fn.name}' — use jnp.where or "
                    f"jax.lax.cond")
        elif isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in ("item", "tolist"):
                hit("FKS103", node, f".{f.attr}() in '{fn.name}'")
            elif _reads(f, np_aliases) is not None:
                hit("FKS104", node,
                    f"in '{fn.name}' — use jnp (host numpy does not trace)")


def lint_source(path: str, source: str) -> List[Finding]:
    """Lint one module's source. Syntax errors surface as a finding (the
    gate must not crash on a broken tree mid-refactor)."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 1, "FKS100",
                        f"syntax error: {e.msg}")]
    np_aliases = _numpy_aliases(tree)
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            jd = _jit_decorator(dec)
            if jd is None:
                continue
            traced = set(_param_names(node)) - _static_params(jd, node)
            _lint_jitted(path, node, np_aliases, traced,
                         _simconfig_params(node), findings)
            break
    return findings


def lint_paths(paths: Sequence[str]) -> List[Finding]:
    """Lint every ``*.py`` under the given files/directories, sorted by
    location. The default gate target is the package root."""
    files: List[Path] = []
    for p in paths:
        path = Path(p)
        if path.is_dir():
            files.extend(sorted(path.rglob("*.py")))
        else:
            files.append(path)
    findings: List[Finding] = []
    for f in files:
        findings.extend(lint_source(str(f), f.read_text()))
    findings.sort(key=lambda x: (x.path, x.line, x.code))
    return findings


# ------------------------------------------------------------ jaxpr pins

#: SimConfig single-flag variants lowered for the flat step — one pin per
#: Python-static knob, so flipping any flag's implementation from static
#: to traced (or vice versa) moves at least one hash
FLAT_VARIANTS: Tuple[Tuple[str, Dict[str, object]], ...] = (
    ("baseline", {}),
    ("watchdog", {"watchdog": True}),
    ("decision_trace", {"decision_trace": True}),
    ("probe_score", {"probe_score": True}),
    ("prefilter_k1", {"node_prefilter_k": 1}),
    ("no_track_ctime", {"track_ctime": False}),
    ("state_pack", {"state_pack": True}),
    ("cond_policy", {"cond_policy": True}),
)

#: deterministic micro-champion for the serve-bucket pin (tier does not
#: matter — the lowered program is what is pinned)
_SERVE_CHAMPION = '''def priority_function(pod, node):
    """Constant-priority first-fit, pinned for the serve-bucket jaxpr."""
    return 1000
'''


def _micro_workload():
    """The tests/conftest.py micro recipe (2 nodes x 6 pods, padded to
    2x2x8) — duplicated here because the pinner must be runnable outside
    pytest (``cli lint`` subprocess); test_analysis pins the two copies
    against each other."""
    from fks_tpu.data.build import make_workload

    nodes = [{"node_id": "n0", "cpu_milli": 4000, "memory_mib": 8000,
              "gpus": [1000, 1000]},
             {"node_id": "n1", "cpu_milli": 2000, "memory_mib": 4000,
              "gpus": []}]
    pods = [{"pod_id": f"p{i}", "cpu_milli": 500, "memory_mib": 500,
             "num_gpu": i % 2, "gpu_milli": 300 * (i % 2),
             "creation_time": i, "duration_time": 5} for i in range(6)]
    return make_workload(nodes, pods, pad_nodes_to=2, pad_gpus_to=2,
                         pad_pods_to=8)


def _jaxpr_hash(fn, *args) -> str:
    import jax

    # a jaxpr prints function-valued params by repr (the batching rule of
    # vm._loop_bound's ``custom_vmap_call``): the address is the process's,
    # not the program's
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()


def compute_pins() -> Dict[str, object]:
    """Lower + hash every pinned entry point. Trace-only (make_jaxpr) —
    no XLA compiles — so the full sweep stays in seconds."""
    import jax

    jax.config.update("jax_enable_x64", True)  # match the pytest config
    import jax.numpy as jnp

    from fks_tpu.models import zoo
    from fks_tpu.sim import flat
    from fks_tpu.sim.engine import SimConfig, loop_tables

    wl = _micro_workload()
    policy = zoo.first_fit()
    pins: Dict[str, str] = {}

    for name, kw in FLAT_VARIANTS:
        cfg = SimConfig(**kw)
        ktable, max_steps = loop_tables(wl, cfg)
        step = flat.build_step(wl, policy, cfg, ktable, max_steps)
        pins[f"flat_step/{name}"] = _jaxpr_hash(
            step, flat.initial_state(wl, cfg))

    # the StageProfiler is host-side only: the baseline step traced
    # INSIDE an active profiler stage must hash identically to
    # flat_step/baseline — pinned so a future profiler edit that leaks
    # into tracing (a fence, a callback, a donated buffer) trips lint
    from fks_tpu.obs.profiler import StageProfiler

    cfg = SimConfig()
    ktable, max_steps = loop_tables(wl, cfg)
    step = flat.build_step(wl, policy, cfg, ktable, max_steps)
    with StageProfiler(scope="lint") as _prof, _prof.stage("pin"):
        pins["flat_step/profiled"] = _jaxpr_hash(
            step, flat.initial_state(wl, cfg))

    # probe_score gates finalize (not the step program), so the flag's
    # off/on pair is pinned on the finalize lowering
    for name, kw in (("baseline", {}), ("probe_score", {"probe_score": True})):
        cfg = SimConfig(**kw)
        pins[f"flat_finalize/{name}"] = _jaxpr_hash(
            lambda s, _cfg=cfg: flat.finalize(wl, _cfg, s),
            flat.initial_state(wl, cfg))

    cfg = SimConfig()
    run = flat.make_segmented_population_run(
        wl, lambda _p, pod, nodes: policy(pod, nodes), cfg, seg_steps=8)
    params = jnp.zeros((2, 1), jnp.float32)
    bstate = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (2,) + x.shape),
        flat.initial_state(wl, cfg))
    pins["segmented_advance/baseline"] = _jaxpr_hash(
        run.advance, params, bstate)

    # the sharded population eval over a 1-device mesh, so a refactor
    # that quietly changes the mesh layer's schedule (a different
    # in_spec, an extra collective) trips lint — intentional changes
    # re-pin
    from fks_tpu.models import parametric
    from fks_tpu.parallel.mesh import make_sharded_eval, population_mesh

    mesh1 = population_mesh(jax.devices()[:1])
    sharded = make_sharded_eval(wl, mesh1, cfg=SimConfig(), elite_k=2,
                                engine="flat")
    params2 = parametric.init_population(jax.random.PRNGKey(0), 2)
    pins["sharded_eval/default_layout"] = _jaxpr_hash(sharded, params2)

    from fks_tpu.serve.artifact import (
        ChampionSpec, ServeEngine, ShapeEnvelope,
    )

    env = ShapeEnvelope(max_pods=16, max_batch=1, min_pod_bucket=16)
    eng = ServeEngine(ChampionSpec(code=_SERVE_CHAMPION), wl,
                      envelope=env, engine="exact")
    pb = env.pod_buckets()[0]
    pins["serve_bucket/exact_l1_p16"] = _jaxpr_hash(
        eng._make_serve_fn(pb), *eng._example_batch(1, pb))

    return {"jax": jax.__version__, "x64": True, "pins": pins}


def check_pins(manifest_path: str = PIN_MANIFEST,
               current: Optional[Dict[str, object]] = None) -> List[str]:
    """Drift messages vs the manifest (empty == green). ``current`` lets
    tests inject a precomputed sweep instead of re-lowering."""
    if not os.path.exists(manifest_path):
        return [f"{manifest_path}: pin manifest missing "
                f"(generate with `python -m fks_tpu.cli lint --write-pins`)"]
    with open(manifest_path) as f:
        want = json.load(f)
    got = current if current is not None else compute_pins()
    msgs: List[str] = []
    if want.get("jax") != got["jax"]:
        msgs.append(f"jax version changed: pins from {want.get('jax')}, "
                    f"running {got['jax']} — re-pin with --write-pins")
    pinned: Dict[str, str] = dict(want.get("pins", {}))
    for name, h in got["pins"].items():
        p = pinned.pop(name, None)
        if p is None:
            msgs.append(f"unpinned entry point {name} "
                        f"(re-pin with --write-pins)")
        elif p != h:
            msgs.append(f"jaxpr drift: {name}: pinned {p[:12]} != "
                        f"current {h[:12]} — a lowered program changed; "
                        f"re-pin only if intentional")
    for name in sorted(pinned):
        msgs.append(f"stale pin {name}: entry point no longer lowered")
    return msgs


def write_pins(manifest_path: str = PIN_MANIFEST) -> Dict[str, object]:
    """Recompute and persist the manifest; returns it."""
    man = compute_pins()
    os.makedirs(os.path.dirname(manifest_path), exist_ok=True)
    with open(manifest_path, "w") as f:
        json.dump(man, f, indent=2, sort_keys=True)
        f.write("\n")
    return man
