"""Mesh scale-out: population sharding + ICI collectives for elite ranking.

TPU-native replacement for the reference's multi-worker story (a
``ProcessPoolExecutor`` with pickle-over-fork as the only inter-worker
substrate, reference: funsearch/funsearch_integration.py:535-562; elite
selection is a host-side Python sort at :494-496). Here:

- the candidate axis ``C`` is sharded over a 1-D ``jax.sharding.Mesh``
  ("pop" axis) via ``shard_map``; each device runs its population shard
  through the vmapped simulator entirely on-chip;
- per-shard fitness is combined with an **all_gather over ICI** so every
  device ranks the full population and agrees on the elite set (the
  BASELINE.json config-5 "ICI all-gather elite selection");
- only elite indices/scores return to host — candidate weights can stay
  device-resident across generations.

Single-host multi-chip uses one 1-D mesh over ``jax.devices()``.
Multi-slice / multi-host topologies use ``hybrid_population_mesh``: a 2-D
``("dcn", "pop")`` mesh whose outer axis crosses slice (DCN) boundaries and
whose inner axis rides ICI, after ``init_distributed()`` has brought up the
process group. The population is sharded over BOTH axes (it is the problem's
only parallel dimension); the fitness all-gather for elite ranking then
decomposes into an ICI gather within each slice and one DCN hop across
slices — collectives ride the fast fabric wherever possible, exactly the
layered layout the scaling playbook prescribes. shard_map and the
collectives are topology-agnostic; every entry point below accepts either
mesh shape.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fks_tpu.data.entities import Workload
from fks_tpu.models import parametric
from fks_tpu.parallel.population import ParamPolicyFn, lead_axis_size
from fks_tpu.sim.engine import SimConfig, initial_state, make_population_run_fn
from fks_tpu.utils.segments import segment_budget

POP_AXIS = "pop"
DCN_AXIS = "dcn"


def population_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D mesh over all (or the given) devices, population axis only.

    The problem has exactly one parallel dimension — candidates; events
    within a trace are sequential (SURVEY.md §5 long-context note) — so the
    mesh is 1-D by design, not a simplification.
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devices, (POP_AXIS,))


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> int:
    """Bring up the JAX process group for multi-host runs (the reference's
    only inter-worker substrate is a single-host ProcessPoolExecutor,
    funsearch_integration.py:535-562 — it has no multi-host story at all).

    On TPU pods with standard env (TPU_WORKER_HOSTNAMES etc.) the arguments
    auto-detect; pass them explicitly elsewhere. No-op when the process
    group is already up. A failed bring-up RAISES when explicit arguments
    were given (silently degrading a 2-host launch to one process would run
    at the wrong scale with no error); with auto-detection only, failure
    means single-process and is suppressed. Returns the process count.
    """
    explicit = any(v is not None
                   for v in (coordinator_address, num_processes, process_id))
    if not jax.distributed.is_initialized():
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id)
        except (RuntimeError, ValueError):
            if explicit:
                raise
            # auto-detect found no cluster env: single-process run
    return jax.process_count()


def hybrid_population_mesh(devices: Optional[Sequence] = None,
                           num_slices: Optional[int] = None) -> Mesh:
    """A 2-D ``("dcn", "pop")`` mesh: outer axis across slices/hosts (DCN),
    inner axis within a slice (ICI). The population shards over both; the
    elite all-gather then moves one message per slice over DCN instead of
    per-device traffic.

    ``num_slices`` defaults to ``jax.process_count()`` (multi-host) and must
    divide the device count. With one slice this degenerates to a
    ``[1, n]`` mesh — same program, no DCN axis traffic.
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    n = devices.size
    slices = num_slices or max(1, jax.process_count())
    if n % slices:
        raise ValueError(f"{n} devices not divisible into {slices} slices")
    return Mesh(devices.reshape(slices, n // slices), (DCN_AXIS, POP_AXIS))


def _pop_axes(mesh: Mesh):
    """The axes the population is sharded over, in mesh order: ("pop",) on
    a 1-D mesh, ("dcn", "pop") on a hybrid mesh."""
    return tuple(a for a in mesh.axis_names if a in (DCN_AXIS, POP_AXIS))


def num_shards(mesh: Mesh) -> int:
    """Total population shards: the product of the mesh's pop axes."""
    n = 1
    for a in _pop_axes(mesh):
        n *= mesh.shape[a]
    return n


_num_shards = num_shards  # internal alias, kept for existing call sites


def _shard_index(mesh: Mesh):
    """Linearized shard id inside shard_map (row-major over the pop axes)."""
    axes = _pop_axes(mesh)
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def pad_population(params, num_shards):
    """Pad C up to a multiple of the shard count (pass the mesh itself or an
    int); returns (padded, real_count).

    ``params`` is any pytree whose every leaf carries the candidate axis as
    its LEADING dimension — a parametric weight matrix ``[C, F]`` or a
    ``vm.stack_programs`` batch alike. Padding replicates the last
    candidate's slice on every leaf. Pass ``real_count`` back into the
    sharded eval so pad slots (duplicates of the last candidate) are masked
    out of elite selection.
    """
    if isinstance(num_shards, Mesh):
        num_shards = _num_shards(num_shards)
    c = lead_axis_size(params)
    target = -(-c // num_shards) * num_shards
    if target != c:
        def _pad_leaf(x):
            pad = jnp.tile(x[-1:], (target - c,) + (1,) * (x.ndim - 1))
            return jnp.concatenate([x, pad], axis=0)

        params = jax.tree_util.tree_map(_pad_leaf, params)
    return params, c


def pad_stats(real_count: int, num_shards) -> dict:
    """Pad-lane accounting for a ``pad_population`` launch (pass the mesh
    itself or a shard count): how many of the launched lanes are padding
    duplicates of the last candidate rather than real work.
    ``pad_waste_fraction`` is the device-time share spent on pad lanes —
    the number the flight recorder's ``mesh_snapshot`` reports."""
    if isinstance(num_shards, Mesh):
        num_shards = _num_shards(num_shards)
    real = int(real_count)
    padded = -(-real // num_shards) * num_shards if real else 0
    return {
        "real_count": real,
        "padded_count": padded,
        "pad_lanes": padded - real,
        "pad_waste_fraction": (padded - real) / padded if padded else 0.0,
    }


def occupancy_stats(real_count: int, num_shards, scenarios: int = 1,
                    segments: int = 1) -> dict:
    """``pad_stats`` extended with the other two batch axes a launch
    multiplies over — scenarios (``scenarios.suite`` vmap) and trace
    segments (the segmented runner's host loop) — for the device-time
    attribution profiler (``StageProfiler``): ``launched_lane_steps``
    is the total lane-dispatch count, ``real_lane_steps`` the share that
    was real candidates. Pad waste is per-lane, so it is unchanged by
    the extra axes; they scale the absolute accounting only."""
    s = pad_stats(real_count, num_shards)
    scenarios = max(1, int(scenarios))
    segments = max(1, int(segments))
    s["scenarios"] = scenarios
    s["segments"] = segments
    s["launched_lane_steps"] = s["padded_count"] * scenarios * segments
    s["real_lane_steps"] = s["real_count"] * scenarios * segments
    return s


def shard_population(params, mesh: Mesh):
    """``device_put`` every leaf of a candidate pytree with its leading
    (candidate) axis sharded over the mesh's pop axes. Identity layout for
    a bare ``jax.Array`` population — the historical fast path — and the
    generic entry for pytree payloads (stacked VM programs)."""
    c = lead_axis_size(params)
    if c % _num_shards(mesh):
        raise ValueError(
            f"population {c} not divisible by shard count "
            f"{_num_shards(mesh)}; use pad_population()")
    return jax.device_put(params, NamedSharding(mesh, P(_pop_axes(mesh))))


_shard_params = shard_population  # internal alias, kept for call sites


def lanes_per_device(x) -> dict:
    """``{device id: lanes held}`` for a device array whose leading axis
    is the lane/candidate axis (``addressable_shards``). A mesh launch in
    which some device holds no lanes is a placement bug the result alone
    cannot show; chip_smoke.py fails on it."""
    out: dict = {}
    for sh in x.addressable_shards:
        # from the shard's index: ``sh.data`` would materialize a
        # per-device array just to read its length
        lanes = len(range(*sh.index[0].indices(x.shape[0])))
        out[sh.device.id] = out.get(sh.device.id, 0) + lanes
    return out


# -------------------------------------------------------- serve batch axis
#
# The serving tier (fks_tpu.serve) coalesces concurrent what-if queries
# onto the SAME leading batch axis the population machinery shards — a
# query lane is a one-candidate population. These three helpers are the
# serve-side pad/shard specs, mirroring make_sharded_code_eval's layout so
# one AOT executable per (lane, pod) bucket spans the whole mesh.


def serve_lane_count(lane_bucket: int, mesh: Optional[Mesh] = None) -> int:
    """Global lane count for a serve dispatch: the PER-DEVICE lane bucket
    times the mesh's shard count (identity with no mesh). The serve engine
    compiles one executable per (global_lanes, pod_bucket), so "equal
    per-device batch" comparisons across mesh sizes share lane buckets;
    remainder lanes inside the global count are ``pad_population``
    duplicates, accounted by ``pad_stats``/``occupancy_stats``."""
    if mesh is None:
        return int(lane_bucket)
    return int(lane_bucket) * _num_shards(mesh)


def serve_sharding(mesh: Mesh) -> NamedSharding:
    """The NamedSharding that places a leading lane/batch axis over the
    mesh's pop axes — what serve uploads (query deltas, cached snapshot
    tables, initial states) are ``device_put`` with, and what the AOT
    executable's in_shardings are lowered from."""
    return NamedSharding(mesh, P(_pop_axes(mesh)))


def make_sharded_serve_fn(serve_fn, mesh: Mesh):
    """Wrap a lane-batched serve pipeline ``(pods, ktable, state0) ->
    SimResult`` in ``shard_map`` over the pop axes: every argument and
    result pytree shards on its leading lane axis. The pipeline contains
    NO collectives — each device drains its own lane chunk through its own
    ``run_batched_lanes`` while_loop, so per-device trip counts are
    independent and a short lane never stalls a long one across the mesh.
    ``check_vma=False`` for the same engine-internal reason as the
    population entry points (see NOTE below)."""
    axes = _pop_axes(mesh)
    return jax.shard_map(serve_fn, mesh=mesh,
                         in_specs=(P(axes), P(axes), P(axes)),
                         out_specs=P(axes), check_vma=False)


def make_sharded_vm_serve_fn(serve_fn, mesh: Mesh):
    """``make_sharded_serve_fn`` for the VM-native serving pipeline
    ``(program, pods, ktable, state0) -> SimResult``: the batch axes
    shard exactly as before, while the champion's packed ``VMProgram``
    tables (argument 0) are REPLICATED — ``P()`` as a pytree-prefix spec
    — so every device holds the full register program and lanes stay
    collective-free. One executable per (global_lanes, pod_bucket,
    program_capacity) then serves EVERY champion of that capacity bucket
    across the whole mesh."""
    axes = _pop_axes(mesh)
    return jax.shard_map(serve_fn, mesh=mesh,
                         in_specs=(P(), P(axes), P(axes), P(axes)),
                         out_specs=P(axes), check_vma=False)


def make_sharded_portfolio_serve_fn(serve_fn, mesh: Mesh):
    """``make_sharded_vm_serve_fn`` for the portfolio serving pipeline
    ``(slot_tables, slots, pods, ktable, state0) -> SimResult``: the
    stacked per-slot program tables (argument 0) are REPLICATED exactly
    like the single champion's tables — every device holds the FULL
    portfolio, so any lane on any device can dispatch to any slot — while
    the per-lane slot indices (argument 1) shard with the batch axes they
    index. Lanes stay collective-free: slot dispatch is a local gather
    into the replicated tables."""
    axes = _pop_axes(mesh)
    return jax.shard_map(serve_fn, mesh=mesh,
                         in_specs=(P(), P(axes), P(axes), P(axes), P(axes)),
                         out_specs=P(axes), check_vma=False)


def _global_results(run, state0, params_shard, axes):
    """Per-shard batched SimResult + the all-gather of the full population
    fitness vector (shared preamble of eval and generation-step). On a 1-D
    mesh the gather rides ICI only; on a hybrid mesh XLA decomposes the
    multi-axis gather into ICI-within-slice + one DCN hop. The full result
    stays shard-local (only the scalar score is gathered) so per-lane
    observables — the decision TraceBuffer included — ride out through the
    caller's sharded out_specs without crossing the interconnect."""
    res = run(params_shard, state0)
    return res, jax.lax.all_gather(res.policy_score, axes, tiled=True)


def _mask_pad(scores, real_count):
    """Pad slots must never win elite selection."""
    iota = jnp.arange(scores.shape[0])
    return jnp.where(iota < real_count, scores, -jnp.inf)


def _top_k_real(global_scores, real_count, k):
    """top_k that never surfaces a pad slot: when real_count < k the
    trailing slots repeat the best real candidate instead of returning a
    -inf pad entry (which would otherwise survive truncation and be
    sampled as a mutation parent)."""
    elite_scores, elite_idx = jax.lax.top_k(
        _mask_pad(global_scores, real_count), k)
    valid = jnp.isfinite(elite_scores)
    return (jnp.where(valid, elite_scores, elite_scores[0]),
            jnp.where(valid, elite_idx, elite_idx[0]))


# NOTE on check_vma=False: the engine's inner heap loops mix invariant
# literals into varying carries; the varying-manual-axes audit rejects that
# even though the program is correct. Correctness of the sharded path is
# covered by the sharded-vs-vmap parity tests instead.


def _engine_runner(workload, param_policy, cfg, engine):
    """(population run fn, initial state) for the chosen engine."""
    from fks_tpu.parallel.population import FUSED_ENGINES, fused_runner
    if engine in FUSED_ENGINES:
        frun = fused_runner(workload, param_policy, cfg, engine)
        return (lambda params, _state0: frun(params)), None
    from fks_tpu.sim import get_engine
    mod = get_engine(engine)
    return (mod.make_population_run_fn(workload, param_policy, cfg),
            mod.initial_state(workload, cfg))


def make_sharded_eval(workload: Workload, mesh: Mesh,
                      param_policy: ParamPolicyFn = parametric.score,
                      cfg: SimConfig = SimConfig(),
                      elite_k: int = 8, engine: str = "exact"):
    """Build ``eval(params[C, F], real_count) -> (scores[C], elite_idx[K],
    elite_scores[K])``.

    ``C`` must be a multiple of the mesh size (use ``pad_population``, and
    forward its ``real_count`` so duplicate pad candidates are excluded from
    the elite ranking). Inside ``shard_map`` each device vmaps over its
    C/shards chunk, then the fitness vector is all-gathered over the ``pop``
    ICI axis and every device computes the identical global top-k — the elite
    set used for parent sampling and truncation (reference semantics: sort
    desc + take elite_size, funsearch_integration.py:494-496).

    With ``cfg.decision_trace`` the tuple grows a fourth element: the
    per-candidate TraceBuffer pytree, sharded over ``pop`` like the scores
    (a ``P(axes)`` out_spec prefix over the whole subtree). Existing
    callers index the first three slots, so the extension is opt-in.
    """
    run, state0 = _engine_runner(workload, param_policy, cfg, engine)
    axes = _pop_axes(mesh)
    out_specs = (P(axes), P(), P()) + ((P(axes),) if cfg.decision_trace else ())

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axes), P()),
        out_specs=out_specs,
        check_vma=False,
    )
    def shard_eval(params_shard, real_count):
        res, global_scores = _global_results(run, state0, params_shard, axes)
        elite_scores, elite_idx = _top_k_real(global_scores, real_count, elite_k)
        out = (res.policy_score, elite_idx, elite_scores)
        if cfg.decision_trace:
            out = out + (res.trace,)
        return out

    def sharded_eval(params, real_count=None):
        params = _shard_params(params, mesh)
        if real_count is None:
            real_count = params.shape[0]
        return shard_eval(params, jnp.asarray(real_count, jnp.int32))

    return jax.jit(sharded_eval)


def make_sharded_generation_step(workload: Workload, mesh: Mesh,
                                 param_policy: ParamPolicyFn = parametric.score,
                                 cfg: SimConfig = SimConfig(),
                                 elite_k: int = 4,
                                 noise: float = 0.05,
                                 engine: str = "exact"):
    """One full on-device evolution generation for parametric populations:
    evaluate (sharded) -> all-gather fitness -> top-k elites -> mutate
    offspring from elites. This is the framework's "training step" — the
    device-resident analogue of the reference's evolve_generation
    (funsearch_integration.py:487-572) minus the host-side LLM stage, which
    stays on CPU exactly as the reference keeps it outside its hot path.

    Returns ``step(params[C,F], key, real_count=None) -> (new_params[C,F],
    scores[C], elite_scores[K])``; both params arrays are sharded over
    ``pop``. Forward ``pad_population``'s ``real_count`` so pad duplicates
    never win elite slots.
    """
    run, state0 = _engine_runner(workload, param_policy, cfg, engine)
    axes = _pop_axes(mesh)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axes), P(), P()),
        out_specs=(P(axes), P(axes), P()),
        check_vma=False,
    )
    def gen_step(params_shard, key, real_count):
        res, global_scores = _global_results(run, state0, params_shard, axes)
        local_scores = res.policy_score
        all_params = jax.lax.all_gather(params_shard, axes, tiled=True)
        elite_scores, elite_idx = _top_k_real(global_scores, real_count, elite_k)
        elites = all_params[elite_idx]

        # Per-shard offspring: elites survive in shard 0's slots, the rest
        # mutate from a random elite. Keys are folded per-shard so shards
        # draw independent noise.
        shard_id = _shard_index(mesh)
        k = jax.random.fold_in(key, shard_id)
        local_c = params_shard.shape[0]
        offspring = parametric.mutate(k, elites, local_c, noise)
        slot = shard_id * local_c + jnp.arange(local_c)
        is_elite_slot = slot < elite_k
        survivors = elites[jnp.minimum(slot, elite_k - 1)]
        new_shard = jnp.where(is_elite_slot[:, None], survivors, offspring)
        return new_shard, local_scores, elite_scores

    def step(params, key, real_count=None):
        params = _shard_params(params, mesh)
        if real_count is None:
            real_count = params.shape[0]
        return gen_step(params, key, jnp.asarray(real_count, jnp.int32))

    return jax.jit(step)


def make_sharded_code_eval(workload: Workload, mesh: Mesh,
                           cfg: SimConfig = SimConfig(),
                           elite_k: int = 8, engine: str = "exact",
                           seg_steps: int = 0, on_segment=None):
    """Build ``eval(stacked, real_count) -> (result, elite_idx[K],
    elite_scores[K])`` for STACKED VM code candidates — the code-candidate
    analogue of ``make_sharded_eval``.

    ``stacked`` is a ``vm.stack_programs`` batch; its candidate count must
    be a multiple of the mesh size (use ``pad_population``, which is
    pytree-generic, and forward ``real_count`` so pad duplicates are
    excluded from the elite ranking). Inside ``shard_map`` each device
    interprets its shard of the program batch through the population
    engine (``vm.score`` — one compiled program for the whole VM
    vocabulary, zero per-candidate XLA compiles), then the fitness vector
    is all-gathered over the pop axes so every device computes the
    identical global top-k. This closes the gap between the parametric
    tier (mesh-wide since the seed) and the headline FunSearch workload,
    which previously vmapped on one device (backend._run_vm_batch).

    ``result`` is the full per-candidate ``SimResult`` (sharded over the
    pop axes): the backend's failure semantics need ``failed``/
    ``truncated``/``policy_score``, not a bare fitness vector.

    ``seg_steps > 0`` bounds each device call to ~``seg_steps`` events per
    dispatch (the FKS_VM_SEG_STEPS contract: the host regains control
    between dispatches — see ``flat.make_segmented_population_run``);
    engines without segmented internals fall back to the single-dispatch
    path. ``on_segment`` (zero-arg callable) fires on
    the host after every segment dispatch — the flight recorder's segment
    counter; ignored on the single-dispatch path.
    """
    from fks_tpu.funsearch import vm
    from fks_tpu.sim import get_engine

    mod = get_engine(engine)
    if seg_steps > 0 and hasattr(mod, "make_segmented_population_run"):
        return _make_segmented_code_eval(workload, mesh, cfg, elite_k, mod,
                                         seg_steps, on_segment)

    run = mod.make_population_run_fn(workload, vm.score, cfg)
    state0 = mod.initial_state(workload, cfg)
    axes = _pop_axes(mesh)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axes), P()),
        out_specs=(P(axes), P(), P()),
        check_vma=False,
    )
    def shard_eval(progs_shard, real_count):
        res = run(progs_shard, state0)
        global_scores = jax.lax.all_gather(res.policy_score, axes,
                                           tiled=True)
        elite_scores, elite_idx = _top_k_real(global_scores, real_count,
                                              elite_k)
        return res, elite_idx, elite_scores

    def sharded_eval(stacked, real_count=None):
        stacked = shard_population(stacked, mesh)
        if real_count is None:
            real_count = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        return shard_eval(stacked, jnp.asarray(real_count, jnp.int32))

    return jax.jit(sharded_eval)


def _make_segmented_code_eval(workload: Workload, mesh: Mesh, cfg: SimConfig,
                              elite_k: int, mod, seg_steps: int,
                              on_segment=None):
    """The segmented body of ``make_sharded_code_eval``: a host loop of
    jitted shard_map'd segments — ``flat.make_segmented_population_run``
    mirrored one level up, at the mesh. Per segment every shard advances
    its lanes ~``seg_steps`` events inside a bounded while_loop; one
    psum'd any-lane-active flag returns to the host, which re-dispatches
    until every lane on every shard drains (same carry, same divergence
    guard as the single-device runner). The handoff is double-buffered
    like ``flat.make_segmented_population_run``'s: segment i+1 is
    dispatched before segment i's psum'd flag is read, so no shard ever
    stalls on the host's flag sync; the flag lags one segment, the one
    overrun segment self-masks to a no-op on every shard, and the budget
    carries the matching extra observation slot (slack 2)."""
    from fks_tpu.funsearch import vm

    axes = _pop_axes(mesh)
    ktable, max_steps = mod.loop_tables(workload, cfg)

    def step_one(prog, s):
        return mod.build_step(
            workload, lambda pod, nodes: vm.score(prog, pod, nodes),
            cfg, ktable, max_steps)(s)

    vstep = jax.vmap(step_one, in_axes=(0, 0))

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axes), P(axes)),
        out_specs=(P(axes), P()),
        check_vma=False,
    )
    def advance(progs_shard, bstate_shard):
        start = bstate_shard.steps  # frozen at segment entry

        def cond(s):
            return jnp.any(mod.lane_active(s, max_steps)
                           & (s.steps - start < seg_steps))

        out = jax.lax.while_loop(
            cond, lambda s: vstep(progs_shard, s), bstate_shard)
        # psum, not all_gather: one scalar per shard, and every device
        # holds the identical global continue/stop flag
        local = jnp.any(mod.lane_active(out, max_steps))
        active = jax.lax.psum(local.astype(jnp.int32), axes) > 0
        return out, active

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axes), P()),
        out_specs=(P(axes), P(), P()),
        check_vma=False,
    )
    def finish(bstate_shard, real_count):
        res = jax.vmap(lambda s: mod.finalize(workload, cfg, s))(bstate_shard)
        global_scores = jax.lax.all_gather(res.policy_score, axes,
                                           tiled=True)
        elite_scores, elite_idx = _top_k_real(global_scores, real_count,
                                              elite_k)
        return res, elite_idx, elite_scores

    state0 = mod.initial_state(workload, cfg)
    from fks_tpu.obs.spans import span

    def run(stacked, real_count=None):
        with span("mesh/shard_put"):
            stacked = shard_population(stacked, mesh)
            pop = jax.tree_util.tree_leaves(stacked)[0].shape[0]
            if real_count is None:
                real_count = pop
            bstate = jax.device_put(mod.broadcast_state(state0, pop),
                                    NamedSharding(mesh, P(_pop_axes(mesh))))
        active = True
        prev = None
        segments = 0
        for _ in range(segment_budget(max_steps, seg_steps, slack=2)):
            # one span per segment; its self time is the launch, its
            # ``wait`` child the host blocked on the device
            with span("mesh/segment", segment=segments):
                bstate, active = advance(stacked, bstate)
                segments += 1
                if on_segment is not None:
                    on_segment()
                # double-buffered handoff: sync on the PREVIOUS segment's
                # psum'd flag only after this segment is already in flight
                if prev is not None:
                    with span("mesh/segment/wait"):
                        drained = not bool(prev)
                    if drained:
                        active = prev
                        break
                prev = active
        if bool(active):
            raise RuntimeError(
                "sharded segmented runner exhausted its segment budget "
                "with lanes still active — cond/step divergence in the "
                "population engine")
        with span("mesh/finish"):
            return finish(bstate, jnp.asarray(real_count, jnp.int32))

    # the jitted one-segment program, as flat's segmented runner exposes
    # its own: what the lowering tests trace
    run.advance = advance
    return run
