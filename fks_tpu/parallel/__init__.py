"""Population/mesh parallelism: the framework's distributed backend.

Replaces the reference's ProcessPoolExecutor fan-out (reference:
funsearch/funsearch_integration.py:535-562) with ``vmap`` on-chip and
``shard_map`` + ICI all-gather across a ``jax.sharding.Mesh``.
"""
from fks_tpu.parallel.population import (  # noqa: F401
    ParamPolicyFn, fitness, lead_axis_size, make_population_eval,
    make_single_run,
)
from fks_tpu.parallel.mesh import (  # noqa: F401
    DCN_AXIS, POP_AXIS, hybrid_population_mesh, init_distributed,
    lanes_per_device,
    make_sharded_code_eval, make_sharded_eval, make_sharded_generation_step,
    make_sharded_serve_fn, num_shards, occupancy_stats, pad_population,
    pad_stats, population_mesh, serve_lane_count, serve_sharding,
    shard_population,
)
