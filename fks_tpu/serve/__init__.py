"""Champion serving: pinned champion -> warm, no-recompile query engine.

- artifact: champion loading, shape envelope, AOT ServeEngine (optionally
  mesh-sharded with device-resident snapshot tables), save/load.
- vm_engine: the VM-native VMServeEngine — champion-as-data executables
  shared across champions, zero-rebuild ``swap_program`` hot-swap.
- batcher: query->workload construction, lane stacking, packed-upload
  helpers (query AND program tables), request coalescer; ``QueryFork``:
  what a serve engine built on a workload with a snapshot forks every
  query from (the loaded cluster's residents ++ the query's pods).
- service: request/metrics layer, JSONL + localhost HTTP fronts, selftest.
"""
from fks_tpu.serve.artifact import (
    ChampionSpec, ServeEngine, ShapeEnvelope,
    latest_champion, load_champion,
)
from fks_tpu.serve.batcher import (
    DEFAULT_DURATION, POD_FIELDS, QueryFork, RequestBatcher,
    build_query_workload,
    pack_program_tables, pack_query_tables, pods_to_dicts, query_pack_plan,
    stack_queries, stack_query_tables, tree_h2d_bytes,
    unpack_program_tables, unpack_query_tables, validate_query_pods,
)
from fks_tpu.serve.service import ServeService, make_http_server, selftest
from fks_tpu.serve.vm_engine import VMServeEngine

__all__ = [
    "ChampionSpec", "ServeEngine", "ShapeEnvelope", "VMServeEngine",
    "latest_champion", "load_champion",
    "DEFAULT_DURATION", "POD_FIELDS", "QueryFork", "RequestBatcher",
    "build_query_workload", "pack_program_tables", "pack_query_tables",
    "pods_to_dicts", "query_pack_plan", "stack_queries",
    "stack_query_tables", "tree_h2d_bytes", "unpack_program_tables",
    "unpack_query_tables", "validate_query_pods",
    "ServeService", "make_http_server", "selftest",
]
