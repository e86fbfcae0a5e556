"""Observability utilities: profiling, logging, JSONL metrics.

The reference has neither profiler hooks nor ``logging`` (SURVEY.md §5);
these are framework additions with a reference-compatible metric schema.
"""
from fks_tpu.utils.cache import place_compile_cache
from fks_tpu.utils.logging import MetricsWriter, get_logger, result_record
from fks_tpu.utils.profiling import (
    ThroughputMeter, block_timed,
)
from fks_tpu.utils.segments import validate_seg_steps

__all__ = [
    "MetricsWriter", "get_logger", "place_compile_cache", "result_record",
    "ThroughputMeter", "block_timed",
    "validate_seg_steps",
]
