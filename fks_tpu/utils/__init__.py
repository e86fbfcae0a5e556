"""Observability utilities: profiling, logging, JSONL metrics.

The reference has neither profiler hooks nor ``logging`` (SURVEY.md §5);
these are framework additions with a reference-compatible metric schema.
"""
from fks_tpu.utils.cache import place_compile_cache
from fks_tpu.utils.logging import MetricsWriter, get_logger, result_record
from fks_tpu.utils.profiling import (
    ThroughputMeter, Timing, block_timed, device_trace, timed,
)
from fks_tpu.utils.segments import validate_seg_steps

__all__ = [
    "MetricsWriter", "get_logger", "place_compile_cache", "result_record",
    "ThroughputMeter", "Timing", "block_timed", "device_trace", "timed",
    "validate_seg_steps",
]
