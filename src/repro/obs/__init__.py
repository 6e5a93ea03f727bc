"""Jit/scan-safe telemetry: in-scan taps, JSONL emission, profiling hooks.

See DESIGN.md §15 for the telemetry contract (tap points, schema version,
off-by-default guarantee).
"""
from .profiling import (compile_count, compile_events, record_compile,
                        reset_compiles)
from .taps import ObsCfg, broadcast_diag, combine_updates, reduce_update_diag
from .writer import (SCHEMA, MetricWriter, cfg_hash, progress_line,
                     run_manifest, to_jsonable, validate_jsonl,
                     validate_record)

__all__ = [
    "ObsCfg", "broadcast_diag", "combine_updates", "reduce_update_diag",
    "SCHEMA", "MetricWriter", "cfg_hash", "progress_line", "run_manifest",
    "to_jsonable", "validate_jsonl", "validate_record",
    "compile_count", "compile_events", "record_compile", "reset_compiles",
]
