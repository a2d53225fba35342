"""Unified telemetry: structured metrics, profiler traces, run reports.

- ``repro.obs.metrics`` — :class:`MetricsRegistry` with typed
  scalar/series/counter/event emitters and pluggable sinks (JSONL file,
  in-memory for tests, CSV export). Device values are host-fetched in one
  batched ``block_until_ready`` at flush boundaries only.
- ``repro.obs.scopes`` — the names of the train step's device scopes
  and of ``Trainer.run``'s host spans, as the profiler trace carries them.
- ``repro.obs.trace`` — the ``--profile`` ``jax.profiler`` hook.
- ``repro.launch.report`` — CLI rendering a run's JSONL telemetry as a
  text summary.
"""
from repro.obs.metrics import (  # noqa: F401
    JsonlSink,
    MemorySink,
    MetricsRegistry,
    read_jsonl,
    write_csv,
)
from repro.obs.trace import profiler_session  # noqa: F401

__all__ = [
    "JsonlSink",
    "MemorySink",
    "MetricsRegistry",
    "read_jsonl",
    "write_csv",
    "profiler_session",
]
