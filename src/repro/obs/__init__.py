"""Observability layer: span tracing, typed metrics, trace export.

Three pieces (docs/observability.md):

* ``obs.trace`` — hierarchical :func:`span` timing with device sync on
  exit; the single timing code path for pipeline stages, shard_map phases
  and dispatched ops; :func:`readback`, the counted device→host read.
* ``obs.schema`` / ``obs.metrics`` — the declared metric registry and the
  validating :class:`Metrics` accumulator the stats dicts emit through.
* ``obs.export`` — Chrome trace-event / Perfetto JSON artifact writer.
* ``obs.memory`` — device-memory (HBM) watermark sampling with a
  live-buffer fallback; spans and benchmark records carry its columns.
* ``obs.experiments`` — declarative experiment engine: content-addressed
  result cache + append-only perf trajectory (``benchmarks/engine.py``).
"""

from .trace import (
    Span,
    ReadbackCount,
    Tracer,
    counting_readbacks,
    current_tracer,
    readback,
    span,
    sync,
    tracing,
)
from .metrics import Metrics, MetricsError, validated
from .export import span_tree, to_chrome_trace, write_chrome_trace
from .memory import MemorySample, Watermark, sample, watermark
from . import schema

__all__ = [
    "Span",
    "Tracer",
    "ReadbackCount",
    "counting_readbacks",
    "current_tracer",
    "readback",
    "span",
    "sync",
    "tracing",
    "Metrics",
    "MetricsError",
    "validated",
    "schema",
    "span_tree",
    "to_chrome_trace",
    "write_chrome_trace",
    "MemorySample",
    "Watermark",
    "sample",
    "watermark",
]
