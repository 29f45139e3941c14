"""Observability: span tracing, metrics, trace context, flight recorder.

Sibling modules, all dependency-free and safe to import from any layer:

- `obs.trace`  — thread-safe span tracer with Chrome trace-event JSON export
  (Perfetto-loadable); records once `trace.install()` runs
  (`dllama --trace out.json`, `bench.py --trace`), and every span is a
  `jax.profiler.TraceAnnotation` besides, so a profiler session sees the
  spans on the device's clock; `merge_chrome_traces` folds a fleet's
  per-process traces into one aligned file.
- `obs.metrics` — counters / gauges / histograms with Prometheus text
  exposition, served by `api_server` at `GET /metrics` (and as a JSON
  snapshot at `GET /v1/stats`).
- `obs.reqctx` — W3C trace-context (traceparent) propagation: one 128-bit
  trace id follows a request from the fleet router through the replica's
  HTTP handler into the BatchEngine scheduler's per-row work.
- `obs.flight` — per-request flight recorder: a bounded ring of the last N
  completed request timelines, served at `GET /v1/requests`, with a
  `--slow-log` JSONL exemplar stream.
- `obs.process` — process self-telemetry gauges (uptime, RSS, threads,
  tracer drops, build info) for /metrics.

The runtime (engine, batch_engine, speculative, paged_cache, hlo_stats) is
instrumented unconditionally: metrics cost one lock + add per event, a span
with no tracer installed costs one bare profiler annotation, the disabled
recorder one global check (perf/obs_overhead.py measures the bundle against
a decode dispatch).
docs/OBSERVABILITY.md has the full span/metric inventory.
"""

from . import flight, metrics, process, reqctx, trace

__all__ = ["flight", "metrics", "process", "reqctx", "trace"]
