"""Dependency-free observability: metrics registry, structured event trace
with Chrome/Perfetto export, and STaMP quantization-health telemetry.

Three modules, layered by what they may import:

* `metrics.py` — pure stdlib.  `MetricsRegistry` with typed counters,
  gauges and fixed-bucket histograms (exponential buckets for latency-like
  quantities), labeled children, `snapshot()`/`reset()` and JSON +
  Prometheus-text exposition.  Both serving engines hang their whole
  `stats` surface off one registry.
* `trace.py` — pure stdlib.  The typed :class:`Event` record that replaced
  the engines' mixed-arity event tuples (tuple-unpacking stays compatible
  via ``__iter__``), the :class:`StepTimer` that times the engine step
  phases (plan / dispatch / post, and nested parts of a phase), each also
  an ``engine.<phase>`` profiler span, and `export_chrome_trace` rendering
  per-request span timelines + per-step phase slices as Chrome
  trace-event JSON (load in Perfetto / ``chrome://tracing``).
* `quantstats.py` — imports jax.  Per-STaMP-site activation clip rate,
  hi-token coverage, scale dynamic range and int-saturation counts,
  computed as cheap on-device reductions *inside* the existing step
  programs (zero extra device dispatches) and aggregated into the
  registry by the engines.
"""

from repro.obs.metrics import (LATENCY_BUCKETS, MetricsRegistry,  # noqa: F401
                               exponential_buckets)
from repro.obs.trace import Event, StepTimer, export_chrome_trace  # noqa: F401
