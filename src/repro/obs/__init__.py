"""Zero-extra-sync telemetry (DESIGN.md §16).

Two parts, one per form of the program:

* **Spans and scopes** (``repro.obs.spans``): the names the program emits
  on the profiler's clock. The replay window step's phases are device
  scopes (``jax.named_scope``); the serve engine's phases are host spans
  (``jax.profiler.TraceAnnotation``), written only while a profiler runs.
  The serve engine's counts live in ``Engine.counters``.
* **The fabric's Recorder**: every sample rides inside a payload the
  fabric already fetches under its declared ``@sync_contract`` budgets —
  the fused per-segment stats fetch (``Fabric._fetch_view``) and the
  per-epoch moved-pages fetch (``Fabric._commit_epoch``). The
  :class:`Recorder` accumulates those piggybacked samples host-side into a
  metrics registry (counters / gauges / histograms keyed by
  ``state.COUNTER_NAMES`` — never integer literals, R3 stays clean) and a
  structured event log; exporters turn them into a Chrome/Perfetto
  ``trace_event`` timeline of the *modeled* pipeline pricing, a
  ``metrics.json`` snapshot, and the run manifest stamped into both.

Recording is opt-in: ``obs=None`` (the default everywhere) is the
recording-off path, bit-identical in pool/counter state to recording-on
(tests/test_obs.py pins this), and the analyzer's R6 rule enforces that
telemetry is emitted ONLY through these piggyback drains. ``spans`` is not
imported here: ``repro.obs`` loads on jax-free hosts.
"""
from repro.obs.manifest import manifest
from repro.obs.recorder import Recorder
from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry,
                                merge_histograms)
from repro.obs import export

__all__ = [
    "Recorder", "manifest", "export",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "merge_histograms",
]
