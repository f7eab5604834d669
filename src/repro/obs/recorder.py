"""The Recorder: host-side accumulator for piggybacked telemetry.

Every ``record_*`` method consumes values that are ALREADY host numpy —
the deltas, times and flags that fell out of the fabric's single
contracted fetches (``Fabric._fetch_view``'s fused per-segment sync,
``Fabric._commit_epoch``'s per-epoch sync) plus host-only scheduling facts
(migration plans, simx cells). Handing the
Recorder a device value is a bug the analyzer's R6 rule flags at the
source level; at runtime the contracts' budgets stay unchanged because
nothing here ever crosses the host/device boundary.

Samples land in two places: a :class:`~repro.obs.registry.MetricsRegistry`
(aggregates; counter metrics keyed by ``state.COUNTER_NAMES`` via zip —
no integer-literal indexing, the R3 layout rule stays clean) and ordered
per-domain event lists (``segments`` / ``plans`` / ``epochs`` for the
fabric) that the exporters in ``repro.obs.export`` turn into a Perfetto
timeline and ``metrics.json``. The serving engine records no samples here:
its telemetry is its host spans on the profiler's clock
(``repro.obs.spans``) and ``Engine.counters``.

The module imports neither jax nor the engine packages at module level
(``repro.obs`` must import on jax-free hosts for ``manifest()``); the
counter-name table is pulled lazily on the first fabric sample.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from repro.obs.registry import MetricsRegistry

# microsecond buckets for delivered-time histograms: 1-2-5 decades from
# 1 us to 50 s (modeled per-segment times live in the ms range)
TIME_US_BOUNDS = tuple(m * 10 ** e for e in range(0, 8) for m in (1, 2, 5))


class Recorder:
    """Accumulates piggybacked samples from one run (one fabric, simx
    cells). Opt-in: constructed by the caller and passed as
    ``obs=`` — the ``obs=None`` default everywhere is the recording-off
    path, bit-identical in pool/counter state (tests/test_obs.py)."""

    def __init__(self):
        self.metrics = MetricsRegistry()
        # fabric timeline, in record order
        self.segments: List[Dict[str, Any]] = []   # one per replayed segment
        self.plans: List[Dict[str, Any]] = []      # one per non-empty plan
        self.epochs: List[Dict[str, Any]] = []     # one per committed epoch
        self.cells: List[Dict[str, Any]] = []      # simx workload cells
        self.fabric_info: Optional[Dict[str, Any]] = None

    # -- attach ---------------------------------------------------------------

    @staticmethod
    def _delta_dict(delta: np.ndarray) -> Dict[str, int]:
        """Name-keyed counter delta via ``state.counters_delta_dict`` —
        the layout-safe (R3) bridge from fetched vectors to metric names.
        Lazy import: repro.obs must load on jax-free hosts."""
        from repro.core.engine import state as S
        return S.counters_delta_dict(delta)

    def attach_fabric(self, fabric) -> None:
        """Called by ``Fabric.__init__`` when constructed with ``obs=``.
        Captures the run facts the exporters need (fleet for pricing,
        scheduler mode for labeling) — never live device state."""
        self.fabric_info = {
            "n_expanders": fabric.n_expanders,
            "devices": list(fabric.devices),     # DeviceConfig per expander
            "window": fabric.window,
            "spill_interval": fabric.spill_interval,
            "pipeline_depth": fabric.pipeline_depth,
            "sync_migration": fabric.sync_migration,
            "migration": fabric.migration_policy.name,
            "migration_enabled": fabric.migration_enabled,
            # None on vmap drivers; the sharded driver's mesh size. The
            # exporters derive the block expander->device placement from
            # (n_expanders, shard_devices) — the recorder stays jax-free.
            "shard_devices": getattr(fabric, "shard_devices", None),
        }

    # -- fabric drains (host values from the contracted fetches) --------------

    def record_segment(self, seg: int, delta: np.ndarray, times: np.ndarray,
                       free_units: Optional[np.ndarray]) -> None:
        """One replayed segment, from ``_fetch_view``'s single fused sync:
        the replay counter delta (int64 [N, C]), the in-jit per-expander
        delivered times (float64 [N] seconds), and the freelist headroom
        (int64 [N] chunk units; None before the first stats fetch)."""
        delta = np.asarray(delta, np.int64)
        times = np.asarray(times, np.float64)
        self.segments.append({
            "seg": int(seg), "delta": delta, "times": times,
            "free_units": None if free_units is None
            else np.asarray(free_units, np.int64).copy(),
        })
        for name, v in self._delta_dict(delta).items():
            self.metrics.counter(f"fabric.{name}").inc(v)
        th = self.metrics.histogram("fabric.segment_time_us", TIME_US_BOUNDS)
        for t in times:
            th.observe(float(t) * 1e6)
        if free_units is not None:
            self.metrics.gauge("fabric.free_units_min").set(
                float(np.min(free_units)))
            self.metrics.histogram("fabric.free_units").observe(
                float(np.min(free_units)))

    def record_plan(self, seg: int, plan, policy: str) -> None:
        """A migration plan the policy produced at segment ``seg``'s
        boundary (pure host data — planning never touches the device)."""
        self.plans.append({
            "seg": int(seg), "policy": policy, "pages": int(len(plan)),
            "urgent": bool(plan.urgent),
            "pairs": plan.pairs(),
        })
        self.metrics.counter("fabric.plans").inc()
        self.metrics.counter("fabric.pages_planned").inc(int(len(plan)))
        if plan.urgent:
            self.metrics.counter("fabric.plans_urgent").inc()

    def record_epoch(self, seg: int, delta: np.ndarray, *, kind: str,
                     overlapped: bool, planned: int, moved: int,
                     urgent: bool, free_units: np.ndarray) -> None:
        """One committed migration epoch, from ``_commit_epoch``'s single
        sync: the migration counter delta (int64 [N, C]) tagged with the
        segment whose replay it overlapped and how it was scheduled
        (``kind``: overlapped | urgent | sync | drain)."""
        delta = np.asarray(delta, np.int64)
        self.epochs.append({
            "seg": int(seg), "delta": delta, "kind": str(kind),
            "overlapped": bool(overlapped), "planned": int(planned),
            "moved": int(moved), "urgent": bool(urgent),
            "free_units": np.asarray(free_units, np.int64).copy(),
        })
        for name, v in self._delta_dict(delta).items():
            self.metrics.counter(f"fabric.migration.{name}").inc(v)
        self.metrics.counter("fabric.epochs").inc()
        self.metrics.counter(f"fabric.epochs_{kind}").inc()
        self.metrics.counter("fabric.pages_moved").inc(int(moved))
        if planned and not moved:
            self.metrics.counter("fabric.epochs_stalled").inc()

    # -- simx drains ------------------------------------------------------------

    def record_cell(self, scheme: str, workload: str,
                    metrics: Dict[str, Any]) -> None:
        """One finished (scheme x workload) simx cell — the metrics dict
        ``run_workload`` assembled is host data already; recording it is
        free. Delivered time lands in the shared time histogram so sweep
        aggregations merge with fabric segment times."""
        self.cells.append({"scheme": str(scheme), "workload": str(workload),
                           "time_s": float(metrics["time_s"]),
                           "normalized_perf":
                               float(metrics["normalized_perf"])})
        self.metrics.counter("simx.cells").inc()
        self.metrics.histogram("simx.cell_time_us", TIME_US_BOUNDS).observe(
            float(metrics["time_s"]) * 1e6)
        self.metrics.gauge(
            f"simx.normalized_perf.{scheme}.{workload}").set(
            float(metrics["normalized_perf"]))
