"""Delivered-time accounting tests (DESIGN.md §12).

  * parity — ``exec_time_dict`` and the vectorized counter-array model are
    BITWISE identical to the pre-refactor scalar model (the legacy formula
    is transcribed verbatim below as the reference), on homogeneous
    configs, including the host=0 edge and the uncompressed baseline;
  * array-native — ``exec_time_vec`` runs inside jit/vmap over a stacked
    ``DeviceLanes`` fleet and agrees with the host float64 path;
  * monotonicity — more internal accesses never decreases delivered time,
    and the fig14 (CXL latency) / fig15 (decompression cycles) sensitivity
    sweeps are monotone per scheme — pinned as regression tests, not just
    bench output;
  * drift guards — ``DeviceLanes`` mirrors every ``DeviceConfig`` field and
    ``ideal_bandwidth`` preserves every field except ``ch_bw``;
  * serving — ``serve_modeled_time`` prices byte/sync counters sanely
    (monotone in bytes, bottleneck across expanders).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.types import replace
from repro.core.engine import state as S
from repro.simx import time as TM


def _legacy_exec_time(traffic, dev):
    """The pre-refactor scalar model, verbatim — the parity reference."""
    host = traffic["host_reads"] + traffic["host_writes"]
    internal = traffic["internal_accesses"]
    t_mem = internal * 64 / (dev.channels * dev.ch_bw)
    t_cxl = host * 64 / dev.cxl_bw
    n_comp = (traffic.get("demotions_dirty", 0)
              + traffic.get("recompress_retry", 0)) * dev.block_scale * 4
    n_decomp = traffic.get("promotions", 0) * dev.block_scale
    t_engine = (n_comp * dev.comp_cycles + n_decomp * dev.decomp_cycles) \
        / dev.clock
    zero_frac = traffic.get("zero_served", 0) / max(host, 1)
    accesses_per_host = internal / max(host, 1)
    decomp_lat_frac = traffic.get("promotions", 0) / max(host, 1)
    l_avg = dev.cxl_lat + (1 - zero_frac) * dev.dram_lat \
        + accesses_per_host * dev.dram_lat * 0.25 \
        + decomp_lat_frac * dev.decomp_cycles / dev.clock
    t_lat = host * l_avg / dev.mlp
    return max(t_mem, t_cxl, t_engine, t_lat)


def _traffic_samples(n=64, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        t = {k: int(rng.integers(0, 50000)) for k in S.COUNTER_NAMES}
        t["internal_accesses"] = sum(t[k] for k in S.TRAFFIC_NAMES)
        out.append(t)
    out.append({k: 0 for k in S.COUNTER_NAMES} | {"internal_accesses": 0})
    out.append({k: 0 for k in S.COUNTER_NAMES}
               | {"internal_accesses": 17, "zero_served": 3})  # host == 0
    return out


DEVICES = [TM.DeviceConfig(), TM.DeviceConfig(block_scale=4.0),
           TM.DEVICE_PROFILES["gen4"], TM.DEVICE_PROFILES["far"],
           TM.ideal_bandwidth(TM.DeviceConfig())]


def test_dict_shim_bitwise_parity_with_legacy_scalar():
    for t in _traffic_samples():
        for dev in DEVICES:
            assert TM.exec_time_dict(t, dev) == _legacy_exec_time(t, dev)


def test_vectorized_counters_bitwise_parity_with_legacy_scalar():
    """The counter-array model (float64 host path) == the legacy scalar,
    bitwise, when internal equals the category sum (homogeneous config)."""
    for t in _traffic_samples():
        vec = TM.counters_from_dict(t)
        t = dict(t, internal_accesses=sum(t[k] for k in S.TRAFFIC_NAMES))
        for dev in DEVICES:
            assert float(TM.exec_time_vec(vec, dev)) == \
                _legacy_exec_time(t, dev)


def test_uncompressed_time_matches_legacy_and_counter_layout():
    """Baseline derived from COUNTER_NAMES == the legacy hand-built dict."""
    for n in (0, 1, 7, 12345):
        legacy = _legacy_exec_time(
            {"host_reads": n, "host_writes": 0, "internal_accesses": n,
             "zero_served": 0, "promotions": 0, "demotions_dirty": 0},
            TM.DeviceConfig())
        assert TM.uncompressed_time(n, TM.DeviceConfig()) == legacy
    vec = TM.uncompressed_counters(9)
    assert vec.shape == (S.NUM_COUNTERS,)
    assert vec[S.C_HOST_RD] == 9 and S.traffic_vector(vec).sum() == 9


def test_ideal_bandwidth_preserves_every_other_field():
    """dataclasses.replace-based: a new DeviceConfig field can never be
    silently dropped by the ideal-bandwidth variant."""
    kw = {"channels": 3, "cxl_bw": 1.0, "cxl_lat": 2.0, "dram_lat": 3.0,
          "clock": 4.0, "comp_cycles": 5, "decomp_cycles": 6, "mlp": 7.0,
          "block_scale": 8.0}
    ideal = TM.ideal_bandwidth(TM.DeviceConfig(ch_bw=44.8e9, **kw))
    assert ideal.ch_bw == 1e15
    for f in dataclasses.fields(TM.DeviceConfig):
        if f.name != "ch_bw":
            assert getattr(ideal, f.name) == kw[f.name], f.name


def test_device_lanes_mirror_device_config_fields():
    """Drift guard: DeviceLanes must carry every DeviceConfig field (and
    stack_devices round-trips the values)."""
    names = {f.name for f in dataclasses.fields(TM.DeviceConfig)}
    assert names == set(TM.DeviceLanes._fields)
    devs = [TM.DeviceConfig(), TM.DEVICE_PROFILES["gen4"]]
    lanes = TM.stack_devices(devs, xp=np)
    for n in names:
        assert lanes._asdict()[n].shape == (2,)
        assert list(lanes._asdict()[n]) == [getattr(d, n) for d in devs]


def test_exec_time_vec_inside_jit_vmap_matches_host_float64():
    """The array path runs under jit + vmap over a stacked (mixed-
    generation) fleet and agrees with the float64 host path."""
    rng = np.random.default_rng(1)
    counters = rng.integers(0, 20000, (4, S.NUM_COUNTERS)).astype(np.int32)
    devs = [TM.DeviceConfig(), TM.DEVICE_PROFILES["gen4"],
            TM.DEVICE_PROFILES["far"], TM.DeviceConfig(block_scale=4.0)]
    lanes_j = TM.stack_devices(devs, xp=jnp)
    times_j = jax.jit(jax.vmap(TM.exec_time_vec))(jnp.asarray(counters),
                                                  lanes_j)
    times_h = TM.exec_time_vec(np.asarray(counters, np.float64),
                               TM.stack_devices(devs, xp=np))
    assert np.allclose(np.asarray(times_j, np.float64), times_h, rtol=1e-4)
    # per-lane: each expander priced by its OWN config
    for e, dev in enumerate(devs):
        assert times_h[e] == float(TM.exec_time_vec(
            np.asarray(counters[e], np.float64), dev))


def test_more_internal_accesses_never_decreases_time():
    """Delivered-time monotonicity: traffic rows that differ only by extra
    internal accesses sort the same way in time — checked in one
    vectorized call over a 64-point ramp."""
    base = TM.counters_from_dict(
        {"host_reads": 500, "host_writes": 100, "data_rd": 1000,
         "promotions": 20, "demotions_dirty": 10, "zero_served": 5})
    ramp = np.broadcast_to(base, (64, S.NUM_COUNTERS)).copy()
    ramp[:, S.C_DATA_RD] += 250 * np.arange(64)
    for dev in DEVICES:
        t = TM.exec_time_vec(ramp, dev)
        assert (np.diff(t) >= 0).all(), dev


@pytest.fixture(scope="module")
def small_cells():
    from repro.simx.engine import run_workload
    from repro.simx.trace import WORKLOADS
    kw = dict(n_accesses=768, promoted_pages=32)
    return {s: run_workload(s, WORKLOADS["pr"], **kw)
            for s in ("ibex", "tmcc")}


def test_fig14_cxl_latency_sweep_monotone_per_scheme(small_cells):
    """Fig. 14 regression: per scheme, delivered time (and the uncompressed
    baseline) never decreases as CXL latency grows, and the normalized-perf
    curve is monotone — its slope never changes sign across the sweep (the
    direction depends on which side is latency-bound: the uncompressed
    baseline is, so the ratio may rise with latency)."""
    lats = (70e-9, 110e-9, 150e-9, 250e-9, 400e-9)
    for scheme, r in small_cells.items():
        devs = [replace(TM.DeviceConfig(), cxl_lat=lat) for lat in lats]
        lanes = TM.stack_devices(devs, xp=np)
        vec = TM.counters_from_dict(r)
        t = TM.exec_time_vec(np.broadcast_to(vec, (len(devs),) + vec.shape),
                             lanes)
        host = r["host_reads"] + r["host_writes"]
        base = TM.uncompressed_time(np.full((len(devs),), host), lanes)
        assert (np.diff(t) >= 0).all(), scheme
        assert (np.diff(base) >= 0).all(), scheme
        d = np.diff(base / t)
        assert (d >= -1e-12).all() or (d <= 1e-12).all(), (scheme, d)


def test_fig15_decomp_cycles_sweep_monotone_per_scheme(small_cells):
    """Fig. 15 regression: per scheme, delivered time is monotone
    non-decreasing in decompression cycles."""
    cycs = (64, 96, 128, 256, 512)
    for scheme, r in small_cells.items():
        devs = [replace(TM.DeviceConfig(), decomp_cycles=c) for c in cycs]
        lanes = TM.stack_devices(devs, xp=np)
        vec = TM.counters_from_dict(r)
        t = TM.exec_time_vec(np.broadcast_to(vec, (len(devs),) + vec.shape),
                             lanes)
        assert (np.diff(t) >= 0).all(), scheme


def test_serve_modeled_time_monotone_and_bottlenecked():
    counters = {"step_syncs": 100, "admit_syncs": 10, "steps": 100}
    stats = {"preempt_bytes": np.array([1 << 20, 1 << 18]),
             "resume_bytes": np.array([1 << 19, 1 << 17])}
    devs = [TM.DeviceConfig(), TM.DEVICE_PROFILES["gen4"]]
    m = TM.serve_modeled_time(counters, stats, devs)
    assert m["modeled_s"] > m["sync_s"] > 0
    assert m["modeled_s_per_step"] == pytest.approx(m["modeled_s"] / 100)
    assert len(m["motion_s_per_expander"]) == 2
    # more parked bytes on the same expander -> no less time
    stats2 = {"preempt_bytes": stats["preempt_bytes"] * 4,
              "resume_bytes": stats["resume_bytes"]}
    m2 = TM.serve_modeled_time(counters, stats2, devs)
    assert m2["modeled_s"] >= m["modeled_s"]
    # bottleneck: the modeled total uses the max lane, not the sum
    assert m["modeled_s"] == pytest.approx(
        m["sync_s"] + max(m["motion_s_per_expander"]))


def test_resolve_fleet_shapes():
    d = TM.DeviceConfig()
    assert TM.resolve_fleet(None, 3) == [d] * 3
    assert TM.resolve_fleet(d, 2) == [d, d]
    g = TM.DEVICE_PROFILES["gen4"]
    assert TM.resolve_fleet([d, g], 4) == [d, g, d, g]
    with pytest.raises(ValueError):
        TM.resolve_fleet([d, g, d], 2)
    with pytest.raises(ValueError):
        TM.resolve_fleet([], 2)
