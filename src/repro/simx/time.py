"""Array-native delivered-time accounting (DESIGN.md §12).

The paper's headline numbers (Fig. 9/12/14/15/16) are *delivered time*, not
byte counts, so the timing model is a first-class layer rather than a
post-hoc script:

  * ``DeviceConfig`` — one expander's Table-1 parameters as a frozen
    (hashable) dataclass: usable as a ``jax.jit`` static argument.
  * ``DeviceLanes`` — a *stacked* fleet of expanders: every field an array
    with a leading expander axis. A NamedTuple, hence a pytree — pass it as
    a traced argument into jitted/vmapped code (mixed-generation fleets:
    different ``ch_bw``/``cxl_lat``/``decomp_cycles`` per expander).
  * ``exec_time_vec`` — the vectorized model: operates on counter *arrays*
    in ``engine.state.COUNTER_NAMES`` order (the ``Pool.counters`` vector),
    broadcasting over any leading axes, under ``jnp`` (inside jit/vmap) or
    ``np`` (host-side float64). The string-keyed-dict API is
    ``exec_time_dict`` — a thin face over the same core, bitwise-identical
    to the old scalar model (tests/test_time_model.py pins this).

Model (documented approximation, not cycle-accurate): execution time is the
max of four saturable resources, plus a latency term moderated by
memory-level parallelism —

  t_mem    = internal 64B accesses x 64 / (channels x DDR bw)
  t_cxl    = host accesses x 64 / CXL bw                (PCIe5 x8 = 32 GB/s)
  t_engine = compressions x 256cyc + decompressions x 64cyc at 2 GHz
             (4B/clk compress, 16B/clk decompress for 1KB blocks, §5)
  t_lat    = host accesses x avg service latency / MLP

The serving-side model (``serve_motion_time``/``serve_modeled_time``)
converts the engine's preempt/resume byte and host-sync counters into
seconds: parked payloads cross the CXL link AND the internal channels
(pipelined → max), demotion pays the compression engine, and every host
sync costs one CXL round trip.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, List, Mapping, NamedTuple, Sequence, Union

import jax.numpy as jnp
import numpy as np

from repro.core.engine import state as S


@dataclass(frozen=True)
class DeviceConfig:
    """One expander's timing parameters (Table 1). Frozen → hashable →
    usable as a jit static; stack several into ``DeviceLanes`` for a
    traced per-expander fleet."""
    channels: int = 2
    ch_bw: float = 44.8e9          # DDR5-5600 bytes/s per channel
    cxl_bw: float = 32e9           # PCIe Gen5 x8
    cxl_lat: float = 70e-9         # round-trip (Table 1)
    dram_lat: float = 55e-9        # tCL+tRCD-ish
    clock: float = 2.0e9
    comp_cycles: int = 256         # per 1KB block (4B/clk)
    decomp_cycles: int = 64        # per 1KB block (16B/clk)
    mlp: float = 4.0               # outstanding-request parallelism
    block_scale: float = 1.0       # 4KB-block schemes: 4x engine latency


def ideal_bandwidth(dev: DeviceConfig) -> DeviceConfig:
    """Fig. 1's 'unlimited internal bandwidth but same latency' variant."""
    return dataclasses.replace(dev, ch_bw=1e15)


# Named generation profiles for mixed fleets (launch/fabric.py
# --device-profile, test_fabric.py's mixed-fleet delivered-time test).
# "gen4" is a previous-generation expander (PCIe4 x8 link, DDR4-ish
# channels, slower engine clock); "far" sits behind a CXL switch (latency
# only).
DEVICE_PROFILES: Dict[str, DeviceConfig] = {
    "default": DeviceConfig(),
    "gen4": DeviceConfig(ch_bw=25.6e9, cxl_bw=16e9, cxl_lat=110e-9,
                         dram_lat=60e-9, clock=1.5e9),
    "far": DeviceConfig(cxl_lat=250e-9),
    "slow_engine": DeviceConfig(clock=1.0e9, comp_cycles=512,
                                decomp_cycles=128),
}

class DeviceLanes(NamedTuple):
    """A stacked expander fleet: ``DeviceConfig`` field-for-field, each a
    float array with a leading expander axis. NamedTuple → pytree → passes
    through jit/vmap as a traced argument (``jax.vmap`` slices one
    expander's scalars per lane). Field names MUST mirror ``DeviceConfig``
    (``stack_devices`` asserts; test_time_model pins the drift guard)."""
    channels: np.ndarray
    ch_bw: np.ndarray
    cxl_bw: np.ndarray
    cxl_lat: np.ndarray
    dram_lat: np.ndarray
    clock: np.ndarray
    comp_cycles: np.ndarray
    decomp_cycles: np.ndarray
    mlp: np.ndarray
    block_scale: np.ndarray


DeviceLike = Union[DeviceConfig, DeviceLanes]


def stack_devices(devs: Sequence[DeviceConfig], xp=jnp) -> DeviceLanes:
    """[DeviceConfig] * N → DeviceLanes with N-length field arrays. Built
    generically from ``dataclasses.fields`` so adding a DeviceConfig field
    without extending DeviceLanes is a loud error, never a silent drop."""
    names = [f.name for f in dataclasses.fields(DeviceConfig)]
    if set(names) != set(DeviceLanes._fields):
        raise TypeError(f"DeviceConfig fields {names} drifted from "
                        f"DeviceLanes fields {list(DeviceLanes._fields)}")
    dtype = jnp.float32 if xp is jnp else np.float64
    return DeviceLanes(**{n: xp.asarray([getattr(d, n) for d in devs],
                                        dtype=dtype) for n in names})


def resolve_fleet(devices, n_expanders: int) -> List[DeviceConfig]:
    """Normalize a fleet spec — None (all-default), one DeviceConfig
    (homogeneous), or a sequence (cycled to length N if shorter) — into a
    list of N DeviceConfigs."""
    if devices is None:
        devices = DeviceConfig()
    if isinstance(devices, DeviceConfig):
        return [devices] * n_expanders
    devices = list(devices)
    if not devices:
        raise ValueError("empty device fleet")
    if len(devices) < n_expanders:
        devices = [devices[i % len(devices)] for i in range(n_expanders)]
    if len(devices) != n_expanders:
        raise ValueError(f"{len(devices)} device configs for "
                         f"{n_expanders} expanders")
    return devices


# ---------------------------------------------------------------------------
# The model core. One implementation serves every caller: python scalars
# (``exec_time_dict``, float64), numpy arrays (host-side sweeps, float64), and
# jnp arrays inside jit/vmap (fabric replay, float32). Operation order is
# EXACTLY the legacy scalar model's, so the float64 paths are bitwise
# identical to the pre-refactor code.
# ---------------------------------------------------------------------------

def _exec_time_core(host, internal, promotions, demotions_dirty,
                    recompress_retry, zero_served, dev: DeviceLike, xp):
    t_mem = internal * 64 / (dev.channels * dev.ch_bw)
    t_cxl = host * 64 / dev.cxl_bw
    n_comp = (demotions_dirty + recompress_retry) * dev.block_scale * 4
    n_decomp = promotions * dev.block_scale          # per block
    t_engine = (n_comp * dev.comp_cycles + n_decomp * dev.decomp_cycles) \
        / dev.clock
    # average service latency per host access
    host1 = xp.maximum(host, 1)
    zero_frac = zero_served / host1
    accesses_per_host = internal / host1
    decomp_lat_frac = promotions / host1
    l_avg = dev.cxl_lat + (1 - zero_frac) * dev.dram_lat \
        + accesses_per_host * dev.dram_lat * 0.25 \
        + decomp_lat_frac * dev.decomp_cycles / dev.clock
    t_lat = host * l_avg / dev.mlp
    return xp.maximum(xp.maximum(t_mem, t_cxl),
                      xp.maximum(t_engine, t_lat))


def exec_time_vec(counters, dev: DeviceLike, xp=None):
    """Vectorized delivered time over counter *arrays*.

    ``counters``: ``[..., NUM_COUNTERS]`` in ``state.COUNTER_NAMES`` order
    (the ``Pool.counters`` vector, or a stacked/broadcast batch of them);
    ``dev``: a ``DeviceConfig`` (broadcast) or ``DeviceLanes`` whose field
    arrays broadcast against the leading axes. Returns seconds ``[...]``.
    Internal traffic is derived from the ten ``state.TRAFFIC_IDX``
    categories — the model and the counter layout cannot drift on key
    names. Runs under jit/vmap when given jnp inputs; on numpy inputs it
    computes in float64 and is bitwise-identical to the legacy scalar
    model (the parity contract)."""
    if xp is None:
        xp = np if isinstance(counters, np.ndarray) else jnp
    c = (np.asarray(counters, np.float64) if xp is np
         else counters.astype(jnp.float32))
    internal = S.traffic_vector(c).sum(axis=-1)
    host = c[..., S.C_HOST_RD] + c[..., S.C_HOST_WR]
    return _exec_time_core(host, internal, c[..., S.C_PROMOTIONS],
                           c[..., S.C_DEMO_DIRTY], c[..., S.C_RECOMP_RETRY],
                           c[..., S.C_ZERO_SERVED], dev, xp)


def counters_from_dict(traffic: Mapping[str, float]) -> np.ndarray:
    """String-keyed traffic dict → float64 ``[NUM_COUNTERS]`` vector in
    ``state.COUNTER_NAMES`` order (missing keys are zero)."""
    return np.asarray([traffic.get(k, 0) for k in S.COUNTER_NAMES],
                      np.float64)


def exec_time_dict(traffic: Mapping[str, float], dev: DeviceConfig) -> float:
    """The string-keyed dict API, a thin face over the vectorized core.

    Honors an explicit ``internal_accesses`` key (fig12's miracle variant
    passes a reduced total that is NOT the category sum); otherwise derives
    it from the ten traffic categories. Float64 throughout — bitwise equal
    to the pre-refactor scalar model."""
    host = traffic["host_reads"] + traffic["host_writes"]
    if "internal_accesses" in traffic:
        internal = traffic["internal_accesses"]
    else:
        internal = sum(traffic.get(k, 0) for k in S.TRAFFIC_NAMES)
    f = np.float64
    return float(_exec_time_core(
        f(host), f(internal), f(traffic.get("promotions", 0)),
        f(traffic.get("demotions_dirty", 0)),
        f(traffic.get("recompress_retry", 0)),
        f(traffic.get("zero_served", 0)), dev, np))


def uncompressed_counters(n_host) -> np.ndarray:
    """Baseline traffic of an uncompressed device serving ``n_host`` host
    reads: derived from ``state.COUNTER_NAMES`` (zeros except host reads
    and one internal access per host read), so the baseline and the model
    can never drift on key names. ``n_host`` may be a scalar or an array
    (leading axes broadcast into the counters batch)."""
    n = np.asarray(n_host, np.float64)
    vec = np.zeros(n.shape + (S.NUM_COUNTERS,), np.float64)
    vec[..., S.C_HOST_RD] = n
    vec[..., S.C_DATA_RD] = n          # internal: one 64B access per read
    return vec


def uncompressed_time(n_host, dev: DeviceLike):
    """Fig-9-style baseline: ``exec_time`` of the uncompressed traffic.
    Scalar in, float out; array in (or ``DeviceLanes``), array out."""
    t = exec_time_vec(uncompressed_counters(n_host), dev, xp=np)
    return float(t) if np.ndim(t) == 0 else t


def pipeline_delivered_time(replay_deltas, migration_deltas, dev: DeviceLike,
                            overlapped: bool = True):
    """Delivered seconds of the fabric's two-stage segment pipeline
    (DESIGN.md §13): per-segment counter DELTAS priced segment by segment,
    then summed per expander.

    ``replay_deltas``/``migration_deltas``: float/int ``[S, N_counters]``
    or ``[S, N, N_counters]`` in ``state.COUNTER_NAMES`` order — segment
    ``s``'s foreground replay delta and the migration-epoch delta the
    scheduler overlapped with it (zeros when no epoch was in flight).

    ``overlapped=True`` prices each segment as
    ``max(replay_s, migration_s)`` — the pipeline hides an epoch's
    migration behind the next segment's foreground replay (an optimistic
    full-overlap bound: real channels would contend). ``False`` prices the
    synchronous path, ``replay_s + migration_s`` — migration on the
    critical path. ``overlapped <= sync`` holds segmentwise by
    construction (max <= sum of non-negatives); tests/test_fabric.py
    asserts it on the same run's deltas. Note the per-segment max is NOT
    the cumulative ``exec_time_vec`` of summed counters — the pipeline model resolves
    the bottleneck resource per segment, the cumulative model once."""
    xp = np if isinstance(replay_deltas, np.ndarray) else jnp
    t_replay = exec_time_vec(replay_deltas, dev, xp=xp)
    t_mig = exec_time_vec(migration_deltas, dev, xp=xp)
    per_seg = xp.maximum(t_replay, t_mig) if overlapped \
        else t_replay + t_mig
    return per_seg.sum(axis=0)


# ---------------------------------------------------------------------------
# Serving-side model: preempt/resume byte + host-sync counters → seconds
# (serve/engine.py counters; DESIGN.md §12).
# ---------------------------------------------------------------------------

def serve_motion_time(preempt_bytes, resume_bytes, dev: DeviceLike, xp=np):
    """Seconds one expander spends moving park/resume payloads: bytes cross
    the CXL link and the internal channels (pipelined → max of the two),
    and every parked 1KB block pays the compression engine (resume installs
    codes without dequantizing — fused attention reads them in place, so
    promotions charge bandwidth only)."""
    moved = preempt_bytes + resume_bytes
    t_link = moved / dev.cxl_bw
    t_mem = moved / (dev.channels * dev.ch_bw)
    t_engine = (preempt_bytes / 1024.0) * dev.block_scale * dev.comp_cycles \
        / dev.clock
    return xp.maximum(xp.maximum(t_link, t_mem), t_engine)


def serve_modeled_time(counters: Mapping[str, int],
                       expander_stats: Mapping[str, np.ndarray],
                       devices: Sequence[DeviceConfig]) -> Dict[str, object]:
    """Modeled serving seconds from an engine's motion/sync counters.

    Expanders move their own parked payloads in parallel (bottleneck =
    max over lanes); host syncs are serialized round trips charged at the
    slowest lane's CXL latency. Returns per-expander motion seconds plus
    the totals (seconds per decode step is the figure of merit:
    serial-vs-batched and fabric-striped serving compare in seconds, not
    just tokens/sec)."""
    lanes = stack_devices(list(devices), xp=np)
    motion = serve_motion_time(
        np.asarray(expander_stats["preempt_bytes"], np.float64),
        np.asarray(expander_stats["resume_bytes"], np.float64), lanes, np)
    syncs = counters["step_syncs"] + counters["admit_syncs"]
    sync_s = float(syncs * np.max(lanes.cxl_lat))
    modeled_s = sync_s + float(np.max(motion))
    steps = max(int(counters["steps"]), 1)
    return {
        "sync_s": sync_s,
        "motion_s_per_expander": [float(t) for t in motion],
        "modeled_s": modeled_s,
        "modeled_s_per_step": modeled_s / steps,
    }
