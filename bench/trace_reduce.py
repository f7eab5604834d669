"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

From the device planes (``/device:TPU:<k>``): the busy time (the union of
the "XLA Modules" intervals inside the traced window), each module's
device time, and each op's self time (the "XLA Ops" line nests a loop's
body inside the loop op, so self time is the op's duration less its
children's). From the host plane: the window (the harness's
``bench.window`` annotation), the harness's own spans, and the program's
Python frames that the profiler's Python tracer records.

Device work is attributed to a *phase* by what the host was doing when it
was launched: the k-th ``PJRT_LoadedExecutable_Execute`` launch on the host
is the k-th module execution on the device (by ``run_id``), and the phase
is the innermost declared frame or span enclosing that launch. Module
names alone do not do this: a ``jax.jit`` of a ``functools.partial`` is
named ``jit__unknown(<hash>)``. Idle gaps (device not busy, inside the
window) are labelled by the innermost declared frame or span at their
midpoint.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
LAUNCH = "PJRT_LoadedExecutable_Execute"

Event = Tuple[str, float, float]      # (name, start_ns, duration_ns)


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    module_s: Dict[str, float] = field(default_factory=dict)
    phase_s: Dict[str, float] = field(default_factory=dict)
    phase_n: Dict[str, int] = field(default_factory=dict)
    op_s: Dict[str, float] = field(default_factory=dict)
    op_n: Dict[str, int] = field(default_factory=dict)
    gap_s: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def ops_matching(self, prefix: str) -> Tuple[float, int]:
        """Self seconds and count of ops whose short name starts with
        ``prefix`` (any phase)."""
        s = n = 0
        for k, v in self.op_s.items():
            if k.split(":", 1)[1].startswith(prefix):
                s += v
                n += self.op_n[k]
        return s, n

    def modules_matching(self, part: str) -> float:
        return sum(v for k, v in self.module_s.items() if part in k)

    def top_ops(self, n: int) -> List[list]:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in top]

    def top_gaps(self, n: int) -> List[list]:
        top = sorted(self.gap_s.items(), key=lambda kv: -kv[1])[:n]
        return [[f"idle under {k}", v] for k, v in top]


def frame_key(name: str) -> str:
    """'$engine.py:512 _start_fresh' -> 'engine.py:_start_fresh'; other
    names (annotations) are returned as they are."""
    if name.startswith("$") and " " in name:
        loc, func = name[1:].split(" ", 1)
        return f"{loc.split(':')[0]}:{func}"
    return name


def _innermost(events: List[Event], times: Sequence[float]) -> List[str]:
    """For each time, the name of the innermost event (properly nested
    intervals) containing it, or 'host' if none."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    order = sorted(range(len(times)), key=lambda i: times[i])
    out = ["host"] * len(times)
    stack: List[Event] = []
    j = 0
    for i in order:
        t = times[i]
        while j < len(evs) and evs[j][1] <= t:
            while stack and stack[-1][1] + stack[-1][2] < evs[j][1]:
                stack.pop()
            stack.append(evs[j])
            j += 1
        while stack and stack[-1][1] + stack[-1][2] < t:
            stack.pop()
        if stack:
            out[i] = stack[-1][0]
    return out


def _union(intervals: List[Tuple[float, float]], lo: float, hi: float
           ) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def _self_times(ops: List[Event]) -> List[Tuple[str, float, float]]:
    """(name, start, self duration) of properly nested op events."""
    evs = sorted(ops, key=lambda e: (e[1], -e[2]))
    self_t = [e[2] for e in evs]
    stack: List[int] = []
    for i, (_, s, d) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= d
        stack.append(i)
    return [(e[0], e[1], t) for e, t in zip(evs, self_t)]


def reduce_events(modules: List[List[Tuple[str, float, float, int]]],
                  ops: List[List[Event]], python: List[Event],
                  launches: List[float], labels: Dict[str, str]) -> Reduced:
    """The reduction on plain event lists (one list of modules and of ops
    per chip; ``modules`` entries are (name, start_ns, dur_ns, run_id)).
    ``labels`` maps a frame key or span name to its phase label."""
    win = [e for e in python if e[0] == WINDOW_SPAN]
    if not win:
        raise ValueError("trace has no bench.window span")
    lo, hi = win[0][1], win[0][1] + win[0][2]
    marked = [(labels[frame_key(n)], s, d) for n, s, d in python
              if frame_key(n) in labels]
    red = Reduced(window_s=(hi - lo) / 1e9, busy_s=0.0)
    n_chips = len(modules)

    chip0 = sorted(modules[0], key=lambda m: m[3])
    starts = sorted(launches)
    k = min(len(chip0), len(starts))        # both ends align (quiescent)
    phase_of = {}
    names = _innermost(marked, starts[len(starts) - k:])
    for m, ph in zip(chip0[len(chip0) - k:], names):
        phase_of[m[3]] = ph

    phase_s: Dict[str, float] = defaultdict(float)
    phase_n: Dict[str, int] = defaultdict(int)
    module_s: Dict[str, float] = defaultdict(float)
    op_s: Dict[str, float] = defaultdict(float)
    op_n: Dict[str, int] = defaultdict(int)
    busy = 0.0
    gaps: List[Tuple[float, float]] = []
    for chip in range(n_chips):
        mods = [m for m in modules[chip] if lo <= m[1] and m[1] + m[2] <= hi]
        spans = _union([(m[1], m[1] + m[2]) for m in mods], lo, hi)
        busy += sum(e - s for s, e in spans) / 1e9
        if chip == 0:
            prev = lo
            for s, e in spans:
                if s > prev:
                    gaps.append((prev, s))
                prev = e
            if hi > prev:
                gaps.append((prev, hi))
        for name, s, d, run in mods:
            base = name.split("(")[0]
            module_s[base] += d / 1e9
            if chip == 0:
                ph = phase_of.get(run, "host")
                phase_s[ph] += d / 1e9
                phase_n[ph] += 1
        mstarts = [m[1] for m in sorted(mods, key=lambda m: m[1])]
        msorted = sorted(mods, key=lambda m: m[1])
        for name, s, t in _self_times(
                [o for o in ops[chip] if lo <= o[1] <= hi]):
            i = bisect.bisect_right(mstarts, s) - 1
            ph = phase_of.get(msorted[i][3], "host") if i >= 0 else "host"
            key = f"{ph}:{name.split(' = ')[0]}"
            op_s[key] += t / 1e9
            op_n[key] += 1
    red.busy_s = busy / n_chips
    red.module_s, red.phase_s, red.phase_n = (dict(module_s), dict(phase_s),
                                              dict(phase_n))
    red.op_s, red.op_n = dict(op_s), dict(op_n)
    mids = [(s + e) / 2 for s, e in gaps]
    gap_s: Dict[str, float] = defaultdict(float)
    for (s, e), ph in zip(gaps, _innermost(marked, mids)):
        gap_s[ph] += (e - s) / 1e9
    red.gap_s = dict(gap_s)
    return red


def extract(pd, n_chips: int):
    """Event lists out of a ``jax.profiler.ProfileData``."""
    modules: List[list] = [[] for _ in range(n_chips)]
    ops: List[list] = [[] for _ in range(n_chips)]
    python: List[Event] = []
    launches: List[float] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1])
            if chip >= n_chips:
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        run = dict(e.stats).get("run_id", -1)
                        modules[chip].append((e.name, e.start_ns,
                                              e.duration_ns, int(run)))
                elif line.name == "XLA Ops":
                    ops[chip] += [(e.name, e.start_ns, e.duration_ns)
                                  for e in line.events]
        elif plane.name == "/host:CPU":
            # the main thread's line is named after the process ("python",
            # "python3", ...); annotations, Python frames and launches are
            # taken from every host line by their names
            for line in plane.lines:
                for e in line.events:
                    if e.name == LAUNCH:
                        launches.append(e.start_ns)
                    else:
                        python.append((e.name, e.start_ns, e.duration_ns))
    return modules, ops, python, launches


def reduce_trace(trace_dir: str, labels: Dict[str, str], n_chips: int = 1
                 ) -> Reduced:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    return reduce_events(*extract(pd, n_chips), labels)
