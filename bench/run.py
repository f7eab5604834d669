#!/usr/bin/env python3
"""Run one benchmark cell once, in one process that holds the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``:

  * the cell's ``config`` -> ``bench/configs/<config>.json``, whose ``form``
    names its form, ``bench/forms/<form>.py``;
  * the cell's ``traffic`` -> ``bench/traffic/<traffic>.json``;
  * each per-layer metric -> its reader ``bench/metrics/<metric>.py``.

With ``--trace 0`` the result line carries the cell's end-to-end metrics,
taken on the host clock with the profiler off; with ``--trace 1`` it carries
the per-layer metrics, read from a profiler trace of the window and from
the program's counters. Every run checks what the timed path produced
against a plain reference once the window has closed, prints each compared
number beside its limit as the last lines of stderr, and prints one JSON
object as the last line of stdout. A run exits non-zero and prints no
result when no TPU (or too few) is found, or when the program is missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
# the TPU runtime logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""
    name: str
    chips: int
    config: dict
    mix: dict
    seed: int
    end_to_end: List[dict]
    per_layer: List[dict]


@dataclass
class Window:
    """What a form's measured window produced. ``e2e`` holds host-clock
    end-to-end values by metric name; ``facts`` holds counts the per-layer
    readers use (counter deltas, steps, accesses, shapes)."""
    e2e: Dict[str, float]
    attempted: int
    failed: int
    facts: Dict[str, Any] = field(default_factory=dict)
    compiles: int = 0


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, seed: int, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    config = json.loads((BENCH / "configs" / f"{w['config']}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())

    def applies(m: dict) -> bool:
        return name in m["workloads"] if "workloads" in m else True

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name, int(w["chips"]), config, mix, int(seed), e2e, per_layer)


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts lowerings and backend compiles (``jax.monitoring`` events);
    a persistent-cache hit still lowers, so this counts every program that
    was not already in this process's caches."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.n = 0
        self.seconds = 0.0
        events = {dispatch.JAXPR_TO_MLIR_MODULE_EVENT,
                  dispatch.BACKEND_COMPILE_EVENT}

        def listen(event, duration, **_):
            if event in events:
                self.n += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


@contextlib.contextmanager
def traced(trace_dir: Optional[str]):
    """Profile the enclosed window into ``trace_dir`` (None: no profiling)
    under the ``bench.window`` span the trace reduction reads. The body
    must wait for its device work before it ends."""
    if trace_dir is None:
        yield
        return
    import jax
    from trace_reduce import WINDOW_SPAN
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def setup_jax(require_chip: bool, chips: int):
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(f"bench: needs {chips} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def device_info(devs, chips: int) -> dict:
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table["devices"][kind]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def check_program(root: pathlib.Path = ROOT) -> None:
    if not (root / "src" / "repro").is_dir():
        raise SystemExit("bench: the program (src/repro) is not in this "
                         "checkout")
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))


def run_cell(cell: Cell, seconds: float, trace: bool,
             require_chip: bool = True, control: bool = False,
             form_hook=None) -> dict:
    """Set up, measure, check. Returns the result object (the last stdout
    line). ``require_chip=False`` and ``form_hook(form)`` are for tests:
    they skip the look for a chip and let a test break the timed path."""
    devs = setup_jax(require_chip, cell.chips)
    import jax
    counter = CompileCounter()
    form_mod = load_module(BENCH / "forms" / f"{cell.config['form']}.py")
    form = form_mod.Form(cell, devs[:cell.chips])
    if form_hook is not None:
        form_hook(form)
    form.setup()
    setup_s = time.perf_counter() - T_START if require_chip else \
        time.perf_counter() - form.t_created
    jax.effects_barrier()

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    c0 = counter.n
    win = form.window(seconds, trace_dir)
    win.compiles = counter.n - c0
    dev = device_info(devs, cell.chips)
    form.release()
    t_check = time.perf_counter()
    checks = form.check(control=control)
    check_s = time.perf_counter() - t_check
    checks["compiles_in_window"] = (win.compiles, 0)

    if trace:
        from trace_reduce import reduce_trace
        red = reduce_trace(trace_dir, form.LABELS, n_chips=cell.chips)
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        peaks = load_peaks(dev["kind"]) if require_chip else \
            {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            v = reader.read(win.facts, red, peaks)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        breakdown = {"device_ops": red.top_ops(10),
                     "idle_gaps": red.top_gaps(10)}
        phases = {k: [red.phase_s[k], red.phase_n[k]] for k in red.phase_s}
    else:
        metrics = {m["name"]: {"value": float(win.e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": float(setup_s), "unit": "s"}
        breakdown = phases = None

    correct = all(v <= lim for v, lim in checks.values())
    out = {"correct": bool(correct), "attempted": int(win.attempted),
           "failed": int(win.failed), "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["info"] = {k: v for k, v in win.facts.items()
                   if isinstance(v, (int, float, str))}
    out["info"].update(getattr(form, "readings", lambda: {})())
    out["info"]["check_s"] = check_s       # the reference's time, after the window
    if phases is not None:
        # device seconds and programs launched under each host phase
        out["info"]["phases"] = phases
    # a number compared that could not be read (no sample) is null, and
    # the run is not correct
    out["checks"] = {k: {"value": float(v) if math.isfinite(v) else None,
                         "limit": float(lim)}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="check the lower-precision control in the "
                         "program's place (not part of a benchmark run)")
    args = ap.parse_args(argv)
    check_program()
    cell = load_cell(args.workload, args.seed)
    out = run_cell(cell, args.seconds, bool(args.trace), control=args.control)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
