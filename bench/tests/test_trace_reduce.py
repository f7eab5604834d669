"""The trace reduction on hand-built events, and on a recorded CPU
profile."""
import jax
import jax.numpy as jnp
import pytest

import trace_reduce as TR

MS = 1_000_000      # ns


def _events():
    # host: window 0-100 ms; step span 10-60 containing a prefill frame
    # 20-30 and launches at 21 (prefill) and 40 (decode); launch at 70
    # under no declared frame
    python = [
        ("bench.window", 0 * MS, 100 * MS),
        ("serve.step", 10 * MS, 50 * MS),
        ("$engine.py:600 step", 11 * MS, 48 * MS),
        ("$engine.py:512 _start_fresh", 20 * MS, 10 * MS),
        ("$array_methods.py:1 set", 21 * MS, 1 * MS),
    ]
    launches = [21 * MS, 40 * MS, 70 * MS, 149 * MS]
    modules = [[("jit__unknown(1)", 22 * MS, 8 * MS, 5),
                ("jit__unknown(2)", 41 * MS, 15 * MS, 6),
                ("jit_scatter(3)", 72 * MS, 2 * MS, 7),
                ("outside(4)", 150 * MS, 5 * MS, 8)]]
    ops = [[("%while.1 = f32[] while(...)", 41 * MS, 15 * MS),
            ("%fusion.2 = f32[] fusion(...)", 42 * MS, 4 * MS),
            ("%fusion.2 = f32[] fusion(...)", 50 * MS, 4 * MS),
            ("%qpack_encode_2d = u32[] custom-call()", 22 * MS, 3 * MS)]]
    labels = {"serve.step": "serve.step",
              "engine.py:step": "serve.decode",
              "engine.py:_start_fresh": "serve.prefill"}
    return modules, ops, python, launches, labels


def test_busy_idle_modules_phases_by_hand():
    red = TR.reduce_events(*_events())
    assert red.window_s == pytest.approx(0.100)
    # busy: 8 + 15 + 2 ms inside the window (the module at 150 ms is out)
    assert red.busy_s == pytest.approx(0.025)
    assert red.idle_share == pytest.approx(0.75)
    assert red.module_s == pytest.approx(
        {"jit__unknown": 0.023, "jit_scatter": 0.002})
    assert red.phase_s == pytest.approx(
        {"serve.prefill": 0.008, "serve.decode": 0.015, "host": 0.002})
    assert red.phase_n == {"serve.prefill": 1, "serve.decode": 1, "host": 1}
    # while self time = 15 - 4 - 4
    assert red.op_s["serve.decode:%while.1"] == pytest.approx(0.007)
    assert red.op_s["serve.decode:%fusion.2"] == pytest.approx(0.008)
    assert red.ops_matching("%qpack_encode_2d") == pytest.approx((0.003, 1))
    # gaps: 0-22 (mid 11: step frame), 30-41 (mid 35.5: step frame),
    # 56-72 (mid 64: outside the step span), 74-100 (host)
    assert red.gap_s == pytest.approx({"serve.decode": 0.022 + 0.011,
                                       "host": 0.016 + 0.026})
    assert red.top_gaps(1) == [["idle under host", pytest.approx(0.042)]]


def test_no_window_span_is_an_error():
    modules, ops, python, launches, labels = _events()
    with pytest.raises(ValueError):
        TR.reduce_events(modules, ops, python[1:], launches, labels)


def test_frame_key():
    assert TR.frame_key("$engine.py:512 _start_fresh") == \
        "engine.py:_start_fresh"
    assert TR.frame_key("serve.step") == "serve.step"


def test_recorded_cpu_profile(tmp_path):
    """A real profile: the window span and the host frames are found; a
    CPU run has no TPU plane, so nothing is busy."""
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(TR.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("replay.chunk"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    red = TR.reduce_trace(str(tmp_path), {"replay.chunk": "replay.chunk"})
    assert red.window_s > 0
    assert red.busy_s == 0
    assert set(red.gap_s) <= {"replay.chunk", "host"}


def test_extract_reads_every_host_line():
    """The main thread's line is named after the process (``python3`` when
    run as ``python3 bench/run.py``): spans and launches are found on any
    host line."""
    from types import SimpleNamespace as NS

    def ev(name, start, dur, **stats):
        return NS(name=name, start_ns=start, duration_ns=dur,
                  stats=list(stats.items()))

    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=[ev(TR.WINDOW_SPAN, 0, 100 * MS),
                                   ev("replay.chunk", 1 * MS, 50 * MS),
                                   ev(TR.LAUNCH, 2 * MS, 1 * MS)]),
        NS(name="tf_pjrt_worker", events=[ev(TR.LAUNCH, 60 * MS, 1 * MS)])])
    tpu = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_f(1)", 3 * MS, 10 * MS,
                                          run_id=1),
                                       ev("jit_f(1)", 61 * MS, 10 * MS,
                                          run_id=2)]),
        NS(name="XLA Ops", events=[])])
    modules, ops, python, launches = TR.extract(NS(planes=[host, tpu]), 1)
    assert sorted(launches) == [2 * MS, 60 * MS]
    assert {e[0] for e in python} == {TR.WINDOW_SPAN, "replay.chunk"}
    red = TR.reduce_events(modules, ops, python, launches,
                           {"replay.chunk": "replay.chunk"})
    assert red.busy_s == pytest.approx(0.020)
    assert red.phase_s == pytest.approx({"replay.chunk": 0.010,
                                         "host": 0.010})
