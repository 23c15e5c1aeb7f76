"""The reduction from a profiler trace to busy time, idle gaps and
per-program device time."""

import glob
import json
import os

import pytest

import devtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

#: a hand-made trace: window 0..100 ns; ops overlap; one module per call
SYNTH = {
    "host": [["bench.traced", 0.0, 100.0], ["bench.window", 0.0, 120.0],
             ["bench.explore", 0.0, 60.0],
             ["bench.step", 70.0, 30.0]],
    "device": {"/device:TPU:0": {
        "XLA Ops": [["fusion.1", 10.0, 20.0], ["while.2", 25.0, 10.0],
                    ["fusion.1", 28.0, 2.0], ["copy.3", 90.0, 20.0]],
        "XLA Modules": [["jit__engine_impl(7)", 10.0, 25.0],
                        ["jit__round1_body(9)", 90.0, 20.0]]}},
}


def test_union_and_busy():
    win = devtrace.window(SYNTH)
    assert win == (0.0, 100.0)
    assert devtrace.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert devtrace.busy(SYNTH, "/device:TPU:0", win) == [(10.0, 35.0),
                                                         (90.0, 100.0)]
    assert devtrace.busy_seconds(SYNTH, win) == pytest.approx(35e-9)


def test_idle_gaps_named_by_innermost_span():
    gaps = devtrace.idle_gaps(SYNTH, devtrace.window(SYNTH))
    # 35..90 lies 25 ns in bench.explore and 20 ns in bench.step
    assert [g[0] for g in gaps] == ["bench.explore", "bench.explore"]
    assert [g[1] for g in gaps] == pytest.approx([55e-9, 10e-9])


def test_program_and_op_seconds():
    win = devtrace.window(SYNTH)
    progs = devtrace.program_seconds(SYNTH, win)
    assert progs == pytest.approx({"jit__engine_impl(7)": 25e-9,
                                   "jit__round1_body(9)": 10e-9})
    assert devtrace.seconds_of(progs, ["jit__engine_impl"]) == pytest.approx(25e-9)
    top = devtrace.top_programs(SYNTH, win)
    assert [n for n, _ in top] == ["jit__engine_impl", "jit__round1_body"]
    assert [v for _, v in top] == pytest.approx([25e-9, 10e-9])


def test_no_chip_reads_nothing():
    ev = {"host": SYNTH["host"], "device": {}}
    win = devtrace.window(ev)
    assert devtrace.busy_seconds(ev, win) is None
    assert devtrace.idle_gaps(ev, win) == []
    assert devtrace.program_seconds(ev, win) == {}


def test_load_reads_the_benchmark_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones(128)
    f(x).block_until_ready()
    tracer = devtrace.Tracer(str(tmp_path), 0.0, True, lambda: {"stage2": 0})
    tracer.begin()
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.explore"):
            f(x).block_until_ready()
    tracer.poll()
    assert not tracer.active and tracer.counts == {"stage2": 0}
    ev = devtrace.load(str(tmp_path))
    names = {e[0] for e in ev["host"]}
    assert {"bench.traced", "bench.window", "bench.explore"} <= names
    win = devtrace.window(ev)
    assert win is not None and win[1] > win[0]


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(DATA, "*.json"))))
def test_recorded_tpu_trace(path):
    """A trace recorded on a TPU v5e (cut to a few explorations)."""
    with open(path) as f:
        rec = json.load(f)
    ev, want = rec["events"], rec["expect"]
    win = devtrace.window(ev)
    assert devtrace.tpu_planes(ev) == want["planes"]
    busy = devtrace.busy_seconds(ev, win)
    assert busy == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < busy <= (win[1] - win[0]) * 1e-9
    progs = devtrace.program_seconds(ev, win)
    for prefix, seconds in want["program_s"].items():
        assert devtrace.seconds_of(progs, [prefix]) == pytest.approx(seconds, rel=1e-9)
    gaps = devtrace.idle_gaps(ev, win)
    assert [g[0] for g in gaps] == want["gap_names"]
