"""bench/trace.py on a trace recorded here on the CPU, and its clock
alignment on a hand-made device trace."""
from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import pytest

from bench import trace


def test_merge_and_covered():
    m = trace.merge([(5, 7), (0, 2), (1, 3), (10, 12)])
    assert m == [[0, 3], [5, 7], [10, 12]]
    assert trace.covered(m, 2, 11) == 1 + 2 + 1
    assert trace.covered(m, 2, 11, [0, 5, 10]) == 4


@pytest.fixture(scope="module")
def recorded():
    d = tempfile.mkdtemp(prefix="bench-trace-test-")
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(d)
    for i in range(4):
        with jax.profiler.TraceAnnotation("bench.step", step=i):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.005)
    jax.profiler.stop_trace()
    try:
        yield trace.load(d)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_recorded_trace_reduces(recorded):
    steps = [s for s in recorded.spans if s[2] == "bench.step"]
    assert [s[3]["step"] for s in steps] == [0, 1, 2, 3]
    assert recorded.ops, "no device operation found"
    red = trace.reduce(recorded, steps)
    assert 0 < red.busy_s <= red.window_s
    assert 0.0 <= red.idle_share < 1.0
    # every step ran its matmul inside its own span
    assert all(b > 0 for b in red.span_busy_s)
    assert sum(red.span_busy_s) <= red.busy_s + 1e-9
    assert red.top_ops and red.top_ops[0][1] > 0
    # the sleeps between steps are device idle time spent waiting
    assert any(name == "bench.wait" for name, _ in red.idle_gaps)


@dataclasses.dataclass
class _Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Line:
    name: str
    events: list


@dataclasses.dataclass
class _Plane:
    name: str
    lines: list


@dataclasses.dataclass
class _Profile:
    planes: list


def test_device_clock_moves_runs_inside_their_host_spans():
    # the device's clock runs 1,000 ns behind the host's: its run of
    # [1100, 1300] is host [2100, 2300]; the host saw it complete at 2400
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Ev("jit_step(1)", 1100, 200,
                                  [("run_id", 7)])]),
        _Line("XLA Ops", [_Ev("%fusion.1 = f32[] fusion()", 1100, 100),
                          _Ev("%all-reduce.2 = f32[] all-reduce(%a)",
                              1200, 100)]),
    ])
    host = _Plane("/host:CPU", [
        _Line("python", [_Ev("bench.step", 2000, 500, [("step", 0)])]),
        _Line("worker", [_Ev("CompleteCallbacks", 2400, 10,
                             [("run_id", 7), ("device_ordinal", 0)])]),
    ])
    t = trace.from_profile(_Profile([dev, host]))
    assert t.shift_ns["/device:TPU:0"] == 1100
    red = trace.reduce(t, t.spans)
    assert red.span_busy_s == [pytest.approx(200e-9)]
    assert red.busy_s == pytest.approx(200e-9)
    assert red.window_s == pytest.approx(500e-9)
