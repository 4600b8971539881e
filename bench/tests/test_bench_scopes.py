"""bench/scopes.py: scope self times and idle labels on hand-made traces,
and the engine's serving.* spans on a trace recorded here on the CPU."""
from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import tempfile
import types

import jax
import numpy as np
import pytest

from bench import cells, scopes, trace
from bench.tests.test_bench_trace import _Ev, _Line, _Plane, _Profile

SERVING = ("serving.schedule", "serving.transfer", "serving.dispatch",
           "serving.sync", "serving.apply", "serving.harvest")


def _step(start, dur, i, kind):
    return _Ev("bench.step", start, dur, [("step", i), ("kind", kind)])


def test_self_times_count_nothing_twice():
    # a while op [0, 100] holding two scoped ops and, inside the second,
    # a nested one: self times add up to the while's length
    ops = [(0, 100, "while.1"), (10, 40, "fusion.1"), (50, 90, "fusion.2"),
           (60, 70, "copy.3")]
    assert scopes.self_times(ops) == [100 - 30 - 40, 30, 40 - 10, 10]
    assert sum(scopes.self_times(ops)) == 100


def test_scope_of_takes_the_innermost_scope():
    assert scopes.scope_of("jit(_unified)/layer.loop/while/body/closed_call/"
                           "attn/attn.gather/gather") == "attn.gather"
    assert scopes.scope_of("jit(_unified)/layer.loop/while/body/"
                           "dynamic_slice") == "layer.loop"
    assert scopes.scope_of("jit(_unified)/iota") is None


_PRE = "jit(_unified)/layer.loop/while/body/closed_call"
MIXED_PATHS = {"while.5": "jit(_unified)/layer.loop/while",
               "fusion.1": _PRE + "/moe.ffn/dot_general",
               "fusion.2": _PRE + "/attn/dot_general",
               "fusion.3": "jit(_unified)/lm_head/dot_general"}
DECODE_PATHS = {"fusion.1": _PRE + "/attn/add",
                "fusion.2": _PRE + "/moe.ffn/dot_general"}


def _device_trace():
    """One mixed step: a while op around moe.ffn and attn ops, then an
    lm_head op; a decode step with an attn and a moe.ffn op.  The host's
    serving spans sit in the device's idle time.  The op_name paths come
    from the programs' HLO text, as on a TPU."""
    mixed = [_Ev("while.5", 1000, 600), _Ev("fusion.1", 1100, 200),
             _Ev("fusion.2", 1350, 150), _Ev("fusion.3", 1650, 50)]
    decode = [_Ev("fusion.1", 3000, 100), _Ev("fusion.2", 3150, 50)]
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Ev("jit__unified(1)", 1000, 700,
                                  [("run_id", 1)]),
                              _Ev("jit__unified(2)", 3000, 200,
                                  [("run_id", 2)])]),
        _Line("XLA Ops", mixed + decode)])
    host = _Plane("/host:CPU", [
        _Line("python", [
            _step(500, 1500, 0, "mixed"),
            _Ev("serving.schedule", 600, 100, [("iter", 1)]),
            _Ev("serving.dispatch", 700, 300,
                [("iter", 1), ("kind", "mixed")]),
            _Ev("serving.sync", 1000, 800, [("iter", 1)]),
            _Ev("serving.harvest", 1800, 150, [("iter", 1)]),
            _Ev("serving.observe_routing", 1850, 50, [("iter", 1)]),
            _step(2500, 1000, 1, "decode"),
            _Ev("serving.dispatch", 2600, 400,
                [("iter", 2), ("kind", "decode")]),
        ]),
        _Line("worker", [
            _Ev("CompleteCallbacks", 1700, 10,
                [("run_id", 1), ("device_ordinal", 0)]),
            _Ev("CompleteCallbacks", 3200, 10,
                [("run_id", 2), ("device_ordinal", 0)])]),
    ])
    hlo = scopes.HloIndex()
    for ops, paths in ((mixed, MIXED_PATHS), (decode, DECODE_PATHS)):
        hlo.add("HloModule jit__unified, is_scheduled=true\n" + "\n".join(
            f'  %{o.name} = f32[] fusion(), '
            f'metadata={{op_name="{paths[o.name]}"}}' for o in ops))
    return _Profile([dev, host]), hlo


def test_scope_self_time_per_step():
    prof, hlo = _device_trace()
    sc = scopes.from_profile(prof, hlo)
    steps = [s for s in sc.trace.spans if s[2] == "bench.step"]
    per = scopes.step_scopes(sc, steps)
    ns = 1e-9
    # the mixed step: while self time 600 - 200 - 150 = 250 ns
    assert per[0] == pytest.approx({"layer.loop": 250 * ns,
                                    "moe.ffn": 200 * ns, "attn": 150 * ns,
                                    "lm_head": 50 * ns})
    # fusion.1 is an attn op in the decode program, a moe.ffn op in the
    # mixed one: each run is read against its own program
    assert per[1] == pytest.approx({"attn": 100 * ns, "moe.ffn": 50 * ns})
    red = trace.reduce(sc.trace, steps)
    assert sum(sum(p.values()) for p in per) == pytest.approx(red.busy_s)


def test_idle_gap_split_between_nested_spans():
    prof, _ = _device_trace()
    sc = scopes.from_profile(prof)
    steps = [s for s in sc.trace.spans if s[2] == "bench.step"]
    # the gap [1700, 3000] after the mixed step's last op lies in
    # serving.sync up to 1800, then in serving.harvest and, nested in it,
    # serving.observe_routing [1850, 1900], then in the step span to 2000,
    # between steps to 2500, in the decode step, and in its dispatch
    ns = 1e-9
    assert dict(scopes.idle_split(sc, steps)) == pytest.approx({
        "bench.step:mixed": (100 + 50) * ns,
        "serving.schedule:mixed": 100 * ns,
        "serving.dispatch:mixed": 300 * ns,
        "serving.sync:mixed": (50 + 100) * ns,
        "serving.harvest:mixed": 100 * ns,
        "serving.observe_routing:mixed": 50 * ns,
        "bench.harness": 500 * ns,
        "bench.step:decode": (100 + 50 + 300) * ns,
        "serving.dispatch:decode": 400 * ns})
    red = trace.reduce(sc.trace, steps)
    assert red.window_s - red.busy_s == pytest.approx(2200 * ns)


def test_step_host_time_leaves_out_the_sync():
    prof, _ = _device_trace()
    sc = scopes.from_profile(prof)
    steps = [s for s in sc.trace.spans if s[2] == "bench.step"]
    host = scopes.step_host_s(sc, steps)
    # schedule 100 + dispatch 300 + harvest 150 (observe_routing within)
    assert host == pytest.approx([550e-9, 400e-9])


# ---------------------------------------------------------------------------
# a tiny paged unified engine under the profiler
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded_engine():
    from repro.configs.base import get_config
    from repro.serving.engine import EngineConfig, ServingEngine
    from jax.profiler import ProfileData
    cfg = get_config("qwen3_moe_30b_a3b").reduced().replace(
        capacity_factor=8.0)
    eng = ServingEngine(cfg, EngineConfig(
        max_batch=2, max_cache=32, async_steps=False, chunk_len=4,
        paged=True, page_size=4, track_experts=True),
        rng=jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, 100, 9), max_new_tokens=6)
    for _ in range(2):                  # compile both block widths
        eng.step()
    eng.submit(rng.integers(0, 100, 6), max_new_tokens=3)
    d = tempfile.mkdtemp(prefix="bench-scopes-test-")
    kinds = []
    jax.profiler.start_trace(d)
    for i in range(4):
        p0 = eng.stats["prefill_tokens"]
        with jax.profiler.TraceAnnotation("bench.step", step=i):
            eng.step()
        kinds.append("mixed" if eng.stats["prefill_tokens"] > p0
                     else "decode")
    jax.profiler.stop_trace()
    try:
        f = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        yield ProfileData.from_file(f[0]), kinds
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_engine_spans_nest_in_order_inside_each_step(recorded_engine):
    pd, kinds = recorded_engine
    sc = scopes.from_profile(pd)
    steps = [s for s in sc.trace.spans if s[2] == "bench.step"]
    assert len(steps) == 4
    for (s0, s1, _, st), kind in zip(steps, kinds):
        inner = [sp for sp in sc.serving if s0 <= sp[0] and sp[1] <= s1]
        top = [sp[2] for sp in inner
               if not any(o is not sp and o[0] <= sp[0] and sp[1] <= o[1]
                          for o in inner)]
        assert top == list(SERVING), top
        assert all("iter" in sp[3] for sp in inner)
        it = {sp[3]["iter"] for sp in inner}
        assert len(it) == 1
        disp = next(sp for sp in inner if sp[2] == "serving.dispatch")
        assert disp[3]["kind"] == ("decode" if kind == "decode"
                                   else disp[3]["kind"])
        assert disp[3]["kind"] in ("decode", "mixed", "prefill")
        harvest = next(sp for sp in inner if sp[2] == "serving.harvest")
        nested = [sp[2] for sp in inner
                  if harvest[0] <= sp[0] and sp[1] <= harvest[1]
                  and sp is not harvest]
        assert nested == ["serving.fetch", "serving.observe_routing"]
    assert not any(sp[2].startswith("bench.") for sp in sc.serving)


def _without_serving(pd) -> _Profile:
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = [_Ev(ev.name, ev.start_ns, ev.duration_ns,
                       list(trace._stats(ev).items()))
                   for ev in line.events
                   if not ev.name.startswith(scopes.SPAN_PREFIX)]
            lines.append(_Line(line.name, evs))
        planes.append(_Plane(plane.name, lines))
    return _Profile(planes)


def test_reduce_reads_the_same_with_and_without_engine_spans(
        recorded_engine):
    pd, _ = recorded_engine
    with_spans = trace.from_profile(pd)
    without = trace.from_profile(_without_serving(pd))
    assert with_spans.spans == without.spans
    steps = [s for s in with_spans.spans if s[2] == "bench.step"]
    assert dataclasses.asdict(trace.reduce(with_spans, steps)) == \
        dataclasses.asdict(trace.reduce(without, steps))


def test_recorded_idle_is_labelled_by_engine_spans(recorded_engine):
    pd, kinds = recorded_engine
    sc = scopes.from_profile(pd)
    steps = [(s, e, n, dict(st, kind=k)) for (s, e, n, st), k in zip(
        [x for x in sc.trace.spans if x[2] == "bench.step"], kinds)]
    idle = scopes.idle_split(sc, steps, top=100)
    red = trace.reduce(sc.trace, steps)
    assert sum(v for _, v in idle) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)
    assert any(k.startswith("serving.") for k, _ in idle)


@pytest.mark.parametrize("name", ["step_host_ms.chat",
                                  "step_host_ms.reasoning"])
def test_step_host_reader(name):
    read = cells.metric_reader(name)
    ctx = types.SimpleNamespace(stats={"dispatches": 4,
                                       "host_ns": 10_000_000})
    assert read(ctx) == pytest.approx(2.5)
    # an engine without the serving.* spans reports nothing, and no error
    assert read(types.SimpleNamespace(stats={"decode_steps": 3})) is None
