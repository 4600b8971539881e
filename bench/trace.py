"""Reduce a profiler trace to the numbers the per-layer metrics read:
busy and idle time of each device, device time inside each host span,
the operations that took most time and the longest idle gaps with what
the host was doing in them.

Device operations are the events of a ``/device:*`` plane's ``XLA Ops``
line; on the CPU backend, where operations run on host threads, the events
that carry an ``hlo_op`` stat.  Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` events, whose names start with
``bench.``; their keyword arguments come back as the span's stats.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os

SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    ops: dict          # device -> [(start_ns, end_ns, op name)]
    spans: list        # [(start_ns, end_ns, name, stats dict)], sorted
    shift_ns: dict     # device -> ns added to its clock to reach the host's


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def _op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def from_profile(pd) -> Trace:
    """Build a Trace from ``jax.profiler.ProfileData``.

    A device's clock is not the host's.  Each program run on the device
    ends before the host's ``CompleteCallbacks`` event for the same run id
    starts, so the device clock is moved by the largest shift that keeps
    every run's end at or before its completion on the host: a run then
    lies inside the host span that dispatched and awaited it."""
    ops = collections.defaultdict(list)
    runs = collections.defaultdict(list)       # device -> [(run_id, end)]
    done = {}                                  # (ordinal, run_id) -> host ns
    spans = []
    for plane in pd.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                start = int(ev.start_ns)
                end = start + int(ev.duration_ns)
                if on_device:
                    if line.name == "XLA Ops":
                        ops[plane.name].append(
                            (start, end, _op_name(ev.name)))
                    elif line.name == "XLA Modules":
                        st = _stats(ev)
                        if "run_id" in st:
                            runs[plane.name].append((st["run_id"], end))
                    continue
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((start, end, ev.name, _stats(ev)))
                    continue
                if ev.name == "CompleteCallbacks":
                    st = _stats(ev)
                    if "run_id" in st:
                        key = (st.get("device_ordinal", 0), st["run_id"])
                        done[key] = min(done.get(key, start), start)
                    continue
                st = _stats(ev)
                if "hlo_op" in st:              # CPU backend: no device plane
                    ops[f"/cpu:{st.get('device_ordinal', 0)}"].append(
                        (start, end, ev.name))
    shift = {}
    for dev in ops:
        ordinal = int(dev.rsplit(":", 1)[-1]) if dev[-1].isdigit() else 0
        cands = [done[(ordinal, rid)] - end for rid, end in runs.get(dev, ())
                 if (ordinal, rid) in done]
        shift[dev] = min(cands) if cands else 0
    out = {}
    for dev, v in ops.items():
        d = shift[dev]
        out[dev] = sorted((s + d, e + d, n) for s, e, n in v)
    spans.sort(key=lambda s: s[0])
    return Trace(out, spans, shift)


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(files[-1]))


def merge(intervals) -> list:
    """Union of (start, end, ...) intervals as sorted disjoint [start, end]."""
    out = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(merged, lo: int, hi: int, starts=None) -> int:
    """Length of ``merged`` (disjoint, sorted) inside [lo, hi].
    ``starts``: the intervals' starts, to skip to the first that counts."""
    i = 0
    if starts is not None:
        i = max(0, bisect.bisect_right(starts, lo) - 1)
    total = 0
    for s, e in merged[i:]:
        if s >= hi:
            break
        total += max(0, min(e, hi) - max(s, lo))
    return total


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                  # mean over devices
    span_busy_s: list              # per host span: device busy inside it
    top_ops: list                  # [(name, seconds)], mean over devices
    idle_gaps: list                # [(host activity, seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(t: Trace, steps: list, top: int = 10) -> Reduced:
    """``steps``: the host spans that cover the traced window, in order.
    The window runs from the first one's start to the last one's end."""
    if not t.ops:
        raise ValueError("the trace holds no device operation")
    if not steps:
        raise ValueError("the trace holds no benchmark span")
    lo, hi = steps[0][0], max(s[1] for s in steps)
    n = len(t.ops)
    busy = 0
    span_busy = [0.0] * len(steps)
    per_op = collections.Counter()
    gaps = collections.Counter()
    for dev_ops in t.ops.values():
        inside = [o for o in dev_ops if o[1] > lo and o[0] < hi]
        m = merge(inside)
        busy += covered(m, lo, hi)
        m_starts = [iv[0] for iv in m]
        for i, (s, e, *_) in enumerate(steps):
            span_busy[i] += covered(m, s, e, m_starts) / n
        for s, e, name in inside:
            per_op[name] += (min(e, hi) - max(s, lo)) / n
        edges = [lo] + [x for iv in m for x in iv] + [hi]
        span_starts = [sp[0] for sp in t.spans]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[_activity(t.spans, span_starts, (a + b) // 2)] += \
                    (b - a) / n
    ns = 1e-9
    return Reduced(
        window_s=(hi - lo) * ns,
        busy_s=busy / n * ns,
        span_busy_s=[x * ns for x in span_busy],
        top_ops=[(k, v * ns) for k, v in per_op.most_common(top)],
        idle_gaps=[(k, v * ns) for k, v in gaps.most_common(top)],
    )


def _activity(spans, starts, at: int) -> str:
    """What the host was doing at time ``at``: the benchmark span covering
    it (they do not nest), else the harness between spans."""
    j = bisect.bisect_right(starts, at) - 1
    if j < 0 or spans[j][1] < at:
        return "bench.harness"
    _, _, name, st = spans[j]
    kind = st.get("kind")
    return f"{name}:{kind}" if kind else name
