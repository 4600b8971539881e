"""The serving engine's own spans and the step program's named scopes in a
profiler trace, read beside ``bench/trace.py``'s reduction (which they
leave as it is, on the same aligned clock).

- Spans: the engine's ``serving.*`` ``jax.profiler.TraceAnnotation``
  events (``ServingEngine._span``), nested inside the benchmark's
  ``bench.step`` spans.
- Scopes: each device operation's innermost ``jax.named_scope`` of
  ``SCOPES``, read from its ``op_name`` metadata path.  A TPU trace's
  operation events carry no such path, so each operation is looked up in
  the compiled HLO text of the step programs, keyed by module and
  operation name (``fusion.N`` repeats across the decode and mixed
  programs, so each run of a module is matched to the program whose
  operations it ran).
- A scope's device time is the self time of its operations: each
  operation's duration less that of the operations nested in it, so a
  layer loop's ``while`` counts nothing twice.  An operation belongs to the
  step span its start lies in, as ``bench/trace.py``'s ``span_busy_s``
  counts device time inside each step span.
- Each idle gap of the traced window is split over the innermost span
  covering each part of it: a ``serving.*`` span with the kind of the step
  around it (``serving.sync:decode``), else ``bench/trace.py``'s label.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

from bench import trace as trace_lib

SPAN_PREFIX = "serving."
SCOPES = ("embed", "attn", "attn.gather", "layer.loop", "layer.cache",
          "moe.route", "moe.dispatch", "moe.ffn", "moe.combine",
          "moe.collective", "lm_head", "sample")
_SCOPES = frozenset(SCOPES)
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


@dataclasses.dataclass
class Scoped:
    trace: trace_lib.Trace   # bench/trace.py's view of the same profile
    serving: list            # [(start_ns, end_ns, name, stats)], sorted
    ops: dict                # device -> [(start, end, name, scope | None)]


def scope_of(path: str) -> str | None:
    """The innermost component of an ``op_name`` path that is a scope."""
    for part in reversed(path.split("/")):
        if part in _SCOPES:
            return part
    return None


class HloIndex:
    """``op_name`` paths of the operations of compiled programs
    (``compiled.as_text()``), by module name."""

    def __init__(self, texts=()):
        self.programs = collections.defaultdict(list)   # module -> [{op: path}]
        for text in texts:
            self.add(text)

    def add(self, text: str) -> None:
        module, paths = None, {}
        for line in text.splitlines():
            if module is None:
                m = _MODULE.match(line)
                if m:
                    module = m.group(1)
                    continue
            m = _INSTR.match(line)
            if m:
                paths[m.group(1)] = m.group(2)
        self.programs[module or ""].append(paths)

    def paths_for(self, module: str, names) -> dict:
        """The program of ``module`` that holds most of ``names``, the
        smallest of those on a tie."""
        progs = self.programs.get(module)
        if not progs:
            return {}
        return max(progs, key=lambda p: (sum(n in p for n in names),
                                         -len(p)))


def from_profile(pd, hlo: HloIndex | None = None) -> Scoped:
    """``pd``: ``jax.profiler.ProfileData``.  Device clocks are moved as
    ``bench/trace.py`` moves them."""
    t = trace_lib.from_profile(pd)
    serving = []
    raw = collections.defaultdict(list)   # device -> [(s, e, name, run)]
    modules = collections.defaultdict(list)   # device -> [(s, e, module)]
    for plane in pd.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                start = int(ev.start_ns)
                end = start + int(ev.duration_ns)
                if on_device:
                    if line.name == "XLA Modules":
                        modules[plane.name].append(
                            (start, end, ev.name.split("(", 1)[0]))
                    elif line.name == "XLA Ops":
                        raw[plane.name].append(
                            (start, end, trace_lib._op_name(ev.name), None))
                    continue
                if ev.name.startswith(SPAN_PREFIX):
                    serving.append((start, end, ev.name, trace_lib._stats(ev)))
                    continue
                st = trace_lib._stats(ev)
                if "hlo_op" in st:              # CPU backend: no device plane
                    raw[f"/cpu:{st.get('device_ordinal', 0)}"].append(
                        (start, end, ev.name,
                         (st.get("hlo_module", ""), st.get("run_id"))))
    ops = {}
    for dev, evs in raw.items():
        shift = t.shift_ns.get(dev, 0)
        runs = _runs(evs, sorted(modules.get(dev, ())))
        out = []
        for (module, _), members in runs.items():
            paths = (hlo.paths_for(module, {evs[i][2] for i in members})
                     if hlo is not None else {})
            for i in members:
                s, e, name, _ = evs[i]
                path = paths.get(name)
                out.append((s + shift, e + shift, name,
                            scope_of(path) if path else None))
        ops[dev] = sorted(out)
    serving.sort(key=lambda s: s[0])
    return Scoped(t, serving, ops)


def _runs(evs, modules) -> dict:
    """Operation indices grouped by program run: the run stat where the
    events carry one (CPU), else the ``XLA Modules`` event around them."""
    runs = collections.defaultdict(list)
    starts = [m[0] for m in modules]
    for i, (s, _, _, run) in enumerate(evs):
        if run is None:
            j = bisect.bisect_right(starts, s) - 1
            run = ((modules[j][2], j) if j >= 0 and modules[j][1] >= s
                   else ("", None))
        runs[run].append(i)
    return runs


def load(trace_dir: str, hlo: HloIndex | None = None) -> Scoped:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(files[-1]), hlo)


def self_times(intervals) -> list:
    """Each interval's length less that of the intervals nested directly
    in it.  ``intervals``: (start, end, ...) sorted by start."""
    out = [e - s for s, e, *_ in intervals]
    stack = []                       # indices of the open enclosing ops
    for i, (s, e, *_) in enumerate(intervals):
        while stack and intervals[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= intervals[stack[-1]][1]:
            out[stack[-1]] -= e - s
        stack.append(i)
    return out


def step_scopes(sc: Scoped, steps: list) -> list:
    """Per step span (start, end, ...): {scope or None: device self
    seconds}, mean over devices."""
    starts = [s[0] for s in steps]
    out = [collections.Counter() for _ in steps]
    n = len(sc.ops) or 1
    for dev_ops in sc.ops.values():
        ordered = sorted(dev_ops, key=lambda o: (o[0], -o[1]))
        for (s, e, _, scope), own in zip(ordered, self_times(ordered)):
            j = bisect.bisect_right(starts, s) - 1
            if j >= 0 and s < steps[j][1]:
                out[j][scope] += own * 1e-9 / n
    return out


def step_host_s(sc: Scoped, steps: list, skip=("serving.sync",)) -> list:
    """Per step span: the self time of the ``serving.*`` spans inside it,
    those named in ``skip`` left out."""
    spans = sc.serving
    own = self_times(spans)
    starts = [s[0] for s in steps]
    out = [0.0] * len(steps)
    for (s, e, name, _), t in zip(spans, own):
        j = bisect.bisect_right(starts, s) - 1
        if j >= 0 and e <= steps[j][1] and name not in skip:
            out[j] += t * 1e-9
    return out


def idle_split(sc: Scoped, steps: list, top: int = 12) -> list:
    """The traced window's idle time, mean over devices, by the innermost
    span covering each part of each gap: [(label, seconds)], most first.
    ``steps``: the ``bench.step`` spans of the window, whose stats give the
    step's ``kind``.  Its total is ``bench/trace.py``'s idle time of the
    same window."""
    t = sc.trace
    lo, hi = steps[0][0], max(s[1] for s in steps)
    kinds = {s[0]: s[3].get("kind") for s in steps}
    bench = [(s, e, name, dict(st, kind=kinds[s]) if s in kinds else st)
             for s, e, name, st in t.spans]
    spans = sorted(bench + list(sc.serving), key=lambda s: s[0])
    n = len(t.ops)
    out = collections.Counter()
    for dev_ops in t.ops.values():
        m = trace_lib.merge(o for o in dev_ops if o[1] > lo and o[0] < hi)
        edges = [lo] + [max(lo, min(hi, x)) for iv in m for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for label, ns in _label_gaps(spans, gaps).items():
            out[label] += ns * 1e-9 / n
    return out.most_common(top)


def _label_gaps(spans, gaps) -> collections.Counter:
    """Split each gap (sorted, disjoint) at every span boundary and give
    each piece the label of the innermost span open over it."""
    cuts = sorted({x for s in spans for x in s[:2]})
    starts = [s[0] for s in spans]
    out = collections.Counter()
    for a, b in gaps:
        lo = bisect.bisect_right(cuts, a)
        hi = bisect.bisect_left(cuts, b)
        pts = [a] + cuts[lo:hi] + [b]
        for p, q in zip(pts, pts[1:]):
            if q > p:
                out[_innermost(spans, starts, (p + q) / 2)] += q - p
    return out


def _innermost(spans, starts, at: float) -> str:
    """The label at time ``at``: the innermost open span, with the kind of
    the benchmark span around it; ``bench.harness`` outside every span.
    Benchmark spans do not nest, so the search stops at the first one."""
    inner, kind = None, None
    for j in range(bisect.bisect_right(starts, at) - 1, -1, -1):
        s, e, name, st = spans[j]
        if e > at and inner is None:
            inner = name
        if name.startswith(trace_lib.SPAN_PREFIX):
            if e > at:
                kind = st.get("kind")
            break
    if inner is None:
        return "bench.harness"
    return f"{inner}:{kind}" if kind else inner
