#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip(s) for the whole run:

1. names the device, and fails (exit 3, no result) on any platform but TPU,
   on fewer chips than the cell asks for, or on a device kind that
   ``bench/peaks.json`` does not list;
2. builds the cell's configuration with weights made on the device from
   ``--seed`` and warms up the step programs its traffic uses (JAX's
   persistent compile cache lives at ``.jax_cache`` in the checkout);
3. starts the cell's traffic: a closed loop of clients, each filled in
   part-way through a request;
4. measures for ``--seconds``, calling ``ServingEngine.submit`` and
   ``ServingEngine.step`` as a streaming server would (every step returns
   its tokens to the host);
5. compares a sample of the served requests with the plain float32
   reference (``bench/reference.py``);
6. prints, as the last line of standard output, one JSON object:
   ``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end
   metrics with ``--trace 0``, the per-layer ones with ``--trace 1``),
   ``device`` and, last, ``check``: each number compared with its limit.
   The same numbers are the last lines of standard error.

``--control 1`` puts the fp8 control (``bench/reference.py``) in the
program's place for the comparison: the served tokens are replaced by the
tokens the control puts first at the same positions, and the run is
judged as any other.  It has to come out not correct.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import cells as cells_lib  # noqa: E402
from bench import flops as flops_lib  # noqa: E402
from bench import reference  # noqa: E402
from bench import spec as spec_lib  # noqa: E402
from bench import trace as trace_lib  # noqa: E402
from bench import traffic as traffic_lib  # noqa: E402
from bench import weights as weights_lib  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_S = 5.0           # length of the profiled part of a --trace 1 window


class NoDevice(RuntimeError):
    """The machine lacks the accelerator the cell needs."""


def load_peaks(kind: str) -> dict:
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise NoDevice(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(known: {sorted(peaks)})")
    return peaks[kind]


def require_devices(chips: int):
    """The first ``chips`` TPU devices, or NoDevice."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {devices[0].platform!r} "
                       f"({devices})")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devices)}")
    load_peaks(devices[0].device_kind)
    return devices[:chips]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def program_config(cell_cfg: dict, spec: spec_lib.ModelSpec):
    """The program's ModelConfig for the cell, checked against the widths
    the configuration file publishes."""
    from repro.configs.base import get_config
    rc = cell_cfg["repro"]
    cfg = get_config(rc["config"]).replace(num_layers=spec.layers,
                                           **rc.get("overrides", {}))
    want = {"d_model": spec.d_model, "num_heads": spec.heads,
            "num_kv_heads": spec.kv_heads, "head_dim": spec.head_dim,
            "d_ff": spec.expert_ff, "num_experts": spec.experts,
            "num_experts_padded": spec.experts_held,
            "experts_per_token": spec.top_k, "vocab_size": spec.vocab,
            "vocab_padded": spec.vocab_held, "tie_embeddings": spec.tied,
            "qk_norm": spec.qk_norm, "rope_theta": spec.rope_theta,
            "param_dtype": spec.dtype, "dtype": spec.dtype}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"program config differs from the file (got, "
                         f"want): {bad}")
    # dropless: every expert's capacity is the step's whole token count
    return cfg.replace(
        capacity_factor=cfg.num_experts_padded / cfg.experts_per_token)


def make_weights(cfg, spec, seed: int):
    """Weights from the seed, placed as the engine serves them; their
    layout is checked against the program's own parameter shapes."""
    import jax
    from repro.models.model import build_model
    want = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    made = jax.tree.map(lambda shp: jax.ShapeDtypeStruct(shp, spec.dtype),
                        weights_lib.shapes(spec),
                        is_leaf=lambda x: isinstance(x, tuple))
    if jax.tree.structure(want) != jax.tree.structure(made) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(made))):
        raise ValueError("bench/weights.py's layout differs from the "
                         "program's parameters")
    return weights_lib.make(spec, seed)


class CompileCounter:
    """Counts programs lowered (jit cache misses, whether or not the
    persistent cache then supplies the executable)."""

    def __init__(self):
        from jax import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1


# ---------------------------------------------------------------------------
# feeding the engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Rec:
    """One request as the client sees it."""
    req: object                 # the engine's Request
    sent: float
    times: list = dataclasses.field(default_factory=list)
    finished: float | None = None


@dataclasses.dataclass
class StepRec:
    i: int
    t1: float                   # when the step returned
    kind: str                   # mixed | decode | idle
    emitted: int = 0
    contexts: np.ndarray | None = None
    segs: np.ndarray | None = None
    experts_touched: int | None = None
    device_s: float | None = None


class Driver:
    """Feeds the engine its traffic and stamps every streamed token."""

    def __init__(self, engine, spec):
        import jax
        self.jax = jax
        self.engine = engine
        self.active: list[Rec] = []
        self.recs: list[Rec] = []
        self.steps: list[StepRec] = []
        self.on_finish = None
        self.profiling = False
        self._dispatch_lengths = None
        self._step: StepRec | None = None
        unified = engine._jit_unified
        observe = engine._observe_routing

        def dispatch(*args, **kw):
            self._dispatch_lengths = engine.lengths.copy()
            return unified(*args, **kw)

        def observe_routing(rec, routing):
            if (self.profiling and rec.kind == "decode"
                    and routing is not None):
                per_row = routing.reshape(routing.shape[0],
                                          rec.routing_batch, -1)
                rows = [r for r, _, _ in rec.rows]
                ids = per_row[:, rows].reshape(routing.shape[0], -1)
                self._step.experts_touched = int(sum(
                    len(np.unique(x[x < spec.experts])) for x in ids))
            observe(rec, routing)

        engine._jit_unified = dispatch
        engine._observe_routing = observe_routing

    def has_work(self) -> bool:
        e = self.engine
        return bool(e.queue) or any(s is not None for s in e.slots)

    def submit(self, spec: traffic_lib.Spec) -> Rec:
        uid = self.engine.submit(spec.prompt,
                                 max_new_tokens=spec.max_new_tokens)
        rec = Rec(self.engine._all[uid], time.perf_counter())
        self.active.append(rec)
        self.recs.append(rec)
        return rec

    def step(self) -> StepRec:
        st = self.engine.stats
        p0, d0 = st["prefill_tokens"], st["decode_tokens"]
        i = len(self.steps)
        rec = StepRec(i, 0.0, "idle")
        self._step = rec
        self._dispatch_lengths = None
        with self.jax.profiler.TraceAnnotation("bench.step", step=i):
            self.engine.step()
        rec.t1 = now = time.perf_counter()
        rec.kind = ("mixed" if st["prefill_tokens"] > p0
                    else "decode" if st["decode_tokens"] > d0 else "idle")
        if self._dispatch_lengths is not None:
            seg = self.engine.lengths - self._dispatch_lengths
            live = seg > 0
            rec.contexts = self._dispatch_lengths[live]
            rec.segs = seg[live]
        still = []
        for r in self.active:
            n = len(r.req.generated)
            if n > len(r.times):
                rec.emitted += n - len(r.times)
                r.times.extend([now] * (n - len(r.times)))
            if r.req.done:
                r.finished = now
            else:
                still.append(r)
        done = [r for r in self.active if r.finished is not None]
        self.active = still
        self.steps.append(rec)
        if self.on_finish is not None:
            for r in done:
                self.on_finish(r)
        return rec

    def wait(self, until: float) -> None:
        with self.jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(max(0.0, min(until - time.perf_counter(), 0.002)))


class Profiler:
    """Profiles ``seconds`` of the window, from ``offset`` seconds after it
    opens, into a temporary directory, which the caller removes."""

    def __init__(self, enabled: bool, offset: float, seconds: float):
        self.enabled = enabled
        self.offset = offset
        self.seconds = seconds
        self.start = self.stop = math.inf
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if enabled else None
        self.state = "before"

    def arm(self, w0: float) -> None:
        self.start = w0 + self.offset
        self.stop = self.start + self.seconds

    def poll(self, driver: Driver, force_stop: bool = False) -> None:
        if not self.enabled:
            return
        import jax
        now = time.perf_counter()
        if self.state == "before" and now >= self.start and not force_stop:
            jax.profiler.start_trace(self.dir)
            self.state = "on"
            driver.profiling = True
        elif self.state == "on" and (now >= self.stop or force_stop):
            driver.profiling = False
            jax.profiler.stop_trace()
            self.state = "done"


def warm_up(driver: Driver, counter: CompileCounter, vocab: int,
            seed: int) -> None:
    """Serve small requests until no program is lowered any more: a
    prompt longer than one chunk (mixed and decode steps), then the same
    prompt again (a prefix hit with a copy-on-write tail)."""
    rng = np.random.default_rng(seed ^ 0x5EED)
    chunk = driver.engine.chunk_len
    for _ in range(6):
        before = counter.n
        prompt = rng.integers(0, vocab, chunk + 8, dtype=np.int32)
        for _ in range(2):
            driver.submit(traffic_lib.Spec(prompt, 4))
            while driver.has_work():
                driver.step()
        if counter.n == before:
            break
    else:
        raise RuntimeError("warm-up kept lowering new programs")
    driver.recs.clear()
    driver.steps.clear()


@dataclasses.dataclass
class Served:
    """What one measured window gave."""
    w0: float
    w1: float
    stats: dict                 # engine counters, diff over the window
    compiles: int               # programs lowered from w0 to the end
    engine_traces: int
    steps: list                 # StepRec inside the window
    due: list                   # Rec that count as attempted
    candidates: list            # Rec that may be compared

    @property
    def failed(self) -> list:
        """Requests that finished in a failure state."""
        return [r for r in self.due
                if r.finished is not None and r.req.status != "done"]


def serve(driver: Driver, counter: CompileCounter, traffic,
          seconds: float, prof: Profiler) -> Served:
    """Warm the system prompts, fill every client's row, then run the
    closed loop for ``seconds``: each client sends its next request when
    its previous one finishes."""
    engine = driver.engine
    for spec in traffic.warm:
        driver.submit(spec)
    while driver.has_work():
        driver.step()
    driver.recs.clear()
    queue = list(reversed(traffic.queue))

    def next_request(rec: Rec) -> None:
        if queue:
            driver.submit(queue.pop())

    fill = [driver.submit(s) for s in traffic.fill]
    while any(not r.times for r in fill):
        driver.step()
    driver.on_finish = next_request
    w0 = time.perf_counter()
    w1 = w0 + seconds
    s0, c0 = dict(engine.stats), counter.n
    tr0, i0 = sum(engine.trace_counts.values()), len(driver.steps)
    prof.arm(w0)
    while True:
        prof.poll(driver)
        if time.perf_counter() >= w1:
            break
        if driver.has_work():
            driver.step()
        else:
            driver.wait(time.perf_counter() + 0.002)
    s1, i1 = dict(engine.stats), len(driver.steps)
    driver.on_finish = None
    prof.poll(driver, force_stop=True)
    due = [r for r in driver.recs if r.sent < w1
           and (r.finished is None or r.finished >= w0)]
    cands = [r for r in due if r.finished is not None and r.finished < w1]
    return Served(
        w0, w1,
        {k: s1[k] - s0[k] for k in s0 if isinstance(s0[k], int)},
        counter.n - c0, sum(engine.trace_counts.values()) - tr0,
        driver.steps[i0:i1], due, cands)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def pct(values, q: float) -> float:
    """Nearest-rank percentile."""
    v = sorted(values)
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def end_to_end(driver: Driver, sv: Served) -> dict:
    w0, w1 = sv.w0, sv.w1
    stamps = [t for r in driver.recs for t in r.times if w0 <= t < w1]
    gaps = [b - a for r in driver.recs for a, b in zip(r.times, r.times[1:])
            if w0 <= a and b < w1]
    return {"output_tok_per_s": len(stamps) / (w1 - w0),
            "itl_p99_ms": 1e3 * pct(gaps, 0.99) if gaps else float("nan")}


@dataclasses.dataclass
class LayerContext:
    """What a per-layer reader (``bench/layer_metrics/<name>.py``) reads."""
    spec: spec_lib.ModelSpec
    peak: dict                  # bench/peaks.json entry of the device
    chips: int
    stats: dict                 # engine.stats diff over the window
    traced: list                # StepRec inside the profiled part
    reduced: trace_lib.Reduced | None

    def device_ms(self, kind: str) -> float | None:
        t = [s.device_s for s in self.traced if s.kind == kind]
        return 1e3 * sum(t) / len(t) if t else None

    def mfu(self) -> float | None:
        steps = [s for s in self.traced if s.segs is not None]
        busy = sum(s.device_s for s in steps)
        if not steps or busy <= 0:
            return None
        fl = sum(flops_lib.step_flops(self.spec, s.contexts, s.segs,
                                      s.emitted) for s in steps)
        return 100.0 * fl / (busy * self.chips
                             * self.peak["bf16_flops_per_s"])

    def decode_hbm_share(self) -> float | None:
        steps = [s for s in self.traced if s.kind == "decode"
                 and s.experts_touched is not None and s.contexts is not None]
        busy = sum(s.device_s for s in steps)
        if not steps or busy <= 0:
            return None
        by = sum(flops_lib.decode_bytes(self.spec, s.contexts,
                                        s.experts_touched) for s in steps)
        return 100.0 * by / (busy * self.chips * self.peak["hbm_bytes_per_s"])


def reduce_trace(prof: Profiler, driver: Driver):
    t = trace_lib.load(prof.dir)
    by_i = {s.i: s for s in driver.steps}
    spans = []
    for s, e, name, st in t.spans:
        if name == "bench.step" and "step" in st:
            step = by_i.get(int(st["step"]))
            if step is not None:
                st["kind"] = step.kind
                spans.append((s, e, name, st, step))
    red = trace_lib.reduce(t, [x[:4] for x in spans])
    for (_, _, _, _, step), busy in zip(spans, red.span_busy_s):
        step.device_s = busy
    return red, [x[4] for x in spans]


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def pick_samples(candidates: list, seed: int, packed: int) -> list:
    """The request with the most served tokens, then others drawn from the
    seed while they fit ``packed`` positions."""
    size = lambda r: len(r.req.prompt) + len(r.req.generated) - 1
    fits = [r for r in candidates if size(r) <= packed]
    if not fits:
        return []
    first = max(fits, key=lambda r: (len(r.req.generated), -r.req.uid))
    out, used = [first], size(first)
    rng = np.random.default_rng(seed)
    for j in rng.permutation(len(fits)):
        r = fits[j]
        if r is not first and used + size(r) <= packed:
            out.append(r)
            used += size(r)
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Built:
    spec: spec_lib.ModelSpec
    cfg: object
    weights: dict


def build(cell: cells_lib.Cell, seed: int, devices) -> Built:
    spec = spec_lib.from_config(cell.config)
    cfg = program_config(cell.config, spec)
    return Built(spec, cfg, make_weights(cfg, spec, seed))


def new_driver(cell: cells_lib.Cell, b: Built) -> Driver:
    from repro.serving.engine import EngineConfig, ServingEngine
    ecfg = EngineConfig(unified_step=True, paged=True, paged_kernel=False,
                        track_experts=True, **cell.config["engine"])
    engine = ServingEngine(b.cfg, ecfg, params=b.weights)
    return Driver(engine, b.spec)


def release(driver: Driver) -> None:
    """Drop the engine and its page pool; the weights stay."""
    engine = driver.engine
    driver.engine = None
    engine._jit_unified = engine._observe_routing = None
    engine.cache = None
    gc.collect()


def samples_of(cell: cells_lib.Cell, sv: Served, seed: int) -> list:
    picked = pick_samples(sv.candidates, seed,
                          cell.config["check"]["packed_tokens"])
    return [(np.asarray(r.req.prompt), np.asarray(r.req.generated, np.int32))
            for r in picked]


def judge(cell: cells_lib.Cell, b: Built, samples: list, failed: int,
          control: bool = False):
    """Compare the sample with the reference.  The number compared is the
    mean, over every served token of the sample, of the gap by which its
    logit lies below the reference's best (``PERF.md`` says why the mean
    and not the widest gap); the widest is reported beside it.  With
    ``control``, the tokens compared are the fp8 control's, at the same
    positions of the same sequences."""
    chk = cell.config["check"]
    gaps_of = reference.control_gaps if control else reference.gaps
    gaps = (gaps_of(b.weights, b.spec, samples, chk["packed_tokens"],
                    chk["expert_group"])
            if samples else np.array([math.inf]))
    mean = float(np.mean(gaps))
    compared = int(sum(len(s) for _, s in samples))
    check = {
        "mean_logit_gap": {"value": mean, "limit": chk["mean_logit_gap"]},
        "requests_unanswered": {"value": failed, "limit": 0},
        "served_tokens_compared": {"value": compared,
                                   "limit": chk["min_compared_tokens"]},
    }
    correct = (mean <= chk["mean_logit_gap"] and failed == 0
               and compared >= chk["min_compared_tokens"])
    return correct, check, float(np.max(gaps))


def run_cell(cell: cells_lib.Cell, seed: int, seconds: float, trace: bool,
             devices, t_process: float = T_PROCESS,
             control: bool = False) -> dict:
    counter = CompileCounter()
    b = build(cell, seed, devices)
    driver = new_driver(cell, b)
    warm_up(driver, counter, b.spec.vocab, seed)
    traffic = traffic_lib.generate(cell.traffic, seed, b.spec.vocab)
    trace_len = min(TRACE_S, seconds)
    prof = Profiler(trace, (seconds - trace_len) / 2, trace_len)
    sv = serve(driver, counter, traffic, seconds, prof)
    e2e = end_to_end(driver, sv)
    e2e["setup_s"] = sv.w0 - t_process
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)
    layer, extra = {}, {}
    if trace:
        red, traced = reduce_trace(prof, driver)
        shutil.rmtree(prof.dir, ignore_errors=True)
        ctx = LayerContext(b.spec, load_peaks(devices[0].device_kind),
                           len(devices), sv.stats, traced, red)
        for m in cell.per_layer:
            v = cells_lib.metric_reader(m.name)(ctx)
            if v is not None:
                layer[m.name] = {"value": float(v), "unit": m.unit}
        extra = {"breakdown": {
            "device_ops": [[k, v] for k, v in red.top_ops],
            "idle_gaps": [[k, v] for k, v in red.idle_gaps]}}
        dev_trace = {"busy_s": red.busy_s, "window_s": red.window_s}
    samples = samples_of(cell, sv, seed)
    failed = len(sv.failed)
    release(driver)
    del driver
    correct, check, widest = judge(cell, b, samples, failed, control)
    d0 = devices[0]
    window = {"setup_s": e2e["setup_s"], "seconds": sv.w1 - sv.w0,
              "steps": len(sv.steps),
              "mixed_steps": sum(s.kind == "mixed" for s in sv.steps),
              "compiles": sv.compiles, "engine_traces": sv.engine_traces,
              "longest_step_gap_s": float(np.max(np.diff(
                  [sv.w0] + [s.t1 for s in sv.steps]))),
              "max_logit_gap": widest, "control": control}
    if control and samples:
        # the program's own reading on the same sample, beside the control's
        chk = cell.config["check"]
        window["program_mean_logit_gap"] = float(np.mean(reference.gaps(
            b.weights, b.spec, samples, chk["packed_tokens"],
            chk["expert_group"])))
    return {
        "correct": bool(correct), "attempted": len(sv.due), "failed": failed,
        "metrics": (layer if trace else
                    {m.name: {"value": float(e2e[m.name]), "unit": m.unit}
                     for m in cell.end_to_end}),
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices), "memory_peak_bytes": int(mem),
                   **(dev_trace if trace else {})},
        **extra,
        "window": window,
        "check": check,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the fp8 control's tokens in place of the "
                         "served ones; the run has to come out not correct")
    args = ap.parse_args(argv)
    cell = cells_lib.load_cell(args.workload)
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        devices = require_devices(cell.chips)
    except NoDevice as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    print(f"bench: {args.workload} seed {args.seed} on {len(devices)} x "
          f"{devices[0].device_kind}", file=sys.stderr, flush=True)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   control=bool(args.control))
    for k in ("window",):
        print(f"{k} {json.dumps(out[k])}", file=sys.stderr)
    for k, v in out["check"].items():
        print(f"{k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
