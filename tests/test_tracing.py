"""The engine's serving.* spans and step_times, and the named scopes of the
step program (docs/DESIGN.md "Tracing")."""
import ast
import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.programs import trace_program
from repro.configs.base import get_config
from repro.core import expert_parallel
from repro.launch.mesh import make_mesh
from repro.models.model import build_model
from repro.serving import engine as engine_mod
from repro.serving.engine import EngineConfig, ServingEngine

MOE_ARCH = "qwen3_moe_30b_a3b"
STEP_SCOPES = ("embed", "attn", "attn.gather", "layer.loop", "layer.cache",
               "moe.route", "moe.dispatch", "moe.ffn", "moe.combine",
               "lm_head", "sample")


def _scopes_in(text: str) -> set:
    return {part for path in re.findall(r'op_name="([^"]*)"', text)
            for part in path.split("/")}


@pytest.mark.parametrize("variant", ["paged", "paged_kernel"])
def test_lowered_unified_program_holds_every_scope(variant):
    txt = trace_program(variant).hlo_text
    missing = set(STEP_SCOPES) - _scopes_in(txt)
    assert not missing, missing


@pytest.mark.parametrize("schedule", ["decentralized", "centralized"])
def test_expert_parallel_collectives_are_scoped(schedule):
    cfg = get_config(MOE_ARCH).reduced().replace(expert_parallel=schedule)
    mesh = make_mesh((1, 1), ("data", "model"))
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda a: a[0], params["blocks"])
    moe_p = {"router": lp["router"], "experts": lp["experts"]}
    x = jnp.ones((2, 4, cfg.d_model), cfg.dtype)
    f = jax.jit(lambda p, x: expert_parallel.moe_layer(cfg, mesh, p, x)[0])
    txt = f.lower(moe_p, x).as_text(debug_info=True)
    found = set(re.findall(r'(moe\.\w+)/', txt))
    assert {"moe.route", "moe.dispatch", "moe.ffn", "moe.combine",
            "moe.collective"} <= found, found


def _engine(**kw):
    cfg = get_config(MOE_ARCH).reduced().replace(capacity_factor=8.0)
    ekw = dict(max_batch=2, max_cache=32, async_steps=False, chunk_len=4,
               paged=True, page_size=4, track_experts=True)
    ekw.update(kw)
    return ServingEngine(cfg, EngineConfig(**ekw), rng=jax.random.PRNGKey(0))


def test_step_times_feed_the_phase_buckets():
    eng = _engine()
    rng = np.random.default_rng(3)
    eng.submit(rng.integers(0, 100, 9), max_new_tokens=4)
    seen = []
    for _ in range(5):
        before = {k: eng.stats[k] for k in ("prefill_s", "decode_s",
                                            "mixed_s")}
        host0 = eng.stats["host_ns"]
        eng.step()
        st = dict(eng.step_times)
        seen.append(st)
        grew = sum(eng.stats[k] - before[k] for k in before)
        # transfer + dispatch + sync, then the harvest's fetch
        assert grew == pytest.approx(st["transfer"] + st["dispatch"]
                                     + st["sync"] + st["fetch"], rel=1e-9)
        # every outermost span but the sync is host time
        host = sum(v for k, v in st.items()
                   if k not in ("sync", "fetch", "observe_routing"))
        assert (eng.stats["host_ns"] - host0) * 1e-9 == pytest.approx(
            host, rel=1e-6, abs=1e-8)
    assert set(seen[0]) == {"schedule", "transfer", "dispatch", "sync",
                            "apply", "harvest", "fetch", "observe_routing"}
    assert eng.stats["dispatches"] == 5
    assert "harvest_s" not in eng.stats and "harvests" not in eng.stats


def test_step_times_clear_at_each_step():
    eng = _engine()
    eng.submit(np.arange(3), max_new_tokens=2)
    eng.step()
    eng.step()
    assert eng.step_times["dispatch"] > 0
    eng.step()                      # nothing left: schedule only
    assert set(eng.step_times) == {"schedule"}


def test_reference_path_spans_feed_its_buckets():
    eng = _engine(paged=False, unified_step=False, prefill_len=16)
    eng.submit(np.arange(5), max_new_tokens=3)
    eng.run_until_done()
    s = eng.stats
    assert s["prefill_s"] > 0 and s["decode_s"] > 0
    assert s["dispatches"] == 3        # one prefill, two decode steps
    assert s["host_ns"] > 0


def test_no_engine_span_claims_the_benchmark_prefix():
    """Spans of the program never start with ``bench.``: the benchmark's
    trace reduction keys on that prefix."""
    names = []
    for node in ast.walk(ast.parse(inspect.getsource(engine_mod))):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("_span", "TraceAnnotation")
                and node.args and isinstance(node.args[0], ast.Constant)):
            names.append(node.args[0].value)
    assert "dispatch" in names and "sync" in names
    assert not [n for n in names if n.startswith("bench")]
    assert engine_mod._Span.__init__ and "serving." in inspect.getsource(
        engine_mod._Span.__enter__)
