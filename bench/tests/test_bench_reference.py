"""The plain reference against the serving engine at reduced shapes of
both families: the engine's chunked-prefill-then-decode logits (paged
pool, prefix hit, batch of rows) agree with it in float32, and the
comparison tells apart an engine whose positions are shifted by one, a
reference rounded to bfloat16 and the fp8 control."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, run
from bench.tests.tiny import CHAT, REASONING, tiny_cell

FAMILIES = {"granite": CHAT, "qwen3": REASONING}
# float32 engine against the float32 reference: both compute the same
# sums in another order, so they agree to a few ulps of the logits
LOGIT_TOL = 1e-4


def _serve(name: str, shift: int = 0):
    """Serve four requests (two sharing a prefix) through the paged
    unified engine in float32; return the built cell, the requests and,
    for each served token, the engine's logits that produced it."""
    from repro.serving.engine import EngineConfig, ServingEngine
    cell = tiny_cell(FAMILIES[name], dtype="float32")
    b = run.build(cell, 12345, jax.devices()[:1])
    kept = {}

    class Probe(ServingEngine):
        def _sample_next(self, logits, temps, topks, step_idx, sampling):
            jax.debug.callback(
                lambda i, lg: kept.__setitem__(int(i), np.asarray(lg)),
                step_idx, logits)
            return super()._sample_next(logits, temps, topks, step_idx,
                                        sampling)

    eng = Probe(b.cfg, EngineConfig(unified_step=True, paged=True,
                                    **cell.config["engine"]),
                params=b.weights)
    if shift:
        fwd = eng.model.forward_routed

        def shifted(params, batch, cache, *a, **k):
            return fwd(params, dict(batch, lengths=batch["lengths"] + shift),
                       cache, *a, **k)
        eng.model = dataclasses.replace(eng.model, forward_routed=shifted)
    rng = np.random.default_rng(3)
    shared = rng.integers(0, b.spec.vocab, 40, dtype=np.int32)
    prompts = [shared, np.concatenate([shared[:37], rng.integers(
        0, b.spec.vocab, 9, dtype=np.int32)])] + [
        rng.integers(0, b.spec.vocab, n, dtype=np.int32) for n in (23, 50)]
    reqs = [eng._all[eng.submit(prompts[0], max_new_tokens=12)]]
    served = {}
    while eng.queue or any(s is not None for s in eng.slots):
        if reqs[0].generated and len(reqs) == 1:
            # the first prompt is cached now: the second hits its prefix
            reqs += [eng._all[eng.submit(p, max_new_tokens=12)]
                     for p in prompts[1:]]
        for r in reqs:
            served.setdefault(r.uid, [])
        before = list(eng.slots)
        idx = eng._step_idx
        n0 = {r.uid: len(r.generated) for r in reqs}
        eng.step()
        for r in reqs:
            if len(r.generated) > n0[r.uid]:
                slot = before.index(r) if r in before else eng.slots.index(r)
                served[r.uid].append(kept[idx][slot, :b.spec.vocab])
    assert eng.stats["prefix_hit_tokens"] > 0
    return cell, b, reqs, served


def _reference_logits(cell, b, reqs, mode="f32"):
    samples = [(np.asarray(r.prompt), np.asarray(r.generated, np.int32))
               for r in reqs]
    tokens, pos, seg, rows, toks, n = reference.pack(
        samples, cell.config["check"]["packed_tokens"])
    h = reference.hidden(b.weights, tokens, pos, seg, s=b.spec,
                         group=cell.config["check"]["expert_group"],
                         mode=mode)
    w = reference._head(b.weights, b.spec).astype(jnp.float32)
    lg = np.asarray(jnp.einsum("nd,dv->nv", h[rows[:n]], w,
                               precision=jax.lax.Precision.HIGHEST))
    return samples, lg


def _rel_err(reqs, served, lg):
    got = np.concatenate([np.stack(served[r.uid]) for r in reqs])
    return float(np.max(np.abs(got - lg)) / np.std(lg))


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family(request):
    return request.param, _serve(request.param)


def test_engine_logits_agree_with_reference(family):
    _, (cell, b, reqs, served) = family
    samples, lg = _reference_logits(cell, b, reqs)
    assert _rel_err(reqs, served, lg) < LOGIT_TOL
    g = reference.gaps(b.weights, b.spec, samples,
                       cell.config["check"]["packed_tokens"],
                       cell.config["check"]["expert_group"])
    assert len(g) == sum(len(s) for _, s in samples)
    assert float(np.max(g)) == pytest.approx(0.0, abs=1e-5)


def test_bf16_rounded_reference_is_told_apart(family):
    _, (cell, b, reqs, served) = family
    _, lg = _reference_logits(cell, b, reqs, mode="bf16")
    assert _rel_err(reqs, served, lg) > 10 * LOGIT_TOL


def test_fp8_control_is_told_apart(family):
    _, (cell, b, reqs, _) = family
    samples = [(np.asarray(r.prompt), np.asarray(r.generated, np.int32))
               for r in reqs]
    chk = cell.config["check"]
    ctl = reference.control_gaps(b.weights, b.spec, samples,
                                 chk["packed_tokens"], chk["expert_group"])
    assert float(np.mean(ctl)) > 0.001


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_shifted_position_is_told_apart(name):
    cell, b, reqs, served = _serve(name, shift=1)
    samples, lg = _reference_logits(cell, b, reqs)
    assert _rel_err(reqs, served, lg) > 10 * LOGIT_TOL
    g = reference.gaps(b.weights, b.spec, samples,
                       cell.config["check"]["packed_tokens"],
                       cell.config["check"]["expert_group"])
    assert float(np.mean(g)) > 1e-3      # the sound engine reads 0
