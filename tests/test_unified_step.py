"""ISSUE 3 tentpole: unified token-budget forward pass.

``Model.forward_routed`` processes an arbitrary (B, T) token block at
arbitrary per-row cache offsets — whole-prompt prefill, chunked prefill,
single-token decode and mixed prefill/decode batches are all the same
program.  These tests pin token-for-token equality against the two-program
reference (``EngineConfig.unified_step=False``) under non-binding capacity
(capacity pools are per-jit-call, so a binding capacity legitimately
drops different tokens per chunk — the batch-capacity semantics documented
in serving/engine.py), plus the no-truncation long-prompt path and
per-request sampling.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, input_specs, mixed_shape
from repro.models.model import build_model
from repro.serving.engine import EngineConfig, ServingEngine

MOE_ARCH = "qwen3_moe_30b_a3b"
DENSE_ARCH = "qwen3_0_6b"


def nocap(arch):
    """Reduced config with non-binding dispatch capacity (the regime where
    chunked == whole-prompt is exact; see module docstring)."""
    return get_config(arch).reduced().replace(capacity_factor=8.0)


def make_engine(cfg, seed=0, **eng_kw):
    kw = dict(max_batch=2, prefill_len=8, max_cache=32)
    kw.update(eng_kw)
    return ServingEngine(cfg, EngineConfig(**kw), rng=jax.random.PRNGKey(seed))


def generations(done):
    return {r.uid: list(r.generated) for r in done}


# ---------------------------------------------------------------------------
# model level: chunked forward_routed == whole-prompt prefill_routed
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", [MOE_ARCH, DENSE_ARCH])
@pytest.mark.parametrize("chunk", [3, 4, 8])   # 3 does not divide 8
def test_chunked_forward_matches_whole_prompt(arch, chunk):
    cfg = nocap(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s, c = 2, 8, 32
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 100, (b, s)),
                       jnp.int32)
    logits_r, cache_r, _ = model.prefill_routed(
        params, {"tokens": toks}, model.init_cache(b, c))
    cache_u = model.init_cache(b, c)
    for lo in range(0, s, chunk):
        hi = min(lo + chunk, s)
        logits_u, cache_u, routing = model.forward_routed(
            params, {"tokens": toks[:, lo:hi],
                     "lengths": jnp.full((b,), lo, jnp.int32),
                     "seg_lens": jnp.full((b,), hi - lo, jnp.int32)},
            cache_u)
        if cfg.is_moe:
            assert routing.shape == (cfg.num_layers, b * (hi - lo),
                                     cfg.experts_per_token)
    v = cfg.vocab_size
    np.testing.assert_array_equal(
        np.argmax(np.asarray(logits_r[:, -1, :v]), -1),
        np.argmax(np.asarray(logits_u[:, :v]), -1))
    # the caches agree exactly on every written slot
    np.testing.assert_allclose(np.asarray(cache_r["k"]),
                               np.asarray(cache_u["k"]), atol=1e-5)


def test_forward_routed_mixed_rows_match_decode_and_prefill():
    """One call whose rows do DIFFERENT work: row 0 decodes one token, row
    1 prefills a chunk — each must equal its single-purpose reference."""
    cfg = nocap(MOE_ARCH)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    b, c = 2, 32
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, 100, (b, 6)), jnp.int32)
    _, cache, _ = model.prefill_routed(params, {"tokens": toks},
                                       model.init_cache(b, c))
    # reference: row 0 decode step on the shared cache
    dec_tok = jnp.asarray([[7], [0]], jnp.int32)
    lengths = jnp.full((b,), 6, jnp.int32)
    logits_d, _, _ = model.decode_step_routed(
        params, jax.tree.map(jnp.copy, cache),
        {"tokens": dec_tok, "lengths": lengths,
         "token_mask": jnp.asarray([[True], [False]])})
    # reference: row 1 continues its prompt by 3 tokens (batch-1 unified
    # call — already verified equal to prefill by the test above)
    cont = jnp.asarray(rng.integers(0, 100, (1, 3)), jnp.int32)
    row1_cache = jax.tree.map(lambda a: a[:, 1:2] if a.ndim >= 2 else a,
                              cache)
    logits_p, _, _ = model.forward_routed(
        params, {"tokens": cont, "lengths": jnp.asarray([6], jnp.int32),
                 "seg_lens": jnp.asarray([3], jnp.int32)}, row1_cache)
    # mixed call: row 0 seg=1 (decode), row 1 seg=3 (prefill chunk)
    blk = jnp.zeros((b, 3), jnp.int32)
    blk = blk.at[0, 0].set(7).at[1].set(cont[0])
    logits_m, _, _ = model.forward_routed(
        params, {"tokens": blk, "lengths": jnp.asarray([6, 6], jnp.int32),
                 "seg_lens": jnp.asarray([1, 3], jnp.int32)}, cache)
    v = cfg.vocab_size
    assert int(jnp.argmax(logits_m[0, :v])) == int(
        jnp.argmax(logits_d[0, -1, :v]))
    assert int(jnp.argmax(logits_m[1, :v])) == int(
        jnp.argmax(logits_p[0, :v]))


def test_mixed_input_specs_match_forward_routed_signature():
    """configs.input_specs(kind="mixed") describes exactly the unified
    step's batch inputs (eval_shape-compatible with forward_routed)."""
    cfg = nocap(MOE_ARCH)
    model = build_model(cfg)
    shape = mixed_shape("mixed_demo", cache_len=32, batch=2, chunk_len=4)
    specs = input_specs(cfg, shape)
    assert set(specs) == {"tokens", "lengths", "seg_lens"}
    assert specs["tokens"].shape == (2, 4)
    p_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    c_sds = model.cache_specs(shape.global_batch, shape.seq_len)
    logits, _, _ = jax.eval_shape(model.forward_routed, p_sds, specs, c_sds)
    assert logits.shape == (2, cfg.vocab_padded)


def test_ring_cache_engine_falls_back_and_block_step_rejects_wide_chunks():
    """Ring caches (window == cache length) only take width-1 blocks: a
    wrapped multi-token write before attention would overwrite slots whose
    old positions are still inside earlier chunk tokens' windows.  The
    model raises loudly and the engine keeps the reference path."""
    cfg = nocap(MOE_ARCH).replace(sliding_window=16)
    eng = ServingEngine(cfg, EngineConfig(max_batch=2, prefill_len=8,
                                          max_cache=32, unified_step=True),
                        rng=jax.random.PRNGKey(0))
    assert not eng.unified                    # cache clipped to a 16-ring
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(1, 32)           # -> ring of 16 slots
    with pytest.raises(ValueError, match="width-1"):
        model.forward_routed(
            params, {"tokens": jnp.zeros((1, 4), jnp.int32),
                     "lengths": jnp.zeros((1,), jnp.int32),
                     "seg_lens": jnp.full((1,), 4, jnp.int32)}, cache)


def test_engine_config_rejects_degenerate_scheduler_knobs():
    cfg = nocap(MOE_ARCH)
    for kw in (dict(chunk_len=0), dict(token_budget=-1)):
        with pytest.raises(ValueError, match="chunk_len must be"):
            ServingEngine(cfg, EngineConfig(max_batch=2, prefill_len=8,
                                            max_cache=32, **kw))
    # an empty prompt would be scheduled as a decode row seeded from the
    # slot's stale last_tok — rejected at submit
    eng = make_engine(cfg)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(np.zeros((0,), np.int32), max_new_tokens=2)


def test_forward_routed_rejects_stateful_families():
    cfg = get_config("mamba2_130m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(1, 16)
    with pytest.raises(NotImplementedError):
        model.forward_routed(
            params, {"tokens": jnp.zeros((1, 4), jnp.int32),
                     "lengths": jnp.zeros((1,), jnp.int32),
                     "seg_lens": jnp.full((1,), 4, jnp.int32)}, cache)


# ---------------------------------------------------------------------------
# engine level: unified scheduler == two-program reference, token for token
# ---------------------------------------------------------------------------

def _run_engine(cfg, prompts, max_new=5, **kw):
    eng = make_engine(cfg, **kw)
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)
    return generations(eng.run_until_done()), eng


@pytest.mark.parametrize("arch", [MOE_ARCH, DENSE_ARCH])
@pytest.mark.parametrize("chunk", [3, 8])      # 3 does not divide 8
def test_unified_engine_matches_reference(arch, chunk):
    """Full-length prompts (the padded reference attends its zero padding,
    so shorter prompts legitimately diverge) + non-binding capacity: the
    chunked/mixed-batch unified engine must be token-identical."""
    cfg = nocap(arch)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 100, 8) for _ in range(4)]   # == prefill_len
    ref, _ = _run_engine(cfg, prompts, unified_step=False, async_steps=False)
    uni, eng = _run_engine(cfg, prompts, unified_step=True, chunk_len=chunk,
                           async_steps=False)
    assert eng.unified
    assert uni == ref
    # async dispatch and a binding per-iteration token budget only change
    # scheduling, never tokens
    uni_a, _ = _run_engine(cfg, prompts, unified_step=True, chunk_len=chunk,
                           async_steps=True)
    uni_b, _ = _run_engine(cfg, prompts, unified_step=True, chunk_len=chunk,
                           token_budget=chunk + 1)
    assert uni_a == ref and uni_b == ref


def test_unified_mixed_batch_matches_staggered_reference():
    """Arrivals mid-generation: the unified engine serves them as mixed
    prefill+decode iterations, the reference as separate programs — tokens
    must agree."""
    cfg = nocap(MOE_ARCH)
    rng = np.random.default_rng(7)
    p1, p2 = rng.integers(0, 100, 8), rng.integers(0, 100, 8)
    outs = {}
    for name, kw in (("ref", dict(unified_step=False)),
                     ("uni", dict(unified_step=True, chunk_len=3))):
        eng = make_engine(cfg, async_steps=False, **kw)
        eng.submit(p1, max_new_tokens=6)
        eng.step()
        eng.step()
        eng.submit(p2, max_new_tokens=4)     # lands mid-flight of p1
        outs[name] = generations(eng.run_until_done())
    assert outs["uni"] == outs["ref"]


def test_unified_serves_prompt_longer_than_prefill_len():
    """The acceptance-criteria scenario: a prompt LONGER than the reference
    prefill_len streams through the cache chunk by chunk, and generation
    equals a straight model-API replay of the untruncated prompt."""
    cfg = nocap(MOE_ARCH)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 100, 21)               # > prefill_len=8
    eng = make_engine(cfg, max_batch=2, prefill_len=8, max_cache=64,
                      unified_step=True, chunk_len=5, async_steps=False)
    eng.submit(prompt, max_new_tokens=6)
    done = eng.run_until_done()
    assert len(done) == 1 and len(done[0].generated) == 6

    # reference replay: whole untruncated prompt through prefill_routed
    model = build_model(cfg)
    cache = model.init_cache(1, 64)
    logits, cache, _ = model.prefill_routed(
        eng.params, {"tokens": jnp.asarray(prompt[None], jnp.int32)}, cache)
    toks = [int(jnp.argmax(logits[0, -1, :cfg.vocab_size]))]
    lengths = np.array([len(prompt)], np.int32)
    for _ in range(5):
        logits, cache, _ = model.decode_step_routed(
            eng.params, cache, {"tokens": jnp.asarray([[toks[-1]]]),
                                "lengths": jnp.asarray(lengths)})
        toks.append(int(jnp.argmax(logits[0, -1, :cfg.vocab_size])))
        lengths += 1
    assert done[0].generated == toks


def test_reference_mode_rejects_long_prompt():
    """Satellite fix: the padded reference engine must REFUSE prompts
    longer than prefill_len instead of silently dropping the prefix."""
    cfg = nocap(MOE_ARCH)
    eng = make_engine(cfg, unified_step=False)
    with pytest.raises(ValueError, match="refusing to silently truncate"):
        eng.submit(np.arange(9), max_new_tokens=2)      # prefill_len == 8
    # unified mode takes it, up to max_cache
    eng_u = make_engine(cfg, unified_step=True)
    eng_u.submit(np.arange(9), max_new_tokens=2)
    with pytest.raises(ValueError, match="refusing to silently truncate"):
        eng_u.submit(np.arange(33), max_new_tokens=2)   # max_cache == 32


def test_prefill_token_stats_count_real_tokens():
    """Satellite fix: prefill tok/s no longer counts padding as work."""
    cfg = nocap(MOE_ARCH)
    eng = make_engine(cfg, unified_step=False, async_steps=False)
    eng.submit(np.arange(5), max_new_tokens=2)          # 5 real, 3 pad
    eng.run_until_done()
    assert eng.stats["prefill_tokens"] == 5
    assert eng.stats["prefill_pad_tokens"] == 3
    tp = eng.throughput()
    assert tp["prefill_padding_overhead"] == pytest.approx(3 / 8)
    eng_u = make_engine(cfg, unified_step=True, chunk_len=4,
                        async_steps=False)
    eng_u.submit(np.arange(5), max_new_tokens=2)
    eng_u.run_until_done()
    assert eng_u.stats["prefill_tokens"] == 5
    assert eng_u.stats["prefill_pad_tokens"] == 0
    assert eng_u.throughput()["prefill_padding_overhead"] == 0.0


def test_unified_decode_rows_never_stall_on_admission():
    """A decode row advances one token on EVERY iteration, even the one
    that admits and prefills a fresh long prompt (the stall-free scheduler
    property; the reference engine runs a separate prefill program first)."""
    cfg = nocap(MOE_ARCH)
    eng = make_engine(cfg, max_batch=2, prefill_len=8, max_cache=64,
                      unified_step=True, chunk_len=4, async_steps=False)
    eng.submit(np.arange(4), max_new_tokens=10)
    eng.step()          # prefill (whole 4-token prompt fits one chunk)
    eng.step()          # decode 1... (token 1 sampled at prefill)
    r1 = eng._all[1]
    n_before = len(r1.generated)
    eng.submit(np.arange(24), max_new_tokens=2)   # long prompt arrives
    eng.step()          # mixed: r1 decodes WHILE r2's first chunk prefills
    assert len(r1.generated) == n_before + 1
    assert eng.prefill_pos[1] == 4                # r2 chunk 1 of 6 done
    done = eng.run_until_done()
    assert sorted(r.uid for r in done) == [1, 2]
    assert eng.stats["mixed_s"] > 0.0             # mixed batches happened
    assert eng.throughput()["decode_stall_s"] == 0.0


def test_token_budget_smaller_than_decode_rows_never_starves_prefill():
    """Decode rows are budget-EXEMPT: even with token_budget=1 and both
    slots decoding, a queued prompt must still make prefill progress once
    a slot frees — and in-flight decode must advance every iteration."""
    cfg = nocap(MOE_ARCH)
    eng = make_engine(cfg, max_batch=2, prefill_len=8, max_cache=32,
                      unified_step=True, chunk_len=4, token_budget=1,
                      async_steps=False)
    rng = np.random.default_rng(11)
    for _ in range(3):
        eng.submit(rng.integers(0, 100, 8), max_new_tokens=4)
    done = eng.run_until_done(max_steps=200)
    assert sorted(r.uid for r in done) == [1, 2, 3]
    assert all(len(r.generated) == 4 for r in done)


def test_unified_rejects_generation_overflowing_cache():
    """prompt + max_new_tokens must fit the cache: past max_cache the
    decode writes would be silently dropped and later tokens generated
    against a truncated context — reject at submit instead."""
    cfg = nocap(MOE_ARCH)
    eng = make_engine(cfg, unified_step=True)        # max_cache == 32
    eng.submit(np.arange(28), max_new_tokens=5)      # 28 + 5 - 1 == 32: ok
    with pytest.raises(ValueError, match="does not fit"):
        eng.submit(np.arange(28), max_new_tokens=6)  # 33 > 32


# ---------------------------------------------------------------------------
# per-request sampling
# ---------------------------------------------------------------------------

def test_stochastic_decode_deterministic_and_isolated():
    """temperature>0 rows sample (reproducibly, per sample_seed); rows at
    the default temperature=0 in the SAME batch stay exactly greedy."""
    cfg = nocap(MOE_ARCH)
    rng = np.random.default_rng(5)
    p_greedy, p_hot = rng.integers(0, 100, 8), rng.integers(0, 100, 8)

    def run(hot_temp):
        eng = make_engine(cfg, unified_step=True, chunk_len=8,
                          async_steps=False)
        u1 = eng.submit(p_greedy, max_new_tokens=6)
        u2 = eng.submit(p_hot, max_new_tokens=6, temperature=hot_temp,
                        top_k=16)
        g = generations(eng.run_until_done())
        return g[u1], g[u2]

    g0, h0 = run(0.0)
    g1, h1 = run(1.5)
    g2, h2 = run(1.5)
    assert g0 == g1 == g2            # greedy row untouched by neighbour
    assert h1 == h2                  # same seed -> same sample path
    assert h1 != h0                  # sampling actually changed tokens
    assert all(0 <= t < cfg.vocab_size for t in h1)


def test_sampling_works_in_reference_mode_too():
    cfg = nocap(MOE_ARCH)
    outs = []
    for _ in range(2):
        eng = make_engine(cfg, unified_step=False, async_steps=False)
        uid = eng.submit(np.arange(8) % 100, max_new_tokens=5,
                         temperature=0.9, top_k=8)
        outs.append(generations(eng.run_until_done())[uid])
    assert outs[0] == outs[1]
    assert all(0 <= t < cfg.vocab_size for t in outs[0])


# ---------------------------------------------------------------------------
# tracker integration
# ---------------------------------------------------------------------------

def test_unified_routing_capture_feeds_tracker():
    """Mixed batches dead-route padding to the E_pad sentinel; the tracker
    must only ever see real expert ids."""
    cfg = nocap(MOE_ARCH)
    eng = make_engine(cfg, unified_step=True, chunk_len=3, async_steps=False)
    rng = np.random.default_rng(9)
    eng.submit(rng.integers(0, 100, 8), max_new_tokens=4)
    eng.step()
    eng.submit(rng.integers(0, 100, 7), max_new_tokens=3)  # mixed iterations
    eng.run_until_done()
    assert eng.tracker is not None
    e2 = eng.expected_experts_per_node(2)
    assert 0.0 < e2 <= cfg.num_experts / 2 + 1e-9
    assert eng.tracker.exec_counts.shape == (cfg.num_layers, cfg.num_experts)
    assert eng.tracker.exec_counts.sum() > 0


# ---------------------------------------------------------------------------
# grouped expert FFN selection
# ---------------------------------------------------------------------------

def test_grouped_moe_steps_counts_mixed_blocks_only(monkeypatch):
    """A (4, 40) block at dropless capacity runs the grouped expert FFN
    (capacity 640 > one 128-row tile); the (4, 1) decode block keeps the
    capacity dispatch (capacity 16).  The engine counts exactly the
    dispatched chunk-width programs."""
    from repro.core import moe
    traced = []
    grouped = moe.grouped_moe
    monkeypatch.setattr(moe, "grouped_moe", lambda ex, x, *a: (
        traced.append(x.shape[0]), grouped(ex, x, *a))[1])
    cfg = nocap(MOE_ARCH)
    eng = make_engine(cfg, max_batch=4, max_cache=64, chunk_len=40,
                      async_steps=False)
    widths = []
    jit = eng._jit_unified
    eng._jit_unified = lambda *a: (widths.append(a[2].shape[1]), jit(*a))[1]
    rng = np.random.default_rng(3)
    for n in (12, 50, 9):
        eng.submit(rng.integers(0, 100, n), max_new_tokens=4)
    eng.run_until_done()
    assert set(widths) == {1, 40}
    assert traced and set(traced) == {4 * 40}
    assert eng.stats["grouped_moe_steps"] == widths.count(40) > 0
    assert eng.stats["dispatches"] == len(widths)
