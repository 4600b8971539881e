"""Unit + property tests for the paper's core: router, dispatch plan,
MoE strategies, prestacking, dynamic loading."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:  # requirements-dev.txt; degrade to fixed samples when absent
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import dynamic_load, moe, prestack, router


def rand_experts(key, e, d, f, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    s = 0.05
    return {"w_gate": jax.random.normal(ks[0], (e, d, f), dtype) * s,
            "w_up": jax.random.normal(ks[1], (e, d, f), dtype) * s,
            "w_down": jax.random.normal(ks[2], (e, f, d), dtype) * s}


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

def test_router_topk_selects_highest_probs():
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (16, 8))
    x = jax.random.normal(jax.random.fold_in(key, 1), (32, 16))
    out = router.route(w, x, k=2, norm_topk=False)
    probs = np.asarray(out.probs)
    for t in range(32):
        top2 = set(np.argsort(probs[t])[-2:])
        assert set(np.asarray(out.top_idx[t])) == top2


def test_router_dead_expert_masking():
    """Padded experts (granite 40->48) must never be selected."""
    key = jax.random.PRNGKey(1)
    w = jax.random.normal(key, (16, 12))
    x = jax.random.normal(jax.random.fold_in(key, 1), (64, 16))
    out = router.route(w, x, k=4, n_valid_experts=9)
    assert int(jnp.max(out.top_idx)) < 9
    assert float(jnp.sum(out.probs[:, 9:])) < 1e-6


def test_router_norm_topk_weights_sum_to_one():
    key = jax.random.PRNGKey(2)
    out = router.route(jax.random.normal(key, (8, 6)),
                       jax.random.normal(jax.random.fold_in(key, 1), (10, 8)),
                       k=3, norm_topk=True)
    np.testing.assert_allclose(np.asarray(jnp.sum(out.top_w, -1)), 1.0,
                               rtol=1e-5)


def test_aux_loss_uniform_routing_is_one():
    """Perfectly uniform router probs give aux = 1 (Switch normalization)."""
    t, e, k = 128, 8, 2
    probs = jnp.full((t, e), 1.0 / e)
    idx = jnp.stack([jnp.arange(t) % e, (jnp.arange(t) + 1) % e], -1)
    aux = router.load_balance_loss(probs, idx.astype(jnp.int32), e)
    np.testing.assert_allclose(float(aux), 1.0, rtol=1e-5)


# ---------------------------------------------------------------------------
# dispatch plan — hypothesis property tests
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(
    t=st.integers(1, 48),
    k=st.integers(1, 4),
    e=st.sampled_from([4, 8, 16]),
    cap=st.sampled_from([1, 2, 8, 64]),
    seed=st.integers(0, 2**31 - 1),
)
def test_dispatch_plan_properties(t, k, e, cap, seed):
    key = jax.random.PRNGKey(seed)
    top_idx = jax.random.randint(key, (t, k), 0, e).astype(jnp.int32)
    e_local = e // 2
    e_start = e_local            # second "node" owns experts [e/2, e)
    tok, valid, slot_of = moe.make_dispatch_plan(top_idx, e, e_start,
                                                 e_local, cap)
    tok, valid, slot_of = map(np.asarray, (tok, valid, slot_of))
    nbuf = e_local * cap
    top = np.asarray(top_idx)

    # 1. every valid slot holds a token routed to that slot's expert
    for s in range(nbuf):
        if valid[s]:
            expert = e_start + s // cap
            assert expert in top[tok[s]], (s, tok[s], expert)
    # 2. slot_of either points into the buffer at the right expert or == nbuf
    for tt in range(t):
        for kk in range(k):
            s = slot_of[tt, kk]
            expert = top[tt, kk]
            local = e_start <= expert < e_start + e_local
            if s < nbuf:
                assert local
                assert s // cap == expert - e_start
                assert valid[s] and tok[s] == tt
            else:
                assert s == nbuf
    # 3. no slot is claimed twice
    claimed = slot_of[slot_of < nbuf]
    assert len(np.unique(claimed)) == len(claimed)
    # 4. per-expert capacity respected
    for le in range(e_local):
        assert valid[le * cap:(le + 1) * cap].sum() <= cap


@settings(max_examples=30, deadline=None)
@given(
    t=st.integers(1, 48),
    k=st.integers(1, 4),
    e=st.sampled_from([4, 8, 16]),
    cap=st.sampled_from([1, 2, 4]),     # small: force capacity pressure
    seed=st.integers(0, 2**31 - 1),
)
def test_dispatch_drops_deterministic_and_stable_ordered(t, k, e, cap, seed):
    """Under capacity pressure, ``make_dispatch_plan`` drops must be (1)
    deterministic — identical plans on identical inputs — and (2)
    stable-ordered: each expert keeps exactly the FIRST ``cap`` routing
    decisions in flat row-major (t, k) order and drops the rest, with slot
    ranks following that order.  The serving engine's batched==sequential
    token equality and the a2a/decentralized schedule equivalence both rest
    on this invariant."""
    key = jax.random.PRNGKey(seed)
    top_idx = jax.random.randint(key, (t, k), 0, e).astype(jnp.int32)
    plan_a = moe.make_dispatch_plan(top_idx, e, 0, e, cap)
    plan_b = moe.make_dispatch_plan(top_idx, e, 0, e, cap)
    for a, b in zip(plan_a, plan_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    tok, valid, slot_of = map(np.asarray, plan_a)
    nbuf = e * cap
    flat = np.asarray(top_idx).reshape(-1)
    flat_slot = slot_of.reshape(-1)
    for ex in range(e):
        decisions = np.nonzero(flat == ex)[0]          # flat row-major order
        kept = [i for i in decisions if flat_slot[i] < nbuf]
        # first-come-first-kept, everything past capacity dropped
        assert kept == list(decisions[:cap])
        # ranks are assigned in arrival order within the expert's slots
        slots = [flat_slot[i] for i in kept]
        assert slots == sorted(slots)
        for i in decisions[cap:]:
            assert flat_slot[i] == nbuf                # dropped sentinel


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), t=st.sampled_from([8, 32]))
def test_dispatch_moe_matches_reference_at_high_capacity(seed, t):
    key = jax.random.PRNGKey(seed)
    e, d, f, k = 4, 16, 32, 2
    experts = rand_experts(jax.random.fold_in(key, 1), e, d, f)
    x = jax.random.normal(jax.random.fold_in(key, 2), (t, d))
    w = jax.random.normal(jax.random.fold_in(key, 3), (d, e))
    out = router.route(w, x, k)
    y_ref = moe.reference_moe(experts, x, out.top_idx, out.top_w)
    cap = moe.round_capacity(t, k, e, 8.0)
    y = moe.dispatch_moe(experts, x, out.top_idx, out.top_w, e, 0, cap)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)


def test_dense_moe_matches_reference():
    key = jax.random.PRNGKey(7)
    e, d, f, k, t = 8, 16, 32, 2, 24
    experts = rand_experts(jax.random.fold_in(key, 1), e, d, f)
    x = jax.random.normal(jax.random.fold_in(key, 2), (t, d))
    w = jax.random.normal(jax.random.fold_in(key, 3), (d, e))
    out = router.route(w, x, k)
    y_ref = moe.reference_moe(experts, x, out.top_idx, out.top_w)
    # split into two "nodes" and sum partials — the paper's fork-join
    y = sum(moe.dense_moe(jax.tree.map(lambda a: a[n * 4:(n + 1) * 4], experts),
                          x, out.top_idx, out.top_w, e_start=n * 4)
            for n in range(2))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), t=st.sampled_from([1, 4, 16]))
def test_gather_moe_matches_reference(seed, t):
    """Capacity-free gather fast path == exact per-token reference, both on
    a single shard and as two half-shard partial sums."""
    key = jax.random.PRNGKey(seed)
    e, d, f, k = 8, 16, 32, 2
    experts = rand_experts(jax.random.fold_in(key, 1), e, d, f)
    x = jax.random.normal(jax.random.fold_in(key, 2), (t, d))
    w = jax.random.normal(jax.random.fold_in(key, 3), (d, e))
    out = router.route(w, x, k)
    y_ref = moe.reference_moe(experts, x, out.top_idx, out.top_w)
    y1 = moe.gather_moe(experts, x, out.top_idx, out.top_w, e_start=0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)
    y2 = sum(moe.gather_moe(jax.tree.map(lambda a: a[n * 4:(n + 1) * 4],
                                         experts),
                            x, out.top_idx, out.top_w, e_start=n * 4)
             for n in range(2))
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)


def test_gather_moe_dead_sentinel_contributes_zero():
    """_mask_rout dead-routes tokens to index E (one past the padded expert
    range); the gather path must clip the index and zero the weight."""
    key = jax.random.PRNGKey(13)
    e, d, f = 4, 8, 16
    experts = rand_experts(key, e, d, f)
    x = jax.random.normal(jax.random.fold_in(key, 1), (3, d))
    top_idx = jnp.array([[0, 1], [e, e], [2, e]], jnp.int32)  # E = sentinel
    top_w = jnp.where(top_idx < e, 0.5, 0.0)
    y = moe.gather_moe(experts, x, top_idx, top_w, e_start=0)
    assert bool(jnp.all(jnp.isfinite(y)))
    np.testing.assert_allclose(np.asarray(y[1]), 0.0, atol=1e-7)


def test_capacity_drop_degrades_gracefully():
    """cap=1 drops tokens (paper: overflow) but output stays finite and
    close in norm for the surviving fraction."""
    key = jax.random.PRNGKey(8)
    e, d, f, k, t = 4, 8, 16, 2, 64
    experts = rand_experts(jax.random.fold_in(key, 1), e, d, f)
    x = jax.random.normal(jax.random.fold_in(key, 2), (t, d))
    w = jax.random.normal(jax.random.fold_in(key, 3), (d, e))
    out = router.route(w, x, k)
    y = moe.dispatch_moe(experts, x, out.top_idx, out.top_w, e, 0, 1)
    assert bool(jnp.all(jnp.isfinite(y)))


# ---------------------------------------------------------------------------
# grouped (dropless sort-by-expert) form
# ---------------------------------------------------------------------------

def _grouped_routing(case, key):
    """(t, k, global experts, e_start, top_idx, top_w) for a routing case,
    on a shard of 8 local experts."""
    e_loc = 8
    t, k, e_total, e_start = 24, 2, 8, 0
    if case == "group_over_tile":
        t = 160                       # expert 3 gets 160 rows: two tiles
    if case == "offset_shard":
        e_total, e_start = 16, 8      # half the picks are another shard's
    scores = jax.random.normal(key, (t, e_total))
    if case == "group_over_tile":
        scores = scores.at[:, 3].set(100.0)
    if case == "empty_groups":
        scores = scores.at[:, 4:].set(-100.0)    # experts 4-7 get no row
    if case == "one_expert":
        k = 1
        scores = scores.at[:, 5].set(100.0)
    top_w, top_idx = jax.lax.top_k(jax.nn.softmax(scores), k)
    top_idx = top_idx.astype(jnp.int32)
    if case == "dead_routed":
        # _mask_rout's padding rows: sentinel index, zero weight
        dead = (jnp.arange(t) % 3 == 0)[:, None]
        top_idx = jnp.where(dead, e_total, top_idx)
        top_w = jnp.where(dead, 0.0, top_w)
    return t, k, e_total, e_start, top_idx, top_w, e_loc


@pytest.mark.parametrize("case", ["dead_routed", "offset_shard",
                                  "empty_groups", "one_expert",
                                  "group_over_tile", "stacked_layer"])
def test_grouped_moe_matches_dispatch_and_reference(case):
    """At dropless capacity the grouped form computes the capacity
    layout's sum, and the exact per-token reference's over the shard's
    own experts; given stacks over layers and a layer index, it computes
    that layer's."""
    key = jax.random.PRNGKey(21)
    t, k, e_total, e_start, top_idx, top_w, e_loc = _grouped_routing(
        case, jax.random.fold_in(key, 1))
    d, f = 16, 32
    full = rand_experts(jax.random.fold_in(key, 2), e_total, d, f)
    experts = jax.tree.map(lambda a: a[e_start:e_start + e_loc], full)
    x = jax.random.normal(jax.random.fold_in(key, 3), (t, d))
    if case == "stacked_layer":
        stack = jax.tree.map(lambda a: jnp.stack([-a, a, 2 * a]), experts)
        y = moe.grouped_moe(stack, x, top_idx, top_w, e_start, layer=1)
    else:
        y = moe.grouped_moe(experts, x, top_idx, top_w, e_start)
    y_disp = moe.dispatch_moe(experts, x, top_idx, top_w, e_total, e_start,
                              capacity=t)
    local = (top_idx >= e_start) & (top_idx < e_start + e_loc)
    y_ref = moe.reference_moe(full, x, top_idx, jnp.where(local, top_w, 0.))
    assert bool(jnp.all(jnp.isfinite(y)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_disp),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("matmul", ["interpret_kernel", "nan_past_groups"])
def test_grouped_rows_past_the_groups_never_reach_the_sum(matmul,
                                                          monkeypatch):
    """The grouped GEMM leaves rows past ``sum(group_sizes)`` unwritten.
    On the CPU its interpret-mode kernel agrees with ``lax.ragged_dot``
    inside the groups and leaves NaN past them; a GEMM that fills those
    rows with NaN still gives ``grouped_moe`` the reference's sum."""
    from repro.kernels import ops
    key = jax.random.PRNGKey(5)
    if matmul == "interpret_kernel":
        lhs = jax.random.normal(key, (200, 16))
        rhs = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, 32))
        sizes = jnp.array([10, 0, 130, 20], jnp.int32)       # 160 of 200
        out = np.asarray(ops.grouped_matmul(lhs, rhs, sizes,
                                            moe.GROUPED_ROW_TILE))
        ref = np.asarray(jax.lax.ragged_dot(lhs, rhs, sizes))
        np.testing.assert_allclose(out[:160], ref[:160], rtol=1e-5,
                                   atol=1e-5)
        assert np.isnan(out[160:]).any()
        return

    def nan_past_groups(lhs, rhs, sizes, row_tile, rhs_offset=None):
        out = jax.lax.ragged_dot(lhs, rhs, sizes,
                                 preferred_element_type=jnp.float32)
        past = jnp.arange(lhs.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(ops, "grouped_matmul", nan_past_groups)
    t, k, e_total, e_start, top_idx, top_w, e_loc = _grouped_routing(
        "dead_routed", jax.random.fold_in(key, 2))
    experts = rand_experts(jax.random.fold_in(key, 3), e_loc, 16, 32)
    x = jax.random.normal(jax.random.fold_in(key, 4), (t, 16))
    y = moe.grouped_moe(experts, x, top_idx, top_w, e_start)
    y_ref = moe.reference_moe(experts, x, top_idx, top_w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)


def _cell_config(arch, **kw):
    from repro.configs.base import get_config
    cfg = get_config(arch)
    # the benchmark's dropless capacity: every expert holds the whole block
    return cfg.replace(
        capacity_factor=cfg.num_experts_padded / cfg.experts_per_token, **kw)


@pytest.mark.parametrize("arch, b, s, quantized, cf, form", [
    ("qwen3_moe_30b_a3b", 32, 32, False, None, "grouped"),    # mixed step
    ("qwen3_moe_30b_a3b", 32, 1, False, None, "dispatch"),    # decode step
    ("granite_moe_3b_a800m", 8, 32, False, None, "grouped"),
    ("granite_moe_3b_a800m", 8, 1, False, None, "dispatch"),
    ("qwen3_moe_30b_a3b", 32, 32, False, 1.25, "dispatch"),   # binding
    ("qwen3_moe_30b_a3b", 32, 32, True, None, "dispatch"),    # QuantTensor
])
def test_local_moe_form_by_shape(arch, b, s, quantized, cf, form,
                                 monkeypatch):
    """``grouped_applies`` and the form ``_local_moe`` traces agree at the
    benchmark cells' blocks; binding capacity and quantized expert
    weights keep the capacity dispatch."""
    from repro.core import expert_parallel, quant
    cfg = _cell_config(arch)
    if cf is not None:
        cfg = cfg.replace(capacity_factor=cf)
    e, d, f, k = (cfg.num_experts_padded, cfg.d_model, cfg.d_ff,
                  cfg.experts_per_token)
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    experts = {"w_gate": sds(e, d, f), "w_up": sds(e, d, f),
               "w_down": sds(e, f, d)}
    if quantized:
        experts = jax.eval_shape(
            lambda ex: jax.tree.map(quant.quantize, ex), experts)
    t, cap, _ = expert_parallel._local_block(cfg, None, b, s)
    assert moe.grouped_applies(t, cap, experts) == (form == "grouped")
    assert expert_parallel.block_form(cfg, None, b, s, experts) == form
    ran = []
    for name in ("grouped_moe", "dispatch_moe", "gather_moe", "dense_moe"):
        real = getattr(moe, name)
        monkeypatch.setattr(moe, name, lambda *a, _n=name, _f=real, **kw: (
            ran.append(_n), _f(*a, **kw))[1])
    rout = router.RouterOut(jax.ShapeDtypeStruct((t, k), jnp.int32),
                            jax.ShapeDtypeStruct((t, k), jnp.float32),
                            None, None)
    jax.eval_shape(lambda ex, x, r: expert_parallel._local_moe(
        cfg, ex, x, r, 0, cap, sharded=False), experts, sds(t, d), rout)
    assert ran == [f"{form}_moe"]


# ---------------------------------------------------------------------------
# prestacking (C2)
# ---------------------------------------------------------------------------

def test_prestack_roundtrip():
    key = jax.random.PRNGKey(9)
    blocks = {"w": jax.random.normal(key, (4, 8, 8)),
              "b": jax.random.normal(jax.random.fold_in(key, 1), (4, 8))}
    assert prestack.validate_roundtrip(blocks)


def test_pad_experts_router_dead():
    experts = {"w_gate": jnp.ones((5, 4, 8))}
    padded = prestack.pad_experts(experts, 8)
    assert padded["w_gate"].shape == (8, 4, 8)
    assert float(jnp.sum(jnp.abs(padded["w_gate"][5:]))) == 0.0


# ---------------------------------------------------------------------------
# dynamic loading (L_R host half)
# ---------------------------------------------------------------------------

def test_quota_topup_paper_example():
    """Fig. 6b: node2 selects 1 expert, node1 selects 3 -> node2 tops up to 3
    with its LRU experts."""
    sel = [[0, 1, 2], [5]]
    lru = [[3, 0, 1, 2], [7, 6, 4, 5]]
    out = dynamic_load.quota_topup(sel, lru)
    assert out[0] == [0, 1, 2]
    assert out[1] == [5, 7, 6]          # LRU order, skipping already-selected
    assert len(out[1]) == len(out[0])


def test_quota_topup_equal_loads_noop():
    sel = [[0, 1], [4, 5]]
    lru = [[2, 3, 0, 1], [6, 7, 4, 5]]
    assert dynamic_load.quota_topup(sel, lru) == [[0, 1], [4, 5]]


def test_lru_tracker_orders_by_staleness():
    tr = dynamic_load.LRUExpertTracker(num_layers=1, num_experts=4)
    tr.observe(0, [1]); tr.tick()
    tr.observe(0, [3]); tr.tick()
    tr.observe(0, [1]); tr.tick()
    order = list(tr.lru_order(0))
    # 0 and 2 never used (step 0), then 3 (step 1), then 1 (step 2)
    assert order.index(3) > order.index(0)
    assert order.index(1) > order.index(3)


def test_simulated_expected_experts_bounds():
    """E[#exec experts/node/layer] lies between the no-topup analytic value
    and E/n, and decreases with more nodes (paper Table 1 trend)."""
    vals = {}
    for n in (2, 4):
        v = dynamic_load.simulate_expected_experts(16, 4, n, n_tokens=300,
                                                   use_topup=False)
        lo = 0.9 * dynamic_load.np.float64(
            __import__("repro.core.perf_model", fromlist=["x"])
            .expected_experts_per_node(16, 4, n))
        assert v >= lo
        assert v <= 16 / n + 1e-9
        vals[n] = v
    assert vals[4] < vals[2]
