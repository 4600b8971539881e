"""Multi-device integration checks, run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (tests/test_distributed.py
drives this; the pytest main process keeps the single real CPU device).

Each check prints 'PASS <name>' or raises.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core import expert_parallel, moe as moe_lib, router as router_lib
from repro.launch import sharding
from repro.launch.mesh import make_test_mesh
from repro.models import attention
from repro.models.model import build_model
from repro import optim


def check_expert_parallel_schedules():
    """All 3 collective schedules x 2 strategies match the exact reference."""
    mesh = make_test_mesh(2, 4)
    cfg = get_config("qwen3_moe_30b_a3b").reduced()
    key = jax.random.PRNGKey(0)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts_padded
    layer_p = {
        "router": jax.random.normal(key, (d, e)) * 0.1,
        "experts": {
            "w_gate": jax.random.normal(jax.random.fold_in(key, 1), (e, d, f)) * 0.05,
            "w_up": jax.random.normal(jax.random.fold_in(key, 2), (e, d, f)) * 0.05,
            "w_down": jax.random.normal(jax.random.fold_in(key, 3), (e, f, d)) * 0.05,
        },
    }
    for b, s in ((4, 16), (4, 1)):
        x = jax.random.normal(jax.random.fold_in(key, 4 + s), (b, s, d))
        x2d = x.reshape(-1, d)
        rout = router_lib.route(layer_p["router"], x2d, cfg.experts_per_token,
                                n_valid_experts=cfg.num_experts)
        y_ref = moe_lib.reference_moe(layer_p["experts"], x2d, rout.top_idx,
                                      rout.top_w).reshape(b, s, d)
        for ep in ("decentralized", "centralized", "a2a", "a2a_pipelined"):
            for strat in ("dispatch", "dense"):
                # gather_decode_max_tk=0 keeps the dispatch path exercised
                # even at small T*K (the gather fast path is checked below)
                c = cfg.replace(expert_parallel=ep, moe_strategy=strat,
                                capacity_factor=8.0, ep_microchunks=2,
                                gather_decode_max_tk=0)
                y, aux, ti = expert_parallel.moe_layer(c, mesh, layer_p, x)
                err = float(jnp.max(jnp.abs(y - y_ref)))
                assert err < 1e-4, (ep, strat, s, err)
                assert np.isfinite(float(aux))
                # device-captured routing == single-device router decisions
                np.testing.assert_array_equal(np.asarray(ti),
                                              np.asarray(rout.top_idx))
        # capacity-free gather decode fast path on the mesh (T*K below the
        # threshold): same exact output through the decentralized schedule
        c = cfg.replace(expert_parallel="decentralized",
                        capacity_factor=8.0, gather_decode_max_tk=4096)
        y, _, _ = expert_parallel.moe_layer(c, mesh, layer_p, x)
        err = float(jnp.max(jnp.abs(y - y_ref)))
        assert err < 1e-4, ("gather", s, err)
    print("PASS expert_parallel_schedules")


def check_a2a_pipelined_token_exact():
    """ISSUE 2 tentpole (b): the microchunked comm/compute-overlapped
    schedule is token-exact against plain a2a whenever capacity is not
    binding — identical routing decisions and per-slot contractions; the
    outputs differ only by XLA's reduction-order reassociation at the
    different GEMM batch shapes (<1e-6 abs, which never flips a greedy
    token — asserted end-to-end in check_serving_engine_on_mesh).  a2a
    matches decentralized in the same regime, and the documented fallbacks
    engage (m that does not divide T_loc -> a2a; single-token decode ->
    decentralized), bitwise, since they run the same code."""
    mesh = make_test_mesh(2, 4)
    cfg = get_config("qwen3_moe_30b_a3b").reduced().replace(
        capacity_factor=8.0, gather_decode_max_tk=0)
    key = jax.random.PRNGKey(17)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts_padded
    layer_p = {
        "router": jax.random.normal(key, (d, e)) * 0.1,
        "experts": {
            "w_gate": jax.random.normal(jax.random.fold_in(key, 1), (e, d, f)) * 0.05,
            "w_up": jax.random.normal(jax.random.fold_in(key, 2), (e, d, f)) * 0.05,
            "w_down": jax.random.normal(jax.random.fold_in(key, 3), (e, f, d)) * 0.05,
        },
    }
    b, s = 4, 16                       # T_loc = (4/2)*(16/4) = 8 per shard
    x = jax.random.normal(jax.random.fold_in(key, 4), (b, s, d))
    y_a2a, _, ti_a2a = expert_parallel.moe_layer(
        cfg.replace(expert_parallel="a2a"), mesh, layer_p, x)
    y_dec, _, _ = expert_parallel.moe_layer(
        cfg.replace(expert_parallel="decentralized"), mesh, layer_p, x)
    for m in (2, 4, 8):
        c = cfg.replace(expert_parallel="a2a_pipelined", ep_microchunks=m)
        y_p, aux, ti = expert_parallel.moe_layer(c, mesh, layer_p, x)
        np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_a2a),
                                   rtol=0, atol=1e-5, err_msg=f"m={m}")
        np.testing.assert_array_equal(np.asarray(ti), np.asarray(ti_a2a))
        assert np.isfinite(float(aux))
    # a2a == decentralized token-exact under non-binding capacity
    err = float(jnp.max(jnp.abs(y_a2a - y_dec)))
    assert err < 1e-5, err
    # m=3 does not divide T_loc=8: falls back to plain a2a, still exact
    y_f, _, _ = expert_parallel.moe_layer(
        cfg.replace(expert_parallel="a2a_pipelined", ep_microchunks=3),
        mesh, layer_p, x)
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_a2a))
    # single-token decode: falls back to the decentralized schedule
    x1 = jax.random.normal(jax.random.fold_in(key, 5), (b, 1, d))
    y1_p, _, _ = expert_parallel.moe_layer(
        cfg.replace(expert_parallel="a2a_pipelined", ep_microchunks=2),
        mesh, layer_p, x1)
    y1_d, _, _ = expert_parallel.moe_layer(
        cfg.replace(expert_parallel="decentralized"), mesh, layer_p, x1)
    np.testing.assert_array_equal(np.asarray(y1_p), np.asarray(y1_d))
    print("PASS a2a_pipelined_token_exact")


def check_cp_decode_matches_single_device():
    """Sequence-sharded decode attention (shard_map online-softmax merge)
    equals the single-device decode step."""
    mesh = make_test_mesh(2, 4)
    cfg = get_config("qwen3_0_6b").reduced()
    key = jax.random.PRNGKey(1)
    p = attention.attn_init(key, cfg, jnp.float32)
    b, clen = 4, 32
    cache1 = attention.init_layer_cache(cfg, b, clen, jnp.float32)
    cache2 = {k: jnp.copy(v) for k, v in cache1.items()}
    # pre-populate with a short prefix
    for t in range(5):
        x = jax.random.normal(jax.random.fold_in(key, 10 + t),
                              (b, 1, cfg.d_model))
        lengths = jnp.full((b,), t, jnp.int32)
        o1, cache1 = attention.attn_decode_step(p, cfg, cache1, x, lengths,
                                                None)
        o2, cache2 = attention.attn_decode_step_cp(p, cfg, cache2, x, lengths,
                                                   None, mesh)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=1e-4, atol=1e-5)
        for kk in ("k", "v"):
            np.testing.assert_allclose(np.asarray(cache1[kk]),
                                       np.asarray(cache2[kk]),
                                       rtol=1e-5, atol=1e-6)
    print("PASS cp_decode")


def check_cp_decode_ring_window():
    """CP decode with a ring (sliding-window) cache matches the local path."""
    mesh = make_test_mesh(1, 8)
    cfg = get_config("recurrentgemma_2b").reduced()
    key = jax.random.PRNGKey(2)
    p = attention.attn_init(key, cfg, jnp.float32)
    b, win = 2, cfg.sliding_window
    assert win % 8 == 0, win
    c1 = attention.init_layer_cache(cfg, b, win, jnp.float32)
    c2 = {k: jnp.copy(v) for k, v in c1.items()}
    # one compiled program per path for the whole loop: called eagerly,
    # every step re-traces and re-compiles the shard_map'd CP step
    local = jax.jit(lambda c, x, n: attention.attn_decode_step(
        p, cfg, c, x, n, win))
    cp = jax.jit(lambda c, x, n: attention.attn_decode_step_cp(
        p, cfg, c, x, n, win, mesh))
    for t in range(win + 9):        # wrap the ring
        x = jax.random.normal(jax.random.fold_in(key, t), (b, 1, cfg.d_model))
        lengths = jnp.full((b,), t, jnp.int32)
        o1, c1 = local(c1, x, lengths)
        o2, c2 = cp(c2, x, lengths)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=1e-4, atol=1e-5, err_msg=f"t={t}")
    print("PASS cp_decode_ring")


def check_sharded_train_step_matches_single():
    """2 sharded train steps == 2 unsharded train steps (same loss curve)."""
    mesh = make_test_mesh(2, 4)
    cfg = get_config("qwen3_moe_30b_a3b").reduced().replace(
        capacity_factor=8.0)
    model = build_model(cfg)
    params0 = model.init(jax.random.PRNGKey(3))
    ocfg = optim.OptimizerConfig(lr=1e-3, warmup_steps=0, total_steps=10)
    key = jax.random.PRNGKey(4)
    b, s = 8, 16
    batches = [{"tokens": jax.random.randint(jax.random.fold_in(key, i),
                                             (b, s), 0, cfg.vocab_size),
                "labels": jax.random.randint(jax.random.fold_in(key, 99 + i),
                                             (b, s), 0, cfg.vocab_size)}
               for i in range(2)]

    def run(mesh_):
        params = jax.tree.map(jnp.copy, params0)
        opt = optim.init(params)
        if mesh_ is not None:
            spec = sharding.params_pspec(cfg, mesh_, params, mode="train")
            params = jax.device_put(params, sharding.named(mesh_, spec))
            opt = jax.device_put(opt, sharding.named(
                mesh_, sharding.opt_pspec(cfg, mesh_, opt, spec)))

        @jax.jit
        def step(params, opt, batch):
            (l, m), g = jax.value_and_grad(model.loss, has_aux=True)(
                params, batch, mesh_)
            params, opt, _ = optim.update(ocfg, g, opt, params)
            return params, opt, l

        losses = []
        for bt in batches:
            params, opt, l = step(params, opt, bt)
            losses.append(float(l))
        return losses

    l_single = run(None)
    l_shard = run(mesh)
    np.testing.assert_allclose(l_single, l_shard, rtol=2e-3, atol=2e-3)
    print("PASS sharded_train_step")


def check_params_pspec_structure():
    """Sharding specs: experts on model axis; attention replicated when heads
    do not divide; vocab sharded."""
    from jax.sharding import PartitionSpec as P
    mesh = make_test_mesh(2, 4)
    cfg = get_config("qwen3_moe_30b_a3b")
    model = build_model(cfg)
    p_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    spec = sharding.params_pspec(cfg, mesh, p_sds, mode="serve")
    assert spec["embed"] == P("model", None)
    assert spec["blocks"]["experts"]["w_gate"] == P(None, "model", None, None)
    assert spec["blocks"]["attn"]["wq"][2] == "model"      # 32 heads % 4 == 0
    assert spec["blocks"]["attn"]["wk"][2] == "model"      # 4 kv % 4 == 0
    # vlm: 28 heads % 4 == 0 -> sharded; but % 16 on prod mesh is not:
    cfg_vlm = get_config("qwen2_vl_7b")
    m_vlm = build_model(cfg_vlm)
    sds = jax.eval_shape(m_vlm.init, jax.random.PRNGKey(0))
    sp = sharding.params_pspec(cfg_vlm, mesh, sds, mode="serve")
    assert sp["blocks"]["attn"]["wq"][2] == "model"        # 28 % 4 == 0 here
    print("PASS params_pspec_structure")


def check_data_sharded_batch():
    from repro.data.pipeline import Pipeline, PipelineConfig, shard_batch
    mesh = make_test_mesh(4, 2)
    pipe = Pipeline(PipelineConfig(seq_len=16, global_batch=8, vocab_size=64))
    b = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
    sb = shard_batch(b, mesh)
    assert sb["tokens"].sharding.spec[0] in ("data", ("data",))
    print("PASS data_sharded_batch")


def check_padded_experts_dead_on_mesh():
    """granite-style expert padding: 6 real experts padded to 8 so they
    divide a 4-way expert-parallel axis; padded experts carry zero weights
    and -inf router logits — output must equal the 6-expert reference."""
    mesh = make_test_mesh(2, 4)
    cfg = get_config("granite_moe_3b_a800m").reduced().replace(
        num_experts=6, num_experts_padded=8, experts_per_token=2,
        capacity_factor=8.0)
    key = jax.random.PRNGKey(7)
    d, f = cfg.d_model, cfg.d_ff
    real = {
        "w_gate": jax.random.normal(jax.random.fold_in(key, 1), (6, d, f)) * 0.05,
        "w_up": jax.random.normal(jax.random.fold_in(key, 2), (6, d, f)) * 0.05,
        "w_down": jax.random.normal(jax.random.fold_in(key, 3), (6, f, d)) * 0.05,
    }
    from repro.core import prestack
    layer_p = {
        "router": jnp.pad(jax.random.normal(key, (d, 6)) * 0.1,
                          ((0, 0), (0, 2))),
        "experts": prestack.pad_experts(real, 8),
    }
    x = jax.random.normal(jax.random.fold_in(key, 4), (4, 8, d))
    x2d = x.reshape(-1, d)
    rout = router_lib.route(layer_p["router"][:, :6], x2d,
                            cfg.experts_per_token)
    y_ref = moe_lib.reference_moe(real, x2d, rout.top_idx,
                                  rout.top_w).reshape(4, 8, d)
    for ep in ("decentralized", "centralized", "a2a", "a2a_pipelined"):
        c = cfg.replace(expert_parallel=ep, ep_microchunks=2)
        y, _, _ = expert_parallel.moe_layer(c, mesh, layer_p, x)
        err = float(jnp.max(jnp.abs(y - y_ref)))
        assert err < 1e-4, (ep, err)
    print("PASS padded_experts")


def check_expert_replication_overlap():
    """Paper §5.3 overlapping placement: r=2 replicas on an 8-way expert
    axis must produce the exact single-copy output (each token served by
    exactly one replica) while halving per-shard capacity."""
    mesh = make_test_mesh(1, 8)
    cfg = get_config("qwen3_moe_30b_a3b").reduced().replace(
        num_experts=8, num_experts_padded=8, experts_per_token=2,
        capacity_factor=8.0)
    key = jax.random.PRNGKey(11)
    d, f, e = cfg.d_model, cfg.d_ff, 8
    experts = {
        "w_gate": jax.random.normal(jax.random.fold_in(key, 1), (e, d, f)) * 0.05,
        "w_up": jax.random.normal(jax.random.fold_in(key, 2), (e, d, f)) * 0.05,
        "w_down": jax.random.normal(jax.random.fold_in(key, 3), (e, f, d)) * 0.05,
    }
    router_w = jax.random.normal(key, (d, e)) * 0.1
    x = jax.random.normal(jax.random.fold_in(key, 4), (2, 16, d))
    x2d = x.reshape(-1, d)
    rout = router_lib.route(router_w, x2d, cfg.experts_per_token)
    y_ref = moe_lib.reference_moe(experts, x2d, rout.top_idx,
                                  rout.top_w).reshape(2, 16, d)

    # r=1 baseline
    y1, _, _ = expert_parallel.moe_layer(
        cfg, mesh, {"router": router_w, "experts": experts}, x)
    # r=2 overlapping placement (duplicated expert stack)
    dup = jax.tree.map(lambda a: jnp.concatenate([a, a], axis=0), experts)
    y2, _, _ = expert_parallel.moe_layer(
        cfg.replace(expert_replication=2), mesh,
        {"router": router_w, "experts": dup}, x)
    for name, y in (("r1", y1), ("r2", y2)):
        err = float(jnp.max(jnp.abs(y - y_ref)))
        assert err < 1e-4, (name, err)
    print("PASS expert_replication")


def check_cp_decode_int8_cache():
    """CP decode with int8 quantized cache == single-device int8 decode."""
    mesh = make_test_mesh(2, 4)
    cfg = get_config("qwen3_0_6b").reduced().replace(kv_cache_dtype="int8")
    key = jax.random.PRNGKey(21)
    p = attention.attn_init(key, cfg, jnp.float32)
    b, clen = 4, 32
    c1 = attention.init_layer_cache(cfg, b, clen, jnp.float32)
    c2 = jax.tree.map(jnp.copy, c1)
    for t in range(6):
        x = jax.random.normal(jax.random.fold_in(key, 30 + t),
                              (b, 1, cfg.d_model))
        lengths = jnp.full((b,), t, jnp.int32)
        o1, c1 = attention.attn_decode_step(p, cfg, c1, x, lengths, None)
        o2, c2 = attention.attn_decode_step_cp(p, cfg, c2, x, lengths, None,
                                               mesh)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(c1["k"]), np.asarray(c2["k"]))
    print("PASS cp_decode_int8")


def check_serving_engine_on_mesh():
    """End-to-end distributed serving (the paper's system): the engine on a
    (2,4) mesh with expert-parallel MoE + sharded params generates the same
    tokens as the single-device engine."""
    from repro.serving.engine import EngineConfig, ServingEngine
    mesh = make_test_mesh(2, 4)
    base = get_config("qwen3_moe_30b_a3b").reduced().replace(
        capacity_factor=8.0, kv_cache_shard="none")
    ecfg = EngineConfig(max_batch=2, prefill_len=8, max_cache=24,
                        track_experts=False)
    prompts = [np.arange(5) % base.vocab_size,
               (np.arange(7) * 3) % base.vocab_size]

    # decentralized = the paper's design; a2a_pipelined = the overlapped
    # schedule end-to-end (prefill pipelines, decode falls back); both run
    # with donation + the gather decode fast path (engine defaults)
    for ep in ("decentralized", "a2a_pipelined"):
        cfg = base.replace(expert_parallel=ep, ep_microchunks=2)
        outs = {}
        for name, m in (("single", None), ("mesh", mesh)):
            eng = ServingEngine(cfg, ecfg, rng=jax.random.PRNGKey(5), mesh=m)
            for p_ in prompts:
                eng.submit(p_, max_new_tokens=4)
            done = sorted(eng.run_until_done(), key=lambda r: r.uid)
            outs[name] = [r.generated for r in done]
        assert outs["single"] == outs["mesh"], (ep, outs)
    print("PASS serving_engine_on_mesh")


def check_quantized_weights_on_mesh():
    """ISSUE 5: int8-quantized expert shards ride the expert-parallel
    schedules unchanged — QuantTensor payload+scale leaves shard over the
    expert axis through the same rank-3 in_specs, activations stay fp, and
    the mesh engine generates the same tokens as the single-device engine
    serving the same quantized store (which in turn is token-identical to
    the fake-quant fp reference, tests/test_quant.py)."""
    from repro.serving.engine import EngineConfig, ServingEngine
    mesh = make_test_mesh(2, 4)
    base = get_config("qwen3_moe_30b_a3b").reduced().replace(
        capacity_factor=8.0, kv_cache_shard="none", weight_quant="int8",
        weight_quant_block=64)
    ecfg = EngineConfig(max_batch=2, prefill_len=8, max_cache=24,
                        track_experts=False)
    prompts = [np.arange(5) % base.vocab_size,
               (np.arange(7) * 3) % base.vocab_size]
    for ep in ("decentralized", "a2a_pipelined"):
        cfg = base.replace(expert_parallel=ep, ep_microchunks=2)
        outs = {}
        for name, m in (("single", None), ("mesh", mesh)):
            eng = ServingEngine(cfg, ecfg, rng=jax.random.PRNGKey(5), mesh=m)
            from repro.core import quant as quant_lib
            assert any(isinstance(l, quant_lib.QuantTensor)
                       for l in jax.tree.leaves(
                           eng.params,
                           is_leaf=lambda x: isinstance(x, quant_lib.QuantTensor)))
            for p_ in prompts:
                eng.submit(p_, max_new_tokens=4)
            done = sorted(eng.run_until_done(), key=lambda r: r.uid)
            outs[name] = [r.generated for r in done]
        assert outs["single"] == outs["mesh"], (ep, outs)
    print("PASS quantized_weights_on_mesh")


def check_analysis_rules_on_mesh():
    """ISSUE 6: the static analyzer's mesh-aware rules hold on the real
    8-device serving programs — every donated cache leaf aliases (R1), the
    per-kind collective bytes match core/perf_model's schedule + serve-mode
    TP prediction within tolerance (R2), and no expert-weight slice is ever
    all-gathered (R6)."""
    from repro.analysis import programs as programs_lib
    from repro.analysis.collectives import CollectiveBudgetRule
    from repro.analysis.donation import DonationAliasRule
    from repro.analysis.framework import run_rules
    from repro.analysis.sharding_lint import ShardingLintRule
    from repro.core import perf_model
    from repro.launch import hlo as hlo_lib

    mesh = make_test_mesh(2, 4)
    cfg_kw = dict(capacity_factor=8.0, kv_cache_shard="none")
    progs = [programs_lib.trace_program(v, mesh=mesh, cfg_kw=cfg_kw)
             for v in ("unified", "decode")]
    rep = run_rules([DonationAliasRule(), CollectiveBudgetRule(),
                     ShardingLintRule()], progs)
    assert rep.ok, rep.summary()
    # non-vacuous: the programs really contain the predicted expert psum
    # traffic, and the prediction is nonzero on this mesh
    for prog in progs:
        assert hlo_lib.analyze(prog.hlo_text).coll["all-reduce"] > 0, prog.name
        pred = perf_model.predicted_collective_bytes(
            prog.cfg, batch=prog.batch, seq=prog.seq,
            n_exp_shards=prog.n_exp_shards,
            n_batch_shards=prog.n_batch_shards)
        assert pred.get("all-reduce", 0) > 0, prog.name
    print("PASS analysis_rules_on_mesh")


CHECKS = [
    check_expert_parallel_schedules,
    check_a2a_pipelined_token_exact,
    check_padded_experts_dead_on_mesh,
    check_expert_replication_overlap,
    check_serving_engine_on_mesh,
    check_quantized_weights_on_mesh,
    check_cp_decode_int8_cache,
    check_cp_decode_matches_single_device,
    check_cp_decode_ring_window,
    check_sharded_train_step_matches_single,
    check_params_pspec_structure,
    check_data_sharded_batch,
    check_analysis_rules_on_mesh,
]


def main():
    names = sys.argv[1:]
    for c in CHECKS:
        if names and c.__name__ not in names:
            continue
        c()
    print("ALL_OK")


if __name__ == "__main__":
    main()
