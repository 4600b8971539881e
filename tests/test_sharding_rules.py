"""Production-mesh PartitionSpec rules, checked against the divisibility
decisions recorded in docs/DESIGN.md §4 — on an AbstractMesh (no devices)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.base import ARCH_IDS, get_config
from repro.launch import sharding
from repro.models.model import build_model

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def specs_for(arch, mode, mesh=MESH):
    cfg = get_config(arch)
    sds = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    return cfg, sds, sharding.params_pspec(cfg, mesh, sds, mode=mode)


def test_moe_experts_on_model_axis():
    """The paper's expert parallelism: expert dim sharded over 'model'."""
    for arch in ("qwen3_moe_30b_a3b", "granite_moe_3b_a800m"):
        _, _, sp = specs_for(arch, "serve")
        for w in ("w_gate", "w_up", "w_down"):
            assert sp["blocks"]["experts"][w][1] == "model", (arch, w)
        assert sp["blocks"]["router"] == P(None, None, None)


def test_vocab_sharded_everywhere():
    for arch in ARCH_IDS:
        _, _, sp = specs_for(arch, "serve")
        assert sp["embed"][0] == "model", arch


def test_deepseek_gqa_divisibility():
    """64 q heads divide 16 -> wq sharded; 8 kv heads do not -> serve mode
    shards the flattened Hkv*hd dim instead (perf iteration A5)."""
    _, _, sp = specs_for("deepseek_67b", "serve")
    assert sp["blocks"]["attn"]["wq"][2] == "model"
    assert sp["blocks"]["attn"]["wk"][2] == "model"   # flattened 1024 % 16
    assert sp["blocks"]["attn"]["wo"][1] == "model"


def test_train_mode_adds_fsdp_axis():
    cfg, _, sp = specs_for("qwen2_72b", "train")
    assert sp["blocks"]["attn"]["wq"] == P(None, "data", "model")
    assert sp["blocks"]["mlp"]["w_down"][1] == "model"
    assert sp["blocks"]["mlp"]["w_down"][2] == "data"
    assert sp["embed"] == P("model", "data")


def test_serve_mode_no_fsdp():
    _, _, sp = specs_for("qwen2_72b", "serve")
    assert "data" not in jax.tree.leaves(
        jax.tree.map(lambda s: tuple(a for a in s if a), sp,
                     is_leaf=lambda x: isinstance(x, P)))


def test_mamba_weights_replicated_over_model():
    """130M SSM: 24 heads % 16 != 0 -> replicated over model (DESIGN §4)."""
    _, _, sp = specs_for("mamba2_130m", "serve")
    blk = sp["blocks"]["mamba"]
    for name in ("in_proj", "conv_w", "A_log", "norm", "out_proj"):
        assert "model" not in tuple(a for a in blk[name] if a), name


def test_rglru_channel_sharding():
    """lru_width 2560 % 16 == 0 -> recurrent channels sharded (DESIGN §4)."""
    _, _, sp = specs_for("recurrentgemma_2b", "serve")
    rec = sp["blocks"]["rec"]["mix"]
    assert rec["in_x"][2] == "model"
    assert rec["out"][1] == "model"


def test_qwen2_vl_heads():
    """28 heads % 16 != 0 -> attention q replicated, FFN carries the TP."""
    _, _, sp = specs_for("qwen2_vl_7b", "serve")
    assert sp["blocks"]["attn"]["wq"][2] is None
    assert sp["blocks"]["mlp"]["w_gate"][2] == "model"  # 18944 % 16 == 0


def test_multi_pod_specs_compatible():
    """The same rules produce valid specs on the 512-chip multi-pod mesh
    (the 'pod' axis is a pure data axis — never appears in param specs)."""
    for arch in ("qwen3_moe_30b_a3b", "qwen2_72b"):
        _, _, sp = specs_for(arch, "train", MESH_MP)
        axes = {a for s in jax.tree.leaves(
            sp, is_leaf=lambda x: isinstance(x, P)) for a in s if a}
        assert "pod" not in axes


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_specs_rank_matches_params(arch):
    cfg, sds, sp = specs_for(arch, "train")
    for leaf, spec in zip(jax.tree.leaves(sds),
                          jax.tree.leaves(sp, is_leaf=lambda x: isinstance(x, P))):
        assert len(spec) == leaf.ndim, (arch, leaf.shape, spec)


def test_cache_pspec_modes():
    cfg = get_config("qwen2_72b")
    model = build_model(cfg)
    c_sds = model.cache_specs(128, 32768)
    for mode, dim in (("seq", 2), ("hd", 4), ("none", None)):
        sp = sharding.cache_pspec(cfg.replace(kv_cache_shard=mode), MESH, c_sds)
        got = sp["k"]
        if dim is None:
            assert "model" not in tuple(a for a in got if a)
        else:
            assert got[dim] == "model", (mode, got)
        assert got[1] == ("data",) or got[1] == "data"
