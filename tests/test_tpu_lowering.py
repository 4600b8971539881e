"""Compile the Pallas kernels for a described TPU v5e, with no chip attached.

Every other kernel test runs in interpret mode, which accepts tilings that
Mosaic, the TPU kernel compiler, refuses.  These compiles run the real
lowering at Granite-3.0-3B-A800M's widths (d 1536, 48 padded experts of
F 512, 24 q / 8 kv heads of 64) and check that the compiled program holds
the kernel.  The topology is described inside a fixture, never at import:
only one process may load the TPU library, and pytest-xdist workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import quant
from repro.kernels import flash_attn, moe_gemm, paged_attn

E, D, F = 48, 1536, 512          # padded experts, d_model, expert d_ff
HQ, HKV, HD = 24, 8, 64
CAPACITY = 128                   # tokens per expert slot block


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _quant_sds(sharding, shape, level):
    qt = jax.eval_shape(lambda w: quant.quantize(w, level),
                        jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    return qt, (_sds(sharding, qt.data.shape, qt.data.dtype),
                _sds(sharding, qt.scale.shape, qt.scale.dtype))


def test_moe_ffn_kernel_lowers_bf16(one_chip):
    x = _sds(one_chip, (E, CAPACITY, D), jnp.bfloat16)
    wgu = _sds(one_chip, (E, D, F), jnp.bfloat16)
    wd = _sds(one_chip, (E, F, D), jnp.bfloat16)
    text = _compiled_text(moe_gemm.moe_ffn_kernel, x, wgu, wgu, wd)
    assert "tpu_custom_call" in text


def _quant_ffn_text(one_chip, level):
    g, (gd, gs) = _quant_sds(one_chip, (E, D, F), level)
    d, (dd, ds) = _quant_sds(one_chip, (E, F, D), level)

    def ffn(x, gd, gs, ud, us, dd, ds):
        w = lambda a, s, m: quant.QuantTensor(a, s, m.bits, m.block,
                                              m.orig_dim, m.out_dtype)
        return moe_gemm.moe_ffn_kernel_quant(x, w(gd, gs, g), w(ud, us, g),
                                             w(dd, ds, d))

    x = _sds(one_chip, (E, CAPACITY, D), jnp.bfloat16)
    return _compiled_text(ffn, x, gd, gs, gd, gs, dd, ds)


def test_moe_ffn_kernel_quant_lowers_int8(one_chip):
    assert "tpu_custom_call" in _quant_ffn_text(one_chip, "int8")


@pytest.mark.xfail(strict=True, reason="the in-kernel int4 nibble unpack "
                   "shifts int8 values, which Mosaic does not legalize")
def test_moe_ffn_kernel_quant_lowers_int4(one_chip):
    assert "tpu_custom_call" in _quant_ffn_text(one_chip, "int4")


@pytest.mark.parametrize("seq", [512, 2048])
def test_flash_attention_lowers(one_chip, seq):
    qkv = _sds(one_chip, (1, HQ, seq, HD), jnp.bfloat16)
    assert "tpu_custom_call" in _compiled_text(
        flash_attn.flash_attention, qkv, qkv, qkv)


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "ROADMAP A2: the (1, page_size, 1, hd) KV block over the "
    "(P, page_size, Hkv, hd) pool puts a block of 1 on the second-minor "
    "axis; the fix changes the pool layout"))
@pytest.mark.parametrize("t", [1, 32])
def test_paged_attention_lowers(one_chip, t):
    b, pages, page_size, nb = 8, 256, 16, 32
    pool = _sds(one_chip, (pages, page_size, HKV, HD), jnp.bfloat16)
    _compiled_text(
        paged_attn.paged_attention,
        _sds(one_chip, (b, t, HQ, HD), jnp.bfloat16), pool, pool,
        _sds(one_chip, (b, nb), jnp.int32), _sds(one_chip, (b,), jnp.int32),
        _sds(one_chip, (b,), jnp.int32))


@pytest.mark.parametrize("t, e, d, f", [
    (1024, 128, 2048, 768),      # Qwen3-30B-A3B's mixed block: M = T*8 = 8192
    (256, E, D, F),              # Granite-3.0-3B-A800M's: M = 2048
])
def test_grouped_moe_lowers(one_chip, monkeypatch, t, e, d, f):
    from repro.core import moe
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    k = 8
    experts = {"w_gate": _sds(one_chip, (e, d, f), jnp.bfloat16),
               "w_up": _sds(one_chip, (e, d, f), jnp.bfloat16),
               "w_down": _sds(one_chip, (e, f, d), jnp.bfloat16)}
    text = _compiled_text(
        lambda ex, x, i, w: moe.grouped_moe(ex, x, i, w, 0), experts,
        _sds(one_chip, (t, d), jnp.bfloat16),
        _sds(one_chip, (t, k), jnp.int32),
        _sds(one_chip, (t, k), jnp.float32))
    assert text.count("tpu_custom_call") >= 3
