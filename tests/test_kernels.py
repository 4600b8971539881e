"""Pallas kernel vs pure-jnp oracle: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.moe_gemm import moe_ffn_kernel


def rand(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.1).astype(dtype)


@pytest.mark.parametrize("e,c,d,f", [
    (1, 8, 64, 128),
    (4, 16, 128, 256),
    (8, 128, 128, 64),
    (3, 33, 96, 80),        # ragged: exercises padding paths
    (2, 1, 128, 256),       # single-token decode capacity
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gemm_matches_ref(e, c, d, f, dtype):
    key = jax.random.PRNGKey(e * 1000 + c)
    ks = jax.random.split(key, 4)
    x = rand(ks[0], (e, c, d), dtype)
    wg = rand(ks[1], (e, d, f), dtype)
    wu = rand(ks[2], (e, d, f), dtype)
    wd = rand(ks[3], (e, f, d), dtype)
    y_k = moe_ffn_kernel(x, wg, wu, wd, interpret=True)
    y_r = ref.moe_ffn_ref(x, wg, wu, wd)
    assert y_k.shape == y_r.shape == (e, c, d)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bc,bf", [(32, 64), (128, 256), (8, 16)])
def test_moe_gemm_block_shape_invariance(bc, bf):
    """Output must not depend on the BlockSpec tiling."""
    key = jax.random.PRNGKey(42)
    ks = jax.random.split(key, 4)
    e, c, d, f = 2, 64, 128, 128
    x = rand(ks[0], (e, c, d), jnp.float32)
    wg = rand(ks[1], (e, d, f), jnp.float32)
    wu = rand(ks[2], (e, d, f), jnp.float32)
    wd = rand(ks[3], (e, f, d), jnp.float32)
    y = moe_ffn_kernel(x, wg, wu, wd, block_c=bc, block_f=bf, interpret=True)
    y_r = ref.moe_ffn_ref(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               rtol=1e-5, atol=1e-5)


def test_ops_wrapper_dispatches_interpret_on_cpu():
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 4)
    e, c, d, f = 2, 16, 64, 64
    x = rand(ks[0], (e, c, d), jnp.float32)
    wg = rand(ks[1], (e, d, f), jnp.float32)
    wu = rand(ks[2], (e, d, f), jnp.float32)
    wd = rand(ks[3], (e, f, d), jnp.float32)
    y = ops.moe_ffn(x, wg, wu, wd)
    y_r = ref.moe_ffn_ref(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend,expect", [("cpu", True), ("tpu", False),
                                            ("gpu", RuntimeError)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, expect):
    """Kernels interpret on the CPU backend only; a backend with no TPU
    lowering is refused instead of silently interpreting."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if expect is RuntimeError:
        with pytest.raises(RuntimeError, match="no 'gpu' lowering"):
            ops._interpret()
    else:
        assert ops._interpret() is expect


def test_kernel_zero_padding_exactness():
    """Zero rows (dispatch padding slots) must produce exactly zero output."""
    e, c, d, f = 2, 16, 64, 64
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    x = jnp.zeros((e, c, d), jnp.float32)
    wg = rand(ks[0], (e, d, f), jnp.float32)
    wu = rand(ks[1], (e, d, f), jnp.float32)
    wd = rand(ks[2], (e, f, d), jnp.float32)
    y = moe_ffn_kernel(x, wg, wu, wd, interpret=True)
    assert float(jnp.max(jnp.abs(y))) == 0.0


# ---------------------------------------------------------------------------
# quantized grouped GEMM (in-kernel dequant of blockwise int8/int4 weights)
# ---------------------------------------------------------------------------

from repro.core import quant
from repro.kernels.moe_gemm import moe_ffn_kernel_quant


@pytest.mark.parametrize("e,c,d,f", [
    (1, 8, 64, 128),
    (4, 16, 128, 256),
    (3, 33, 96, 80),        # ragged C/F: padding paths + F < quant block
    (2, 1, 128, 256),       # single-token decode capacity
])
@pytest.mark.parametrize("level", ["int8", "int4"])
@pytest.mark.parametrize("qb", [64, 128])
def test_moe_gemm_quant_matches_ref(e, c, d, f, level, qb):
    """The in-VMEM tile dequant (ISSUE 5 tentpole) must match the
    dequantize-then-dense oracle across shapes, bit widths and quant
    blocks — including F that none of (tile, quant block) divides."""
    key = jax.random.PRNGKey(e * 1000 + c + qb)
    ks = jax.random.split(key, 4)
    x = rand(ks[0], (e, c, d), jnp.float32)
    wg = quant.quantize(rand(ks[1], (e, d, f), jnp.float32), level, block=qb)
    wu = quant.quantize(rand(ks[2], (e, d, f), jnp.float32), level, block=qb)
    wd = quant.quantize(rand(ks[3], (e, f, d), jnp.float32), level, block=qb)
    y = moe_ffn_kernel_quant(x, wg, wu, wd, interpret=True)
    y_r = ref.moe_ffn_ref_quant(x, wg, wu, wd)
    assert y.shape == y_r.shape == (e, c, d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bc,bf", [(32, 64), (128, 256), (8, 16)])
def test_moe_gemm_quant_block_shape_invariance(bc, bf):
    """Output must not depend on the BlockSpec tiling (the f-tile is
    clamped to whole quant blocks internally)."""
    key = jax.random.PRNGKey(42)
    ks = jax.random.split(key, 4)
    e, c, d, f = 2, 64, 128, 128
    x = rand(ks[0], (e, c, d), jnp.float32)
    wg = quant.quantize(rand(ks[1], (e, d, f), jnp.float32), "int8",
                        block=64)
    wu = quant.quantize(rand(ks[2], (e, d, f), jnp.float32), "int8",
                        block=64)
    wd = quant.quantize(rand(ks[3], (e, f, d), jnp.float32), "int8",
                        block=64)
    y = moe_ffn_kernel_quant(x, wg, wu, wd, block_c=bc, block_f=bf,
                             interpret=True)
    y_r = ref.moe_ffn_ref_quant(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               rtol=1e-5, atol=1e-5)


def test_moe_gemm_quant_bf16_activations():
    """bf16 activations over a quantized store (the production dtype mix)
    stay within the bf16 kernel tolerance of the oracle."""
    key = jax.random.PRNGKey(9)
    ks = jax.random.split(key, 4)
    e, c, d, f = 2, 16, 128, 256
    x = rand(ks[0], (e, c, d), jnp.bfloat16)
    mk = lambda k, s: quant.quantize(rand(k, s, jnp.bfloat16), "int8",
                                     block=64)
    wg, wu, wd = mk(ks[1], (e, d, f)), mk(ks[2], (e, d, f)), \
        mk(ks[3], (e, f, d))
    y = moe_ffn_kernel_quant(x, wg, wu, wd, interpret=True)
    y_r = ref.moe_ffn_ref_quant(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_r, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_ops_wrapper_dispatches_quantized():
    """ops.moe_ffn routes QuantTensor weights to the quantized kernel —
    the expert_ffn(use_kernel=True) path needs no call-site branching."""
    key = jax.random.PRNGKey(13)
    ks = jax.random.split(key, 4)
    e, c, d, f = 2, 16, 64, 64
    x = rand(ks[0], (e, c, d), jnp.float32)
    wg = quant.quantize(rand(ks[1], (e, d, f), jnp.float32), "int4",
                        block=32)
    wu = quant.quantize(rand(ks[2], (e, d, f), jnp.float32), "int4",
                        block=32)
    wd = quant.quantize(rand(ks[3], (e, f, d), jnp.float32), "int4",
                        block=32)
    y = ops.moe_ffn(x, wg, wu, wd)
    y_r = ref.moe_ffn_ref_quant(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention kernel
# ---------------------------------------------------------------------------

from repro.kernels.flash_attn import flash_attention


@pytest.mark.parametrize("s,window,causal", [
    (64, None, True), (128, 32, True), (96, None, True),
    (64, None, False), (80, 48, True),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(s, window, causal, dtype):
    key = jax.random.PRNGKey(s)
    b, h, hd = 2, 3, 64
    ks = jax.random.split(key, 3)
    q = rand(ks[0], (b, h, s, hd), dtype)
    k = rand(ks[1], (b, h, s, hd), dtype)
    v = rand(ks[2], (b, h, s, hd), dtype)
    y = flash_attention(q, k, v, causal=causal, window=window,
                        block_q=32, block_k=32, interpret=True)
    y_r = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_r, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bq,bk", [(16, 32), (64, 64), (32, 16)])
def test_flash_attention_block_invariance(bq, bk):
    key = jax.random.PRNGKey(9)
    b, h, s, hd = 1, 2, 128, 32
    ks = jax.random.split(key, 3)
    q = rand(ks[0], (b, h, s, hd), jnp.float32)
    k = rand(ks[1], (b, h, s, hd), jnp.float32)
    v = rand(ks[2], (b, h, s, hd), jnp.float32)
    y = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
    y_r = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# paged attention kernel (block-table decode + chunked prefill)
# ---------------------------------------------------------------------------

from repro.kernels.paged_attn import paged_attention


def _paged_case(seed, *, b, t, hq, hkv, hd, ps, nb, num_pages, quant=False,
                dtype=jnp.float32):
    """Random pool + per-row block tables (distinct pages per row) with
    lengths spread over the table's reach and ragged seg_lens."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, t, hq, hd)) * 0.1, dtype)
    bt = jnp.asarray(rng.permuted(
        np.tile(np.arange(num_pages), (b, 1)), axis=1)[:, :nb], jnp.int32)
    lengths = jnp.asarray(rng.integers(0, max(nb * ps - t, 1), b), jnp.int32)
    seg = jnp.asarray(rng.integers(0, t + 1, b), jnp.int32)
    shape = (num_pages, ps, hkv, hd)
    if quant:
        kp = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        # spread scales over two orders of magnitude: the in-kernel
        # dequant must track per-(page, slot, head) scale exactly
        ks = jnp.asarray(10 ** rng.uniform(-3, -1, shape[:-1] + (1,)),
                         jnp.float32)
        vs = jnp.asarray(10 ** rng.uniform(-3, -1, shape[:-1] + (1,)),
                         jnp.float32)
    else:
        kp = jnp.asarray(rng.standard_normal(shape) * 0.1, dtype)
        vp = jnp.asarray(rng.standard_normal(shape) * 0.1, dtype)
        ks = vs = None
    return q, kp, vp, bt, lengths, seg, ks, vs


@pytest.mark.parametrize("t,hq,hkv,ps,nb,window", [
    (1, 4, 2, 8, 4, None),       # decode, G=2
    (1, 8, 2, 16, 2, None),      # decode, G=4
    (4, 4, 2, 5, 7, None),       # chunked prefill, page_size divides nothing
    (6, 8, 2, 3, 9, 4),          # windowed prefill, ragged everything
    (7, 6, 6, 4, 6, None),       # MHA (G=1), t*G not a block multiple
    (3, 4, 1, 5, 5, 7),          # single kv head, window wider than a page
])
@pytest.mark.parametrize("quant", [False, True])
def test_paged_attention_matches_ref(t, hq, hkv, ps, nb, window, quant):
    """Kernel vs page-walk oracle across ragged page sizes, GQA group
    counts, window/non-window, fp and int8-with-scales pools."""
    q, kp, vp, bt, ln, sg, ks, vs = _paged_case(
        t * 100 + hq * 10 + ps, b=3, t=t, hq=hq, hkv=hkv, hd=32, ps=ps,
        nb=nb, num_pages=nb + 3, quant=quant)
    y = paged_attention(q, kp, vp, bt, ln, sg, k_scale=ks, v_scale=vs,
                        window=window, interpret=True)
    y_r = ref.paged_attention_ref(q, kp, vp, bt, ln, sg, k_scale=ks,
                                  v_scale=vs, window=window)
    assert y.shape == y_r.shape == q.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bq", [1, 2, 8, 128])
def test_paged_attention_block_q_invariance(bq):
    """Output must not depend on the q-row tiling."""
    q, kp, vp, bt, ln, sg, ks, vs = _paged_case(
        11, b=2, t=5, hq=4, hkv=2, hd=32, ps=4, nb=6, num_pages=9)
    y = paged_attention(q, kp, vp, bt, ln, sg, block_q=bq, interpret=True)
    y_r = ref.paged_attention_ref(q, kp, vp, bt, ln, sg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_padding_rows_zero():
    """Tokens past seg_lens are padding: their output rows must be exactly
    zero (finite garbage would still be ignored by the engine's last-valid
    logit selection, but zero is the kernel's contract)."""
    q, kp, vp, bt, ln, sg, *_ = _paged_case(
        5, b=2, t=6, hq=4, hkv=2, hd=32, ps=4, nb=6, num_pages=8)
    sg = jnp.asarray([2, 0], jnp.int32)
    y = paged_attention(q, kp, vp, bt, ln, sg, interpret=True)
    assert float(jnp.max(jnp.abs(y[0, 2:]))) == 0.0
    assert float(jnp.max(jnp.abs(y[1]))) == 0.0


def test_paged_attention_garbage_pages_masked():
    """Pool pages outside every row's block-table reach hold NaN/Inf
    garbage; table entries past a row's live extent point at page 0.  The
    position mask (and the OOB write sentinel upstream) must keep all of
    it out of the output."""
    q, kp, vp, bt, ln, sg, *_ = _paged_case(
        7, b=2, t=3, hq=4, hkv=2, hd=32, ps=4, nb=4, num_pages=8)
    used = np.unique(np.asarray(bt))
    garbage = np.setdiff1d(np.arange(8), used)
    kp = kp.at[garbage].set(jnp.nan)
    vp = vp.at[garbage].set(jnp.inf)
    y = paged_attention(q, kp, vp, bt, ln, sg, interpret=True)
    y_r = ref.paged_attention_ref(q, kp, vp, bt, ln, sg)
    assert bool(jnp.all(jnp.isfinite(y)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               rtol=2e-5, atol=2e-5)


def test_ops_paged_attention_dispatches_cache_dict():
    """ops.paged_attention unpacks the pool cache dict and routes int8
    pools (sibling scale leaves) to the in-kernel-dequant variant."""
    q, kp, vp, bt, ln, sg, ks, vs = _paged_case(
        3, b=2, t=1, hq=4, hkv=2, hd=32, ps=8, nb=3, num_pages=5,
        quant=True)
    y = ops.paged_attention(q, {"k": kp, "v": vp, "k_scale": ks,
                                "v_scale": vs}, bt, ln, sg)
    y_r = ref.paged_attention_ref(q, kp, vp, bt, ln, sg, k_scale=ks,
                                  v_scale=vs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               rtol=2e-5, atol=2e-5)


def test_model_level_flash_kernel_equivalence():
    """cfg.use_flash_kernel routes attention through the Pallas kernel
    (interpret mode on CPU) and must match the standard path end-to-end."""
    from repro.configs.base import get_config
    from repro.models.model import build_model
    cfg = get_config("qwen3_0_6b").reduced()
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)),
                                   jnp.int32)}
    m0 = build_model(cfg)
    m1 = build_model(cfg.replace(use_flash_kernel=True))
    params = m0.init(jax.random.PRNGKey(0))
    l0, _ = m0.forward(params, batch)
    l1, _ = m1.forward(params, batch)
    np.testing.assert_allclose(np.asarray(l0, np.float32),
                               np.asarray(l1, np.float32),
                               rtol=2e-4, atol=2e-4)
