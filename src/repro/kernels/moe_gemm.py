"""Pallas TPU kernel: prestacked grouped expert FFN (SwiGLU).

The paper's prestacking (C2) made expert weights one contiguous array so the
runtime never re-prepares them; on TPU the same layout lets a single kernel
stream every expert's tiles HBM->VMEM with no per-expert dispatch.  This
kernel fuses the whole expert FFN  y = (silu(x Wg) * (x Wu)) Wd  for a batch
of experts:

  grid = (E, C/bc, F/bf)   — f innermost, accumulating into a VMEM scratch
  x   : (E, C, D)  block (1, bc, D)
  Wg/Wu: (E, D, F) block (1, D, bf)       } MXU-aligned tiles
  Wd  : (E, F, D)  block (1, bf, D)
  out : (E, C, D)  block (1, bc, D), written on the last f step

VMEM working set (bc=128, bf=256, D=2048, bf16):
  x 0.5 MB + Wg/Wu 2x1 MB + Wd 1 MB + fp32 acc 1 MB ~= 4.5 MB  << 16 MB.

Validated against kernels/ref.py in interpret mode (CPU) over a
shape/dtype sweep; TPU is the deployment target.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quant


def _kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *, n_f: int):
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                   # (bc, D)
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)       # (bc, bf)
    acc_ref[...] += jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)

    @pl.when(f == n_f - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "interpret"))
def moe_ffn_kernel(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                   w_down: jax.Array, *, block_c: int = 128,
                   block_f: int = 256, interpret: bool = False) -> jax.Array:
    """x: (E, C, D); w_gate/w_up: (E, D, F); w_down: (E, F, D) -> (E, C, D).

    C and F are padded up to the block sizes (zero padding is exact for this
    FFN: silu(0)*0 = 0 and zero Wd rows contribute nothing).
    """
    e, c, d = x.shape
    f = w_gate.shape[-1]
    bc, bf = min(block_c, c), min(block_f, f)
    cp = (c + bc - 1) // bc * bc
    fp = (f + bf - 1) // bf * bf
    if cp != c:
        x = jnp.pad(x, ((0, 0), (0, cp - c), (0, 0)))
    if fp != f:
        w_gate = jnp.pad(w_gate, ((0, 0), (0, 0), (0, fp - f)))
        w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, fp - f)))
        w_down = jnp.pad(w_down, ((0, 0), (0, fp - f), (0, 0)))
    n_c, n_f = cp // bc, fp // bf

    out = pl.pallas_call(
        functools.partial(_kernel, n_f=n_f),
        grid=(e, n_c, n_f),
        in_specs=[
            pl.BlockSpec((1, bc, d), lambda e_, c_, f_: (e_, c_, 0)),
            pl.BlockSpec((1, d, bf), lambda e_, c_, f_: (e_, 0, f_)),
            pl.BlockSpec((1, d, bf), lambda e_, c_, f_: (e_, 0, f_)),
            pl.BlockSpec((1, bf, d), lambda e_, c_, f_: (e_, f_, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, d), lambda e_, c_, f_: (e_, c_, 0)),
        out_shape=jax.ShapeDtypeStruct((e, cp, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, d), jnp.float32)],
        interpret=interpret,
    )(x, w_gate, w_up, w_down)
    return out[:, :c, :]


# ---------------------------------------------------------------------------
# quantized variant: in-VMEM dequantization of blockwise int8/int4 weights
# ---------------------------------------------------------------------------

def _dequant_tile(v, s, bits: int, qb: int, rows: int):
    """Dequantize one weight tile inside the kernel.

    v: (Kp, N) int8 payload tile (packed pairs along axis 0 for int4);
    s: (nb, N) fp32 per-block scales; ``rows`` is the tile's logical K.
    Nibble unpack is shifts/compares and the scale expansion a static
    repeat — both lower on TPU without extra HBM traffic: the tile was
    fetched quantized (1 or 0.5 bytes/value) and widens to fp32 in VMEM
    only, which is the whole point of the quantized store (the HBM read
    per expert tile shrinks 2-4x vs bf16)."""
    if bits == 4:
        v = quant.unpack_int4(v, axis=0)
    v = v[:rows]
    sf = jnp.repeat(s, qb, axis=0)[:rows]
    return v.astype(jnp.float32) * sf


def _kernel_q(x_ref, wg_ref, wgs_ref, wu_ref, wus_ref, wd_ref, wds_ref,
              o_ref, acc_ref, *, n_f: int, bits: int, qb: int, d: int,
              bf: int):
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                   # (bc, D)
    wg = _dequant_tile(wg_ref[0], wgs_ref[0], bits, qb, d)   # (D, bf)
    wu = _dequant_tile(wu_ref[0], wus_ref[0], bits, qb, d)
    g = jnp.dot(x, wg.astype(x.dtype), preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu.astype(x.dtype), preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)       # (bc, bf)
    # wds_ref holds the expert's whole (Fp/qb, D) scale column: a
    # (bf/qb, D) block puts a sub-8 tile on the second-minor axis, and a
    # dynamic sublane slice at offset f*bf/qb is refused as well, so this
    # f-step's rows are picked by exact masked row sums
    wds = wds_ref[0]                                          # (Fp/qb, D)
    nbf = bf // qb
    if nbf != wds.shape[0]:
        rid = jax.lax.broadcasted_iota(jnp.int32, wds.shape, 0)
        wds = jnp.concatenate(
            [jnp.sum(jnp.where(rid == f * nbf + j, wds, 0.0), axis=0,
                     keepdims=True) for j in range(nbf)], axis=0)
    wd = _dequant_tile(wd_ref[0], wds, bits, qb, bf)          # (bf, D)
    acc_ref[...] += jnp.dot(h, wd.astype(x.dtype),
                            preferred_element_type=jnp.float32)

    @pl.when(f == n_f - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _pad_dim(a, size: int, axis: int):
    if a.shape[axis] == size:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, size - a.shape[axis])
    return jnp.pad(a, pad)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f",
                                             "interpret"))
def moe_ffn_kernel_quant(x: jax.Array, w_gate: quant.QuantTensor,
                         w_up: quant.QuantTensor, w_down: quant.QuantTensor,
                         *, block_c: int = 128, block_f: int = 256,
                         interpret: bool = False) -> jax.Array:
    """Grouped SwiGLU FFN over blockwise-quantized expert weights.

    x: (E, C, D) fp; w_gate/w_up: QuantTensor (E, D, F) quantized along D;
    w_down: QuantTensor (E, F, D) quantized along F — the layout
    ``core/quant.quantize_tree`` produces for the prestacked expert stack.
    Same grid as ``moe_ffn_kernel`` (E, C/bc, F/bf), but each weight tile
    arrives in VMEM as int8/packed-int4 payload + fp32 block scales and is
    dequantized in-kernel (``_dequant_tile``): HBM streams the compressed
    bytes, the MXU sees fp tiles.  The f-tile width is clamped to a
    multiple of the quantization block so scale tiles stay aligned; F is
    zero-padded to whole tiles (exact: zero scales dequantize to zero and
    silu(0)*0 contributes nothing).  Validated against kernels/ref.py in
    interpret mode (tests/test_kernels.py); TPU is the deployment target.
    """
    e, c, d = x.shape
    f = w_gate.shape[-1]
    bits, qb = w_gate.bits, w_gate.block
    assert (w_up.bits, w_up.block) == (bits, qb), "mixed quant params"
    assert (w_down.bits, w_down.block) == (bits, qb), "mixed quant params"
    bc = min(block_c, c)
    cp = (c + bc - 1) // bc * bc
    if cp != c:
        x = jnp.pad(x, ((0, 0), (0, cp - c), (0, 0)))
    # f-tile width: a multiple of the quant block (so every wd scale tile
    # is whole blocks), covering F padded up to whole quant blocks
    fq = -(-f // qb) * qb
    bf = max(min(block_f, fq) // qb * qb, qb)
    fp = -(-fq // bf) * bf
    n_c, n_f = cp // bc, fp // bf

    wg_d = _pad_dim(w_gate.data, fp, 2)            # (E, Dp, Fp)
    wu_d = _pad_dim(w_up.data, fp, 2)
    wg_s = _pad_dim(w_gate.scale, fp, 2)           # (E, nb_d, Fp)
    wu_s = _pad_dim(w_up.scale, fp, 2)
    rows = fp // 2 if bits == 4 else fp
    wd_d = _pad_dim(w_down.data, rows, 1)          # (E, Fp[/2], D)
    wd_s = _pad_dim(w_down.scale, fp // qb, 1)     # (E, Fp/qb, D)
    dp, nb_d = wg_d.shape[1], wg_s.shape[1]
    bf_rows = bf // 2 if bits == 4 else bf

    out = pl.pallas_call(
        functools.partial(_kernel_q, n_f=n_f, bits=bits, qb=qb, d=d, bf=bf),
        grid=(e, n_c, n_f),
        in_specs=[
            pl.BlockSpec((1, bc, d), lambda e_, c_, f_: (e_, c_, 0)),
            pl.BlockSpec((1, dp, bf), lambda e_, c_, f_: (e_, 0, f_)),
            pl.BlockSpec((1, nb_d, bf), lambda e_, c_, f_: (e_, 0, f_)),
            pl.BlockSpec((1, dp, bf), lambda e_, c_, f_: (e_, 0, f_)),
            pl.BlockSpec((1, nb_d, bf), lambda e_, c_, f_: (e_, 0, f_)),
            pl.BlockSpec((1, bf_rows, d), lambda e_, c_, f_: (e_, f_, 0)),
            pl.BlockSpec((1, fp // qb, d), lambda e_, c_, f_: (e_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, d), lambda e_, c_, f_: (e_, c_, 0)),
        out_shape=jax.ShapeDtypeStruct((e, cp, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, d), jnp.float32)],
        interpret=interpret,
    )(x, wg_d, wg_s, wu_d, wu_s, wd_d, wd_s)
    return out[:, :c, :]
