"""jit'd public wrappers around the Pallas kernels.

On the CPU backend the kernels execute in interpret mode so the whole
framework remains runnable/testable; on TPU the same call sites compile the
real kernels.  ``interpret`` is resolved from the backend at trace time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from repro.core import quant
from repro.kernels import flash_attn, moe_gemm, paged_attn, ref


def _interpret() -> bool:
    """Interpret mode on the CPU backend, compiled kernels on TPU.  Any
    other backend raises: running a TPU kernel through the interpreter
    there would be silently slow, not a measurement of the kernel."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas TPU kernels have no {backend!r} lowering; "
                       f"run them on a TPU, or on the CPU in interpret mode")


def moe_ffn(x: jax.Array, w_gate, w_up, w_down) -> jax.Array:
    """Prestacked grouped expert FFN (E, C, D) -> (E, C, D).

    Weights may be raw arrays or blockwise-quantized QuantTensors
    (docs/DESIGN.md §8) — the quantized variant streams int8/packed-int4
    tiles HBM->VMEM and dequantizes in-kernel."""
    if isinstance(w_gate, quant.QuantTensor):
        return moe_gemm.moe_ffn_kernel_quant(x, w_gate, w_up, w_down,
                                             interpret=_interpret())
    return moe_gemm.moe_ffn_kernel(x, w_gate, w_up, w_down,
                                   interpret=_interpret())


moe_ffn_ref = ref.moe_ffn_ref
moe_ffn_ref_quant = ref.moe_ffn_ref_quant


def _tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 up to ``cap`` that divides ``dim``, or
    the whole ``dim`` when none does (a block as wide as its array is
    always legal)."""
    fits = [t for t in range(128, min(cap, dim) + 1, 128) if dim % t == 0]
    return max(fits) if fits else dim


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   row_tile: int, rhs_offset=None) -> jax.Array:
    """Grouped GEMM: rows of ``lhs`` (M, K) lie in consecutive groups of
    ``group_sizes`` (G,) rows, and group g is multiplied by ``rhs[g]``
    (G, K, N), or by ``rhs[rhs_offset + g]`` where ``rhs`` holds more
    groups than ``group_sizes`` (an int32 scalar).  Returns (M, N) float32.

    Megablox's ``gmm`` (JAX's Pallas TPU grouped GEMM) over ``row_tile``-row
    tiles: it visits only the tiles that hold a group's rows, so an expert
    with one row costs one tile and rows past ``sum(group_sizes)`` cost
    nothing.  Those rows are left unwritten (NaN in interpret mode): the
    caller masks them."""
    m, k = lhs.shape
    pad = -m % row_tile
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tiling = (row_tile, _tile(k, 512), _tile(rhs.shape[-1], 768))
    # gmm reads group g from rhs[g - group_offset]
    offset = (None if rhs_offset is None
              else -jnp.asarray(rhs_offset, jnp.int32))
    out = megablox.gmm(lhs, rhs, group_sizes.astype(jnp.int32), jnp.float32,
                       tiling, offset, None, False, _interpret())
    return out[:m]


def flash_attention(q, k, v, *, causal=True, window=None,
                    block_q=256, block_k=512):
    """Flash attention (B, H, S, hd) -> (B, H, S, hd)."""
    return flash_attn.flash_attention(q, k, v, causal=causal, window=window,
                                      block_q=block_q, block_k=block_k,
                                      interpret=_interpret())


flash_attention_ref = ref.flash_attention_ref


def paged_attention(q, cache, block_tables, lengths, seg_lens, *,
                    window=None, block_q=128):
    """Block-table paged attention straight off the page-pool cache dict
    (models/attention.paged_layer_cache_spec leaves) — decode (T=1) and
    chunked prefill (T>1) share one kernel.  int8 pools dispatch the
    in-kernel-dequant variant off their sibling scale leaves."""
    return paged_attn.paged_attention(
        q, cache["k"], cache["v"], block_tables, lengths, seg_lens,
        k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"),
        window=window, block_q=block_q, interpret=_interpret())


paged_attention_ref = ref.paged_attention_ref
