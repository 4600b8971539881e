"""MoE expert execution strategies — the paper's load-balancing methods as
static-shape TPU computations (docs/DESIGN.md §2, §5).

* ``dense``    — Busy Full Loading (L_B, paper §4.2): every expert computes
                 every token; unselected contributions are zeroed in the
                 weighted sum.  Zero dispatch overhead, E/k× extra FLOPs.
* ``dispatch`` — Router-Aided Dynamic Loading (L_R, paper §4.2) adapted to
                 SPMD: fixed-capacity dispatch.  Every shard executes an
                 identical, statically-shaped amount of expert work (the
                 "equalize to the max" half of L_R); token assignments above
                 capacity are dropped, below capacity padded (the LRU
                 freshness half is host-side, see core/dynamic_load.py).
* ``grouped``  — the dropless form of ``dispatch`` where its capacity cannot
                 bind: routing decisions sorted by expert, one grouped GEMM
                 over 128-row tiles, so an expert costs the tiles its rows
                 fill instead of a whole capacity block.

All functions here operate on *local* expert shards: ``experts`` params carry
a leading E_local axis and ``e_start`` locates the shard in the global expert
space.  ``core/expert_parallel.py`` wraps them in shard_map.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import quant

Array = jax.Array


def round_capacity(tokens: int, k: int, num_experts: int,
                   capacity_factor: float, multiple: int = 8) -> int:
    """Static per-expert capacity, rounded up for MXU-friendly tiling."""
    raw = math.ceil(tokens * k / num_experts * capacity_factor)
    return max(multiple, math.ceil(raw / multiple) * multiple)


def expert_ffn(experts: dict, xe: Array, use_kernel: bool = False) -> Array:
    """Grouped SwiGLU FFN. xe: (E_local, C, D) -> (E_local, C, D).

    ``use_kernel`` selects the Pallas prestacked grouped-GEMM kernel
    (kernels/moe_gemm.py); default is the pure-jnp path (also the oracle).
    Expert weights may be raw arrays or blockwise-quantized QuantTensors
    (docs/DESIGN.md §8) — the jnp path dequantizes through the ``qdot``
    policy point, the kernel path dequantizes tiles in-VMEM.
    """
    with jax.named_scope("moe.ffn"):
        if use_kernel:
            from repro.kernels import ops
            return ops.moe_ffn(xe, experts["w_gate"], experts["w_up"],
                               experts["w_down"])
        g = quant.qdot("ecd,edf->ecf", xe, experts["w_gate"],
                       preferred_element_type=jnp.float32)
        u = quant.qdot("ecd,edf->ecf", xe, experts["w_up"],
                       preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(xe.dtype)
        return quant.qdot("ecf,efd->ecd", h, experts["w_down"],
                          preferred_element_type=jnp.float32).astype(xe.dtype)


# ---------------------------------------------------------------------------
# strategy: dense  (busy full loading, L_B)
# ---------------------------------------------------------------------------

def dense_moe(experts: dict, x: Array, top_idx: Array, top_w: Array,
              e_start: int, use_kernel: bool = False) -> Array:
    """x: (T, D). Every local expert computes every token; combine masks
    out everything the router did not select.  Returns the *local partial
    sum* (T, D) — caller psums across expert shards."""
    e_local = experts["w_gate"].shape[0]
    t = x.shape[0]
    with jax.named_scope("moe.dispatch"):
        xe = jnp.broadcast_to(x[None], (e_local, t, x.shape[1]))
    ye = expert_ffn(experts, xe, use_kernel)                # (E_local, T, D)
    with jax.named_scope("moe.combine"):
        # combine weight of local expert e for token t
        local_ids = e_start + jnp.arange(e_local)           # (E_local,)
        sel = top_idx[None, :, :] == local_ids[:, None, None]
        w = jnp.sum(jnp.where(sel, top_w[None], 0.0), axis=-1)  # (E_loc, T)
        return jnp.einsum("et,etd->td", w.astype(ye.dtype), ye)


# ---------------------------------------------------------------------------
# strategy: dispatch  (capacity-based, L_R)
# ---------------------------------------------------------------------------

def make_dispatch_plan(top_idx: Array, num_experts: int, e_start: int,
                       e_local: int, capacity: int):
    """Compute gather/scatter indices for capacity dispatch.

    Returns (dispatch_tok, slot_of, valid):
      dispatch_tok: (E_local * C,) int32 — source token per expert slot
                    (overflow/padding slots point at token 0 and are masked)
      slot_valid:   (E_local * C,) bool  — slot actually holds a token
      slot_of:      (T, K) int32 — destination slot per routing decision,
                    == E_local*C (one-past-end) when dropped / non-local
    """
    t, k = top_idx.shape
    flat_e = top_idx.reshape(-1)                            # (T*K,)
    order = jnp.argsort(flat_e, stable=True)                # group by expert
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, jnp.arange(num_experts), side="left")
    rank = jnp.arange(t * k, dtype=jnp.int32) - first[sorted_e].astype(jnp.int32)
    is_local = (sorted_e >= e_start) & (sorted_e < e_start + e_local)
    ok = is_local & (rank < capacity)
    dest = (sorted_e - e_start) * capacity + rank           # (T*K,)
    nbuf = e_local * capacity
    dest = jnp.where(ok, dest, nbuf).astype(jnp.int32)

    dispatch_tok = jnp.zeros((nbuf + 1,), jnp.int32).at[dest].set(
        (order // k).astype(jnp.int32), mode="drop")
    slot_valid = jnp.zeros((nbuf + 1,), jnp.bool_).at[dest].set(
        True, mode="drop")
    slot_of = jnp.zeros((t * k,), jnp.int32).at[order].set(dest)
    return dispatch_tok[:nbuf], slot_valid[:nbuf], slot_of.reshape(t, k)


def dispatch_moe(experts: dict, x: Array, top_idx: Array, top_w: Array,
                 num_experts: int, e_start: int, capacity: int,
                 use_kernel: bool = False) -> Array:
    """Capacity-based dispatch on the local shard. x: (T, D) (all tokens
    visible locally — the decentralized design of paper §4.3). Returns the
    local partial sum (T, D); caller psums across expert shards."""
    e_local = experts["w_gate"].shape[0]
    t, d = x.shape
    with jax.named_scope("moe.dispatch"):
        dispatch_tok, slot_valid, slot_of = make_dispatch_plan(
            top_idx, num_experts, e_start, e_local, capacity)
        xe = x[dispatch_tok] * slot_valid[:, None].astype(x.dtype)
        xe = xe.reshape(e_local, capacity, d)
    ye = expert_ffn(experts, xe, use_kernel).reshape(e_local * capacity, d)
    with jax.named_scope("moe.combine"):
        ye_pad = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)], axis=0)
        y_tk = ye_pad[slot_of]                              # (T, K, D)
        return jnp.einsum("tk,tkd->td", top_w.astype(y_tk.dtype), y_tk)


# ---------------------------------------------------------------------------
# strategy: grouped  (dropless sort-by-expert, grouped GEMM)
# ---------------------------------------------------------------------------

GROUPED_ROW_TILE = 128          # rows per tile of the grouped GEMM


def grouped_applies(t: int, capacity: int, experts: dict) -> bool:
    """Whether ``grouped_moe`` replaces ``dispatch_moe`` on a (t, D) block
    with this per-expert capacity.  All three must hold:

    * ``capacity >= t``: a token picks each expert at most once, so the
      capacity layout drops nothing and the grouped form computes exactly
      the same sum;
    * ``capacity > GROUPED_ROW_TILE``: at or below one row tile the
      capacity layout computes no more rows per expert than the grouped
      form would (and its FLOP per weight byte, C, is under the chip's
      ridge);
    * the expert weights are raw arrays: the grouped GEMM has no
      ``QuantTensor`` form."""
    return (capacity >= t and capacity > GROUPED_ROW_TILE
            and not isinstance(experts["w_gate"], quant.QuantTensor))


def group_by_expert(top_idx: Array, e_start, e_local: int):
    """Sort the (T, K) routing decisions by local expert.

    Returns (order, group_sizes, local): ``order`` (T*K,) the stable
    permutation of the flattened decisions that groups them by expert,
    decisions for another shard's experts or dead-routed (``_mask_rout``'s
    E_pad sentinel) last; ``group_sizes`` (E_local,) int32 the decisions
    per local expert; ``local`` (T, K) bool which decisions are local."""
    local_e = top_idx - e_start
    local = (local_e >= 0) & (local_e < e_local)
    key = jnp.where(local, local_e, e_local).reshape(-1)
    order = jnp.argsort(key, stable=True)
    ends = jnp.searchsorted(key[order], jnp.arange(1, e_local + 1),
                            side="left")
    return order, jnp.diff(ends, prepend=0).astype(jnp.int32), local


def grouped_moe(experts: dict, x: Array, top_idx: Array, top_w: Array,
                e_start, layer=None) -> Array:
    """Dropless routed expert FFN on the local shard. x: (T, D).

    The T*K routing decisions are sorted by local expert
    (``group_by_expert``); decisions for another shard's experts or
    dead-routed ones sort past every group, so the grouped GEMM
    (``kernels.ops.grouped_matmul``) never computes them.  Each expert
    costs the row tiles its own rows fill, not a (C, D) capacity block.
    Returns the local partial sum (T, D); caller psums across shards.

    ``layer``: when given, ``experts`` are whole stacks over layers
    (L, E_local, ...) and layer ``layer``'s experts are used.  The GEMM
    reads them in place, at group offset ``layer * E_local`` of the stack
    seen as L * E_local groups: a layer's slice handed to the Pallas call
    would first be copied into a buffer of its own, every layer."""
    from repro.kernels import ops
    t, k = top_idx.shape
    e_local = experts["w_gate"].shape[-3]
    offset = None
    if layer is not None:
        offset = layer * e_local
        experts = {name: w.reshape((-1,) + w.shape[2:])
                   for name, w in experts.items()}
    with jax.named_scope("moe.dispatch"):
        order, group_sizes, local = group_by_expert(top_idx, e_start,
                                                    e_local)
        xs = x[order // k]                                      # (T*K, D)
    with jax.named_scope("moe.ffn"):
        gmm = lambda a, w: ops.grouped_matmul(a, w, group_sizes,
                                              GROUPED_ROW_TILE, offset)
        g = gmm(xs, experts["w_gate"])
        u = gmm(xs, experts["w_up"])
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        ys = gmm(h, experts["w_down"]).astype(x.dtype)
    with jax.named_scope("moe.combine"):
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=order.dtype))
        y_tk = ys[inv].reshape(t, k, -1)
        # rows past the groups are never written by the grouped GEMM and
        # may hold anything, NaN included: select, never multiply
        y_tk = jnp.where(local[..., None], y_tk, 0)
        return jnp.einsum("tk,tkd->td", top_w.astype(y_tk.dtype), y_tk)


# ---------------------------------------------------------------------------
# strategy: gather  (capacity-free decode fast path)
# ---------------------------------------------------------------------------

def gather_moe(experts: dict, x: Array, top_idx: Array, top_w: Array,
               e_start: int) -> Array:
    """Capacity-free per-token expert gather on the local shard.

    The dispatch path pays ``round_capacity``'s floor of 8 slots per expert
    no matter how few tokens arrive — a single-token decode step against E
    experts runs E·8 FFN rows of which at most K are real.  For small T·K
    (``cfg.gather_decode_max_tk``) this path instead gathers each token's
    selected expert weights directly (reference_moe's form, sharded): T·K
    FFN rows, zero padding, zero drops, and only the selected experts'
    weights are read — the decode analogue of the paper's observation that
    per-token expert *loads* dominate small-batch inference.

    x: (T, D).  Non-local selections (including ``_mask_rout``'s E_pad
    dead-route sentinel) contribute zero via a masked combine weight.
    Returns the local partial sum (T, D); caller psums across shards.
    ``use_kernel`` does not apply: the Pallas grouped GEMM wants the
    (E_local, C, D) capacity layout this path exists to avoid.  Quantized
    expert weights keep the path's defining property: ``QuantTensor[idx]``
    gathers only the selected experts' payload+scales, and only that
    gathered slice is dequantized."""
    e_local = experts["w_gate"].shape[0]
    with jax.named_scope("moe.ffn"):
        local = (top_idx >= e_start) & (top_idx < e_start + e_local)
        idx = jnp.clip(top_idx - e_start, 0, e_local - 1)
        g = quant.qdot("td,tkdf->tkf", x, experts["w_gate"][idx],
                       preferred_element_type=jnp.float32)
        u = quant.qdot("td,tkdf->tkf", x, experts["w_up"][idx],
                       preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        y = quant.qdot("tkf,tkfd->tkd", h, experts["w_down"][idx],
                       preferred_element_type=jnp.float32)
    with jax.named_scope("moe.combine"):
        w = jnp.where(local, top_w, 0.0)
        return jnp.einsum("tk,tkd->td", w.astype(jnp.float32),
                          y.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# single-device reference combine (used by tests as the oracle)
# ---------------------------------------------------------------------------

def reference_moe(experts: dict, x: Array, top_idx: Array, top_w: Array) -> Array:
    """Exact per-token top-k MoE (no capacity drops), pure gather form."""
    t, k = top_idx.shape
    wg, wu, wd = experts["w_gate"], experts["w_up"], experts["w_down"]

    def one_tok(xt, idx, w):
        g = quant.qdot("d,kdf->kf", xt, wg[idx], preferred_element_type=jnp.float32)
        u = quant.qdot("d,kdf->kf", xt, wu[idx], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(xt.dtype)
        y = quant.qdot("kf,kfd->kd", h, wd[idx], preferred_element_type=jnp.float32)
        return jnp.einsum("k,kd->d", w, y.astype(jnp.float32)).astype(xt.dtype)

    return jax.vmap(one_tok)(x, top_idx, top_w)
