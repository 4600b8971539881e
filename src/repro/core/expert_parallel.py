"""Expert-parallel execution of one MoE layer across the mesh.

Four collective schedules (docs/DESIGN.md §5):

* ``centralized``   — the paper's naive organization (Fig. 3): expert inputs
  flow through a center, 2 communications per layer.  SPMD realization:
  token activations are sequence-sharded over the expert axis, all-gathered
  to every expert shard (comm 1), and partial expert outputs are
  reduce-scattered back (comm 2).
* ``decentralized`` — the paper's P-*-D design (Fig. 7, GShard-inspired):
  attention + router replicated over the expert axis, experts sharded, one
  all-reduce (psum) on expert outputs per layer.
* ``a2a``           — beyond-paper schedule: tokens stay sequence-sharded,
  dispatch/combine use all_to_all so only top-k activations move, not the
  full token stream.  (What modern MoE stacks do; see EXPERIMENTS.md §Perf.)
* ``a2a_pipelined`` — a2a with comm/compute overlap: the local token block
  is split into ``cfg.ep_microchunks`` chunks and software-pipelined with a
  double-buffered scan — chunk i's expert FFN is independent of chunk i+1's
  dispatch all_to_all, so a latency-hiding scheduler can overlap them.
  Addresses the paper's central measurement that expert computation time ≈
  expert communication time (§5.2): pipelining hides the shorter of the
  two behind the longer (modelled analytically by
  core/perf_model.estimate(..., microchunks=m)).  Token-exact vs ``a2a``
  whenever capacity is not binding; per-chunk capacity is
  round_capacity(T_loc/m).

When the token count cannot be split over the expert axis (single-token
decode), ``centralized`` degrades to psum + a value-preserving ring
``ppermute`` so the *second* communication of the centralized design is
still present in the lowered HLO (cost-faithful; values unchanged), and
both a2a schedules fall back to ``decentralized``.

Quantized expert shards (core/quant.QuantTensor leaves, docs/DESIGN.md §8)
ride every schedule unchanged: the int8/int4 payload and its per-block
scales are sibling rank-3 leaves sharing the leading expert axis, so
``_expert_specs``'s rank-3 PartitionSpecs broadcast over both and the
shard_map bodies receive local QuantTensor shards.  Activations stay fp —
dispatch/combine collectives move fp activations only; dequantization
happens at the expert FFN's ``qdot`` policy point (core/moe.expert_ffn).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import moe as moe_lib
from repro.core import router as router_lib

Array = jax.Array


def mesh_batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


EXPERT_AXIS = "model"


def _local_form(cfg, t: int, capacity: int, e_local: int, experts: dict,
                sharded: bool) -> str:
    """The local form ``_local_moe`` runs on a (t, D) block, chosen by
    shape and expert weight type alone: "dense", "gather", "grouped" or
    "dispatch".  ``sharded``: inside a schedule's shard_map, where the
    grouped GEMM cannot run (its Pallas call declares no varying mesh axes
    on its output, which ``check_vma`` refuses)."""
    if cfg.moe_strategy == "dense":
        return "dense"
    k = cfg.experts_per_token
    if 0 < t * k <= getattr(cfg, "gather_decode_max_tk", 0):
        # capacity-free decode fast path (no round_capacity floor, no
        # dispatch plan, no drops), form chosen by modeled cost:
        #  * per-token gather when T*K <= E_local — reads only the selected
        #    experts' weights (< one full local shard);
        #  * one-hot dense compute when T is below the capacity floor —
        #    same weight traffic as dispatch but E_local*T FFN rows instead
        #    of E_local*C mostly-padding slots, and none of the
        #    argsort/scatter plan overhead.
        # Above both cut-offs the fixed-capacity dispatch is already the
        # cheaper layout and wins.
        if t * k <= e_local:
            return "gather"
        if t < capacity:
            return "dense"
    # dropless capacity over more rows than a grouped tile: sort by expert
    # and run the grouped GEMM instead of E_local x C mostly-padding rows
    if not sharded and moe_lib.grouped_applies(t, capacity, experts):
        return "grouped"
    return "dispatch"


def _layer_of(experts: dict, layer) -> dict:
    """Layer ``layer``'s experts from stacks over layers."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
        experts)


def _local_moe(cfg, experts: dict, x2d: Array, rout: router_lib.RouterOut,
               e_start, capacity: int, sharded: bool = True,
               layer=None) -> Array:
    """``layer``: ``experts`` are stacks over layers, of which this one is
    used (``moe_layer``)."""
    t = x2d.shape[0]
    e_local = experts["w_gate"].shape[-3]
    form = _local_form(cfg, t, capacity, e_local, experts, sharded)
    if form == "grouped":
        return moe_lib.grouped_moe(experts, x2d, rout.top_idx, rout.top_w,
                                   e_start, layer)
    if layer is not None:
        experts = _layer_of(experts, layer)
    if form == "dense":
        return moe_lib.dense_moe(experts, x2d, rout.top_idx, rout.top_w,
                                 e_start, cfg.use_kernel)
    if form == "gather":
        return moe_lib.gather_moe(experts, x2d, rout.top_idx, rout.top_w,
                                  e_start)
    return moe_lib.dispatch_moe(experts, x2d, rout.top_idx, rout.top_w,
                                cfg.num_experts_padded, e_start, capacity,
                                cfg.use_kernel)


def _local_block(cfg, mesh, b: int, s: int) -> tuple[int, int, int] | None:
    """(tokens, capacity, local experts) of the block ``_local_moe`` sees
    when ``moe_layer`` runs a (b, s) block, or None where an a2a schedule
    computes the block without it."""
    k, e_pad = cfg.experts_per_token, cfg.num_experts_padded
    cap = lambda t: moe_lib.round_capacity(max(t, 1), k, e_pad,
                                           cfg.capacity_factor)
    if mesh is None or EXPERT_AXIS not in getattr(mesh, "axis_names", ()):
        return b * s, cap(b * s), e_pad
    n = mesh.shape[EXPERT_AXIS]
    r = max(getattr(cfg, "expert_replication", 1), 1)
    schedule = cfg.expert_parallel
    if schedule in ("a2a", "a2a_pipelined"):
        if s % n == 0:
            return None
        schedule = "decentralized"       # their single-token fallback
    # every schedule below sees its batch shard's whole token stream
    t = (b // max(_axes_size(mesh, _batch_axes(mesh, b)), 1)) * s
    if schedule == "decentralized":
        # each of the r replicas takes a 1/r stripe of it
        return t, cap(-(-t // r)), e_pad * r // n
    return t, cap(t), e_pad * r // n


def block_form(cfg, mesh, b: int, s: int, experts: dict) -> str | None:
    """The local form a MoE layer runs on a (b, s) block (``_local_form``),
    or None where no ``_local_moe`` runs.  ``experts`` needs only its
    ``w_gate`` leaf, for the weight type."""
    blk = _local_block(cfg, mesh, b, s)
    sharded = mesh is not None and EXPERT_AXIS in getattr(
        mesh, "axis_names", ())
    return None if blk is None else _local_form(cfg, *blk, experts, sharded)


def _mask_rout(rout: router_lib.RouterOut, valid: Array,
               e_pad: int) -> router_lib.RouterOut:
    """Dead-route invalid tokens: padding/garbage batch rows must consume
    ZERO expert capacity (the batched-prefill engine recomputes in-flight
    and empty slots under a mask — without this their tokens would crowd
    real tokens out of the fixed-capacity dispatch)."""
    top_idx = jnp.where(valid[:, None], rout.top_idx, e_pad)
    top_w = jnp.where(valid[:, None], rout.top_w, 0.0)
    return rout._replace(top_idx=top_idx.astype(jnp.int32), top_w=top_w)


def moe_layer(cfg, mesh, layer_p: dict, x: Array, token_mask: Array | None = None
              ) -> tuple[Array, Array, Array]:
    """Apply one MoE layer.

    x: (B, S, D) -> (y (B, S, D), aux_loss (), top_idx (B*S, K) int32).

    ``top_idx`` is the layer's *actual* routing decision per token — the
    device-side capture the serving engine's ``LRUExpertTracker`` consumes
    (paper Table 1, E[#exec experts/node/layer]) instead of re-running the
    router on the host.  With overlapping expert placement (r > 1) it is
    the pre-stripe decision: which experts each token selected, not which
    replica served it.

    ``token_mask``: optional (B, S) bool — False tokens are dead-routed to
    the padding sentinel (index E_pad): they consume no expert capacity,
    produce zero MoE output, and appear as E_pad in ``top_idx``.

    ``layer_p``: {"router": (D, E_pad), "experts": {"w_gate": (E_pad, D, F),
    "w_up": ..., "w_down": ...}} — per-layer slices of the prestacked stack;
    or, with a ``"layer"`` index, "experts" are the whole stacks
    (L, E_pad, ...) and that layer's are used.
    """
    b, s, d = x.shape
    k = cfg.experts_per_token
    r = max(getattr(cfg, "expert_replication", 1), 1)
    experts, layer = layer_p["experts"], layer_p.get("layer")
    if layer is not None and (r > 1 or (
            mesh is not None and EXPERT_AXIS in mesh.axis_names)):
        layer_p = dict(layer_p, experts=_layer_of(experts, layer))
        experts, layer = layer_p["experts"], None
    if mesh is None or EXPERT_AXIS not in getattr(mesh, "axis_names", ()):
        # single-shard path (smoke tests / CPU examples); with overlapping
        # placement the stacked array carries r copies — use the first
        if r > 1:
            experts = jax.tree.map(
                lambda a: a[:cfg.num_experts_padded], experts)
        x2d = x.reshape(b * s, d)
        rout = _route(cfg, layer_p["router"], x2d,
                      None if token_mask is None
                      else token_mask.reshape(b * s))
        y = _local_moe(cfg, experts, x2d, rout, 0,
                       _local_block(cfg, None, b, s)[1], sharded=False,
                       layer=layer)
        return y.reshape(b, s, d), rout.aux_loss, rout.top_idx

    n_exp_shards = mesh.shape[EXPERT_AXIS]
    if r > 1:
        assert cfg.expert_parallel == "decentralized", (
            "overlapping expert placement (paper §5.3) is implemented on "
            "the decentralized schedule")
        assert n_exp_shards % r == 0, (n_exp_shards, r)
        assert (cfg.num_experts_padded * r) % n_exp_shards == 0
    e_local = cfg.num_experts_padded * r // n_exp_shards
    batch_axes = _batch_axes(mesh, b)

    if token_mask is None:
        token_mask = jnp.ones((b, s), jnp.bool_)
    fn = {"decentralized": _decentralized, "centralized": _centralized,
          "a2a": _a2a, "a2a_pipelined": _a2a_pipelined}[cfg.expert_parallel]
    y, aux, top_idx = fn(cfg, mesh, layer_p, x, token_mask, batch_axes,
                         n_exp_shards, e_local)
    return y, aux, top_idx.reshape(b * s, k)


def _axes_size(mesh, axes) -> int:
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def _batch_axes(mesh, b: int) -> tuple[str, ...]:
    """The mesh axes a (b, ...) block's batch is sharded over: none unless
    b divides them (long_500k has b=1)."""
    axes = mesh_batch_axes(mesh)
    return axes if b % max(_axes_size(mesh, axes), 1) == 0 else ()


def _expert_specs(e_axis: str) -> dict:
    return {"w_gate": P(e_axis, None, None), "w_up": P(e_axis, None, None),
            "w_down": P(e_axis, None, None)}


def _route(cfg, router_w, x2d: Array, valid: Array | None
           ) -> router_lib.RouterOut:
    """The routing decision for (T, D) tokens, with the tokens whose
    ``valid`` (T,) is False dead-routed (``_mask_rout``)."""
    with jax.named_scope("moe.route"):
        rout = router_lib.route(router_w, x2d, cfg.experts_per_token,
                                norm_topk=cfg.router_norm_topk,
                                n_valid_experts=cfg.num_experts)
        if valid is not None:
            rout = _mask_rout(rout, valid, cfg.num_experts_padded)
    return rout


# ---------------------------------------------------------------------------
# decentralized (paper Fig. 7): replicated tokens, sharded experts, one psum
# ---------------------------------------------------------------------------

def _decentralized(cfg, mesh, layer_p, x, token_mask, batch_axes, n_shards,
                   e_local):
    """Paper Fig. 7, plus the paper's §5.3 *overlapping expert placement*:
    with ``cfg.expert_replication = r > 1`` every expert is stored on r
    shards (the stacked expert array carries r concatenated copies — "use
    the extra memory to load experts overlappingly") and each replica
    handles the 1/r token stripe ``token_idx % r == replica_id``, which is
    how the paper distributes computation more evenly past 4 nodes."""
    b, s, _ = x.shape
    r = max(getattr(cfg, "expert_replication", 1), 1)
    cap = _local_block(cfg, mesh, b, s)[1]
    e_pad = cfg.num_experts_padded
    n_grp = n_shards // r           # shards per expert copy

    def body(router_w, experts, x_loc, tm_loc):
        bl, sl, d = x_loc.shape
        x2d = x_loc.reshape(bl * sl, d)
        rout = _route(cfg, router_w, x2d, tm_loc.reshape(bl * sl))
        routed = rout.top_idx            # pre-stripe: actual decisions
        idx = jax.lax.axis_index(EXPERT_AXIS)
        if r > 1:
            replica = idx // n_grp
            e_start = (idx % n_grp) * e_local
            stripe = (jnp.arange(bl * sl) % r) == replica
            # tokens outside this replica's stripe route to a dead sentinel
            top_idx = jnp.where(stripe[:, None], rout.top_idx, e_pad)
            top_w = jnp.where(stripe[:, None], rout.top_w, 0.0)
            rout = rout._replace(top_idx=top_idx.astype(jnp.int32),
                                 top_w=top_w)
        else:
            e_start = idx * e_local
        y = _local_moe(cfg, experts, x2d, rout, e_start, cap)
        with jax.named_scope("moe.collective"):
            y = jax.lax.psum(y, EXPERT_AXIS)
            aux = (jax.lax.pmean(rout.aux_loss, batch_axes) if batch_axes
                   else rout.aux_loss)
        return (y.reshape(bl, sl, d), aux,
                routed.reshape(bl, sl, cfg.experts_per_token))

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), _expert_specs(EXPERT_AXIS), P(batch_axes, None, None),
                  P(batch_axes, None)),
        out_specs=(P(batch_axes, None, None), P(), P(batch_axes, None, None)),
        check_vma=True,
    )(layer_p["router"], layer_p["experts"], x, token_mask)


# ---------------------------------------------------------------------------
# centralized (paper Fig. 3): 2 communications per layer
# ---------------------------------------------------------------------------

def _centralized(cfg, mesh, layer_p, x, token_mask, batch_axes, n_shards,
                 e_local):
    b, s, d = x.shape
    seq_shardable = s % n_shards == 0
    cap = _local_block(cfg, mesh, b, s)[1]

    if seq_shardable:
        def body(router_w, experts, x_loc, tm_loc):
            bl, sl, dd = x_loc.shape
            # comm 1: gather the full token stream to every expert shard
            with jax.named_scope("moe.collective"):
                x_full = jax.lax.all_gather(x_loc, EXPERT_AXIS, axis=1,
                                            tiled=True)
                tm_full = jax.lax.all_gather(tm_loc, EXPERT_AXIS, axis=1,
                                             tiled=True)
            x2d = x_full.reshape(bl * sl * n_shards, dd)
            rout = _route(cfg, router_w, x2d,
                          tm_full.reshape(bl * sl * n_shards))
            e_start = jax.lax.axis_index(EXPERT_AXIS) * e_local
            y = _local_moe(cfg, experts, x2d, rout, e_start, cap)
            # comm 2: reduce partial sums and scatter back to sequence shards
            y = y.reshape(bl, sl * n_shards, dd)
            with jax.named_scope("moe.collective"):
                y = jax.lax.psum_scatter(y, EXPERT_AXIS, scatter_dimension=1,
                                         tiled=True)
                aux = jax.lax.pmean(rout.aux_loss,
                                    (EXPERT_AXIS,) + tuple(batch_axes))
            # every shard routed the full gathered stream — emit this
            # shard's own sequence slice, globally reassembled by out_specs
            ti = rout.top_idx.reshape(bl, sl * n_shards, cfg.experts_per_token)
            ti = jax.lax.dynamic_slice_in_dim(
                ti, jax.lax.axis_index(EXPERT_AXIS) * sl, sl, axis=1)
            return y, aux, ti

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), _expert_specs(EXPERT_AXIS),
                      P(batch_axes, EXPERT_AXIS, None),
                      P(batch_axes, EXPERT_AXIS)),
            out_specs=(P(batch_axes, EXPERT_AXIS, None), P(),
                       P(batch_axes, EXPERT_AXIS, None)),
            check_vma=True,
        )(layer_p["router"], layer_p["experts"], x, token_mask)

    # decode fallback: psum (comm 1) + value-preserving ring permute (comm 2)
    def body(router_w, experts, x_loc, tm_loc):
        bl, sl, dd = x_loc.shape
        x2d = x_loc.reshape(bl * sl, dd)
        rout = _route(cfg, router_w, x2d, tm_loc.reshape(bl * sl))
        e_start = jax.lax.axis_index(EXPERT_AXIS) * e_local
        y = _local_moe(cfg, experts, x2d, rout, e_start, cap)
        with jax.named_scope("moe.collective"):
            y = jax.lax.psum(y, EXPERT_AXIS)
            perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
            y = jax.lax.ppermute(y, EXPERT_AXIS, perm)  # identical values
            aux = (jax.lax.pmean(rout.aux_loss, batch_axes) if batch_axes
                   else rout.aux_loss)
        return (y.reshape(bl, sl, dd), aux,
                rout.top_idx.reshape(bl, sl, cfg.experts_per_token))

    # check_vma=False: the ring ppermute moves identical values, so the
    # output *is* replicated over the expert axis, but VMA cannot prove it.
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), _expert_specs(EXPERT_AXIS), P(batch_axes, None, None),
                  P(batch_axes, None)),
        out_specs=(P(batch_axes, None, None), P(), P(batch_axes, None, None)),
        check_vma=False,
    )(layer_p["router"], layer_p["experts"], x, token_mask)


# ---------------------------------------------------------------------------
# a2a (beyond paper): sequence-sharded tokens + all_to_all dispatch/combine
# ---------------------------------------------------------------------------

def _a2a_dispatch(cfg, xi, ti, n_shards, e_local, cap):
    """Token-block dispatch: capacity plan + gather + all_to_all (comm 1).

    Shared by ``_a2a`` (whole local block) and ``_a2a_pipelined`` (one
    microchunk) — the plan builds buffers for *all* experts, grouped by
    owner shard, so shard i's slice j travels to shard j.  Returns the
    post-exchange (n_src_shards, e_local*cap, d) buffer of local-expert
    inputs plus ``slot_of`` for the combine."""
    dd = xi.shape[-1]
    with jax.named_scope("moe.dispatch"):
        dispatch_tok, slot_valid, slot_of = moe_lib.make_dispatch_plan(
            ti, cfg.num_experts_padded, 0, cfg.num_experts_padded, cap)
        xe = xi[dispatch_tok] * slot_valid[:, None].astype(xi.dtype)
        xe = xe.reshape(n_shards, e_local * cap, dd)
    with jax.named_scope("moe.collective"):
        xe = jax.lax.all_to_all(xe, EXPERT_AXIS, split_axis=0,
                                concat_axis=0, tiled=False)
    return xe, slot_of


def _a2a_ffn_combine(cfg, experts, xe, slot_of, wi, n_shards, e_local, cap):
    """Token-block compute: expert FFN + return all_to_all (comm 2) +
    weighted combine back into source-token order (shared by ``_a2a`` and
    ``_a2a_pipelined``)."""
    dd = xe.shape[-1]
    xe = xe.transpose(1, 0, 2).reshape(e_local, n_shards * cap, dd)
    ye = moe_lib.expert_ffn(experts, xe, cfg.use_kernel)
    # invert (e_local, cap*n_src) -> (n_src, e_local*cap) exactly
    ye = ye.reshape(e_local, cap, n_shards, dd).transpose(2, 0, 1, 3)
    ye = ye.reshape(n_shards, e_local * cap, dd)
    with jax.named_scope("moe.collective"):
        ye = jax.lax.all_to_all(ye, EXPERT_AXIS, split_axis=0,
                                concat_axis=0, tiled=False)
    with jax.named_scope("moe.combine"):
        ye = ye.reshape(n_shards * e_local * cap, dd)
        ye_pad = jnp.concatenate([ye, jnp.zeros((1, dd), ye.dtype)], axis=0)
        y_tk = ye_pad[slot_of]
        return jnp.einsum("tk,tkd->td", wi.astype(y_tk.dtype), y_tk)


def _a2a(cfg, mesh, layer_p, x, token_mask, batch_axes, n_shards, e_local):
    b, s, d = x.shape
    if s % n_shards != 0:
        # single-token decode: fall back to the decentralized schedule
        return _decentralized(cfg, mesh, layer_p, x, token_mask, batch_axes,
                              n_shards, e_local)
    t_loc = (b // max(_axes_size(mesh, batch_axes), 1)) * (s // n_shards)
    # per-(source shard, expert) capacity
    cap = moe_lib.round_capacity(max(t_loc, 1), cfg.experts_per_token,
                                 cfg.num_experts_padded, cfg.capacity_factor)

    def body(router_w, experts, x_loc, tm_loc):
        bl, sl, dd = x_loc.shape
        x2d = x_loc.reshape(bl * sl, dd)
        rout = _route(cfg, router_w, x2d, tm_loc.reshape(bl * sl))
        xe, slot_of = _a2a_dispatch(cfg, x2d, rout.top_idx, n_shards,
                                    e_local, cap)
        y = _a2a_ffn_combine(cfg, experts, xe, slot_of, rout.top_w,
                             n_shards, e_local, cap)
        with jax.named_scope("moe.collective"):
            aux = jax.lax.pmean(rout.aux_loss,
                                (EXPERT_AXIS,) + tuple(batch_axes))
        return (y.reshape(bl, sl, dd), aux,
                rout.top_idx.reshape(bl, sl, cfg.experts_per_token))

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), _expert_specs(EXPERT_AXIS),
                  P(batch_axes, EXPERT_AXIS, None),
                  P(batch_axes, EXPERT_AXIS)),
        out_specs=(P(batch_axes, EXPERT_AXIS, None), P(),
                   P(batch_axes, EXPERT_AXIS, None)),
        check_vma=True,
    )(layer_p["router"], layer_p["experts"], x, token_mask)


# ---------------------------------------------------------------------------
# a2a_pipelined: microchunked a2a with comm/compute overlap
# ---------------------------------------------------------------------------

def _a2a_pipelined(cfg, mesh, layer_p, x, token_mask, batch_axes, n_shards,
                   e_local):
    """Software-pipelined a2a: the local token block is split into
    ``cfg.ep_microchunks`` chunks, and a double-buffered ``lax.scan`` keeps
    one chunk's dispatched activations in flight while the previous chunk's
    expert FFN runs — within each scan step, ``dispatch(chunk i+1)`` (the
    all_to_all) has no data dependency on ``ffn_combine(chunk i)`` (the
    expert GEMMs), which is exactly the structure XLA's latency-hiding
    scheduler needs to overlap collective DMA with compute.  The paper
    measures expert comm ≈ expert compute (§5.2); this schedule bounds the
    layer at max(comm, compute) + min(comm, compute)/m instead of their sum
    (see core/perf_model.estimate(..., microchunks=m)).

    Per-chunk capacity is ``round_capacity(T_loc/m)``, so routing and
    per-slot contractions are identical to ``a2a`` whenever capacity is not
    binding — token-exact end-to-end (outputs differ only by XLA's
    reduction-order reassociation at the chunked GEMM shapes, <1e-6, which
    never flips a greedy token; both properties are asserted in
    tests/distributed_checks.py).  Falls back to ``_a2a`` when the chunk
    split does not divide, which itself falls back to ``_decentralized``
    for single-token decode."""
    b, s, d = x.shape
    if s % n_shards != 0:
        # single-token decode: same fallback as _a2a
        return _decentralized(cfg, mesh, layer_p, x, token_mask, batch_axes,
                              n_shards, e_local)
    m = max(getattr(cfg, "ep_microchunks", 1), 1)
    t_loc = (b // max(_axes_size(mesh, batch_axes), 1)) * (s // n_shards)
    if m <= 1 or t_loc % m != 0 or t_loc // m < 1:
        return _a2a(cfg, mesh, layer_p, x, token_mask, batch_axes, n_shards,
                    e_local)
    k = cfg.experts_per_token
    e_pad = cfg.num_experts_padded
    # per-(source shard, chunk, expert) capacity
    cap = moe_lib.round_capacity(t_loc // m, k, e_pad, cfg.capacity_factor)

    def body(router_w, experts, x_loc, tm_loc):
        bl, sl, dd = x_loc.shape
        t = bl * sl
        x2d = x_loc.reshape(t, dd)
        rout = _route(cfg, router_w, x2d, tm_loc.reshape(t))
        tc = t // m
        xc = x2d.reshape(m, tc, dd)
        ic = rout.top_idx.reshape(m, tc, k)
        wc = rout.top_w.reshape(m, tc, k)
        dispatch = lambda xi, ti: _a2a_dispatch(cfg, xi, ti, n_shards,
                                                e_local, cap)
        ffn_combine = lambda xe, so, wi: _a2a_ffn_combine(
            cfg, experts, xe, so, wi, n_shards, e_local, cap)

        # double-buffered pipeline: the carry holds chunk i's in-flight
        # dispatched buffer; each step issues chunk i+1's dispatch BEFORE
        # consuming chunk i, so the two can overlap
        xe0, so0 = dispatch(xc[0], ic[0])

        def step(carry, nxt):
            xe_i, so_i, w_i = carry
            x_n, i_n, w_n = nxt
            xe_next, so_next = dispatch(x_n, i_n)      # comm for chunk i+1
            y_i = ffn_combine(xe_i, so_i, w_i)         # compute for chunk i
            return (xe_next, so_next, w_n), y_i

        (xe_l, so_l, w_l), ys = jax.lax.scan(
            step, (xe0, so0, wc[0]), (xc[1:], ic[1:], wc[1:]))
        y_last = ffn_combine(xe_l, so_l, w_l)          # drain the pipeline
        y = jnp.concatenate([ys.reshape((m - 1) * tc, dd), y_last], axis=0)
        with jax.named_scope("moe.collective"):
            aux = jax.lax.pmean(rout.aux_loss,
                                (EXPERT_AXIS,) + tuple(batch_axes))
        return (y.reshape(bl, sl, dd), aux,
                rout.top_idx.reshape(bl, sl, k))

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), _expert_specs(EXPERT_AXIS),
                  P(batch_axes, EXPERT_AXIS, None),
                  P(batch_axes, EXPERT_AXIS)),
        out_specs=(P(batch_axes, EXPERT_AXIS, None), P(),
                   P(batch_axes, EXPERT_AXIS, None)),
        check_vma=True,
    )(layer_p["router"], layer_p["experts"], x, token_mask)
