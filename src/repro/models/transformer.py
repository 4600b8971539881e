"""Decoder stacks for every assigned architecture family.

Parameters are *prestacked* along a leading layer axis and the stack runs
under ``jax.lax.scan`` — the TPU realization of the paper's expert-wise
weights prestacking (C2): one contiguous array per weight kind, O(1) HLO
size in depth, and a layout the grouped-GEMM kernel can consume directly.
``prestack=False`` (naive baseline, Fig. 4's "unstacking") switches to a
python loop over per-layer arrays.

Families:
  dense / audio / vlm : attention + SwiGLU MLP
  moe                 : attention + expert-parallel MoE (core/expert_parallel)
  ssm                 : Mamba-2 SSD blocks (no MLP)
  hybrid              : RG-LRU x2 + local attention, each followed by MLP
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import expert_parallel
from repro.models import attention, layers, mamba2, rglru

Array = jax.Array


def seq_constrain(mesh, x: Array) -> Array:
    """Megatron-style sequence sharding of the residual stream over the
    'model' axis (beyond-paper activation-memory optimization; collectives
    around attention / MoE dispatch are inserted by GSPMD / shard_map)."""
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    b, s, _ = x.shape
    if s % mesh.shape["model"] != 0 or s < 2048:
        return x
    batch_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    nb = 1
    for a in batch_axes:
        nb *= mesh.shape[a]
    ba = batch_axes if (nb and b % nb == 0) else ()
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(ba, "model", None)))


# ---------------------------------------------------------------------------
# per-layer init (stacked via vmap)
# ---------------------------------------------------------------------------

def _dense_layer_init(cfg, dtype, key):
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": layers.norm_init(cfg.norm, cfg.d_model, dtype),
        "attn": attention.attn_init(k1, cfg, dtype),
        "ln2": layers.norm_init(cfg.norm, cfg.d_model, dtype),
        "mlp": layers.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype),
    }
    return p


def _moe_layer_init(cfg, dtype, key):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    e, d, f = cfg.num_experts_padded, cfg.d_model, cfg.d_ff

    def expert_w(k, din, dout):
        ks = jax.random.split(k, e)
        return jax.vmap(lambda kk: layers.dense_init(kk, din, dout, dtype))(ks)

    experts = {
        "w_gate": expert_w(k3, d, f),
        "w_up": expert_w(k4, d, f),
        "w_down": expert_w(k5, f, d),
    }
    r = max(getattr(cfg, "expert_replication", 1), 1)
    if r > 1:
        # paper §5.3 overlapping placement: store r copies so each expert
        # lives on r expert-parallel shards ("use the extra memory")
        experts = jax.tree.map(
            lambda a: jnp.concatenate([a] * r, axis=0), experts)
    return {
        "ln1": layers.norm_init(cfg.norm, d, dtype),
        "attn": attention.attn_init(k1, cfg, dtype),
        "ln2": layers.norm_init(cfg.norm, d, dtype),
        "router": layers.dense_init(k2, d, e, dtype),
        "experts": experts,
    }


def _ssm_layer_init(cfg, dtype, key):
    return {
        "ln": layers.norm_init(cfg.norm, cfg.d_model, dtype),
        "mamba": mamba2.mamba_init(key, cfg, dtype),
    }


def _hybrid_layer_init(cfg, dtype, key, kind: str):
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": layers.norm_init(cfg.norm, cfg.d_model, dtype),
        "ln2": layers.norm_init(cfg.norm, cfg.d_model, dtype),
        "mlp": layers.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype),
    }
    if kind == "rec":
        p["mix"] = rglru.rglru_init(k1, cfg, dtype)
    else:
        p["mix"] = attention.attn_init(k1, cfg, dtype)
    return p


def hybrid_pattern(cfg) -> list[str]:
    """rec,rec,attn repeating (RecurrentGemma's 1 attention per 2 recurrent)."""
    return ["attn" if i % 3 == 2 else "rec" for i in range(cfg.num_layers)]


def init_blocks(cfg, key) -> dict:
    dtype = cfg.param_dtype_jnp
    L = cfg.num_layers
    if cfg.family == "hybrid":
        pat = hybrid_pattern(cfg)
        rec_keys = jax.random.split(jax.random.fold_in(key, 0),
                                    pat.count("rec"))
        attn_keys = jax.random.split(jax.random.fold_in(key, 1),
                                     max(pat.count("attn"), 1))
        rec = jax.vmap(lambda k: _hybrid_layer_init(cfg, dtype, k, "rec"))(rec_keys)
        attn = jax.vmap(lambda k: _hybrid_layer_init(cfg, dtype, k, "attn"))(attn_keys)
        return {"rec": rec, "attn": attn}
    keys = jax.random.split(key, L)
    if cfg.family == "moe":
        f = lambda k: _moe_layer_init(cfg, dtype, k)
    elif cfg.family == "ssm":
        f = lambda k: _ssm_layer_init(cfg, dtype, k)
    else:
        f = lambda k: _dense_layer_init(cfg, dtype, k)
    return jax.vmap(f)(keys)


# ---------------------------------------------------------------------------
# forward (full-sequence): train and prefill share block bodies
# ---------------------------------------------------------------------------

def _attn_mlp_block(cfg, mesh, layer_p, x, positions, window, mrope_pos,
                    cache_l=None, decode=False, token_mask=None,
                    block_lens=None, block_tables=None, paged_kernel=False):
    """Generic attention(+cache) + {mlp | moe} block.

    Returns (x, new_cache, aux, routed) where ``routed`` is the MoE layer's
    per-token routing decision ((B*S, K) int32, see expert_parallel.moe_layer)
    or None for non-MoE families.  ``token_mask`` (B, S) bool marks tokens
    that may consume expert capacity (batched prefill masks garbage rows).

    ``block_lens`` = (lengths, seg_lens) selects the unified token-block
    path (attention.attn_block_step): an arbitrary (B, T) chunk appended at
    per-row cache offsets — chunked prefill and mixed prefill/decode batches
    share this one body (docs/DESIGN.md §6).  ``block_tables`` (B, NB)
    additionally selects the paged-cache form of that path: ``cache_l``
    holds page-pool leaves and each row reaches its cache through its
    block table (docs/DESIGN.md §7); ``paged_kernel`` swaps that path's
    virtual-cache gather for the Pallas block-table kernel (§11)."""
    with jax.named_scope("attn"):
        h = layers.norm_apply(cfg.norm, layer_p["ln1"], x)
        if block_lens is not None and block_tables is not None:
            lengths, seg_lens = block_lens
            h, new_cache = attention.attn_block_step_paged(
                layer_p["attn"], cfg, cache_l, h, positions, lengths,
                seg_lens, block_tables, window, mrope_pos, mesh=mesh,
                use_kernel=paged_kernel)
        elif block_lens is not None:
            lengths, seg_lens = block_lens
            h, new_cache = attention.attn_block_step(
                layer_p["attn"], cfg, cache_l, h, positions, lengths,
                seg_lens, window, mrope_pos, mesh=mesh)
        elif decode:
            if attention.use_cp_decode(cfg, mesh, cache_l["k"].shape[1]):
                h, new_cache = attention.attn_decode_step_cp(
                    layer_p["attn"], cfg, cache_l, h, positions, window,
                    mesh, mrope_pos)
            else:
                h, new_cache = attention.attn_decode_step(
                    layer_p["attn"], cfg, cache_l, h, positions, window,
                    mrope_pos, mesh=mesh)
        elif cache_l is not None:
            pos2d = positions if positions.ndim == 2 else positions[None]
            h, new_cache = attention.attn_prefill(
                layer_p["attn"], cfg, cache_l, h, pos2d, window, mrope_pos,
                mesh=mesh)
        else:
            pos2d = positions if positions.ndim == 2 else positions[None]
            h = attention.attn_forward(layer_p["attn"], cfg, h, pos2d,
                                       window, mrope_pos, mesh=mesh)
            new_cache = None
        if not decode:
            # constrain at the produce site: the TP partial-sum of wo is
            # reduce-SCATTERED into the sequence-sharded residual instead
            # of all-reduced at full length (Megatron sequence parallelism)
            h = seq_constrain(mesh, h)
        x = x + h
    if cfg.family == "moe":
        with jax.named_scope("moe.route"):
            h = layers.norm_apply(cfg.norm, layer_p["ln2"], x)
        moe_p = {k: layer_p[k] for k in ("router", "experts", "layer")
                 if k in layer_p}
        h, aux, routed = expert_parallel.moe_layer(cfg, mesh, moe_p, h,
                                                   token_mask)
    else:
        h = layers.norm_apply(cfg.norm, layer_p["ln2"], x)
        h = layers.mlp_apply(layer_p["mlp"], h, cfg.act)
        aux = jnp.zeros((), jnp.float32)
        routed = None
    if not decode:
        h = seq_constrain(mesh, h)
    return x + h, new_cache, aux, routed


def _ssm_block(cfg, layer_p, x, cache_l=None, decode=False):
    h = layers.norm_apply(cfg.norm, layer_p["ln"], x)
    if decode:
        h, new_cache = mamba2.mamba_decode_step(layer_p["mamba"], cfg, cache_l, h)
    elif cache_l is not None:
        h, new_cache = mamba2.mamba_forward(layer_p["mamba"], cfg, h,
                                            state=cache_l)
    else:
        h = mamba2.mamba_forward(layer_p["mamba"], cfg, h)
        new_cache = None
    return x + h, new_cache, jnp.zeros((), jnp.float32)


def _hybrid_block(cfg, layer_p, kind, x, positions, cache_l=None, decode=False,
                  mesh=None):
    h = layers.norm_apply(cfg.norm, layer_p["ln1"], x)
    if kind == "rec":
        if decode:
            h, new_cache = rglru.rglru_decode_step(layer_p["mix"], cfg, cache_l, h)
        elif cache_l is not None:
            h, new_cache = rglru.rglru_forward(layer_p["mix"], cfg, h,
                                               state=cache_l)
        else:
            h = rglru.rglru_forward(layer_p["mix"], cfg, h)
            new_cache = None
    else:
        w = cfg.sliding_window
        if decode:
            if attention.use_cp_decode(cfg, mesh, cache_l["k"].shape[1]):
                h, new_cache = attention.attn_decode_step_cp(
                    layer_p["mix"], cfg, cache_l, h, positions, w, mesh)
            else:
                h, new_cache = attention.attn_decode_step(
                    layer_p["mix"], cfg, cache_l, h, positions, w, mesh=mesh)
        elif cache_l is not None:
            pos2d = positions if positions.ndim == 2 else positions[None]
            h, new_cache = attention.attn_prefill(layer_p["mix"], cfg, cache_l,
                                                  h, pos2d, w, mesh=mesh)
        else:
            pos2d = positions if positions.ndim == 2 else positions[None]
            h = attention.attn_forward(layer_p["mix"], cfg, h, pos2d, w,
                                       mesh=mesh)
            new_cache = None
    x = x + h
    h = layers.norm_apply(cfg.norm, layer_p["ln2"], x)
    h = layers.mlp_apply(layer_p["mlp"], h, cfg.act)
    return x + h, new_cache, jnp.zeros((), jnp.float32)


def _maybe_remat(cfg, fn):
    return jax.checkpoint(fn) if cfg.remat else fn


def forward_stack(cfg, mesh, blocks, x, positions, window, mrope_pos=None):
    """Run all layers over a full sequence. Returns (x, total_aux)."""
    if cfg.family == "hybrid":
        pat = hybrid_pattern(cfg)
        aux = jnp.zeros((), jnp.float32)
        ri = ai = 0
        for kind in pat:
            if kind == "rec":
                lp = jax.tree.map(lambda a: a[ri], blocks["rec"])
                ri += 1
            else:
                lp = jax.tree.map(lambda a: a[ai], blocks["attn"])
                ai += 1
            fn = _maybe_remat(cfg, lambda xx, lp=lp, kind=kind: _hybrid_block(
                cfg, lp, kind, seq_constrain(mesh, xx), positions,
                mesh=mesh)[0])
            x = fn(x)
        return x, aux

    if cfg.family == "ssm":
        def body(xx, lp):
            out, _, aux = _ssm_block(cfg, lp, seq_constrain(mesh, xx))
            return out, aux
    else:
        def body(xx, lp):
            out, _, aux, _ = _attn_mlp_block(cfg, mesh, lp,
                                             seq_constrain(mesh, xx),
                                             positions, window, mrope_pos)
            return out, aux

    if cfg.prestack:
        x, auxs = jax.lax.scan(
            lambda c, lp: _maybe_remat(cfg, body)(c, lp), x, blocks)
        aux = jnp.sum(auxs)
    else:
        # naive "unstacked" layout: python loop over per-layer slices
        aux = jnp.zeros((), jnp.float32)
        for i in range(cfg.num_layers):
            lp = jax.tree.map(lambda a: a[i], blocks)
            x, a = _maybe_remat(cfg, body)(x, lp)
            aux = aux + a
    return x, aux


# ---------------------------------------------------------------------------
# cached paths (prefill / decode) — caches stacked along the layer axis
# ---------------------------------------------------------------------------

def stack_cache_spec(cfg, batch: int, cache_len: int, dtype):
    L = cfg.num_layers
    if cfg.family == "ssm":
        per = mamba2.mamba_cache_spec(cfg, batch, dtype)
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((L,) + s.shape, s.dtype), per)
    if cfg.family == "hybrid":
        pat = hybrid_pattern(cfg)
        rec = rglru.rglru_cache_spec(cfg, batch, dtype)
        attn_len = min(cache_len, cfg.sliding_window or cache_len)
        att = attention.layer_cache_spec(cfg, batch, attn_len, dtype)
        return {
            "rec": jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                (pat.count("rec"),) + s.shape, s.dtype), rec),
            "attn": jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                (pat.count("attn"),) + s.shape, s.dtype), att),
        }
    win = effective_window(cfg, cache_len)
    clen = min(cache_len, win) if win else cache_len
    per = attention.layer_cache_spec(cfg, batch, clen, dtype)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((L,) + s.shape, s.dtype), per)


def init_stack_cache(cfg, batch: int, cache_len: int, dtype):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        stack_cache_spec(cfg, batch, cache_len, dtype))


def paged_stack_cache_spec(cfg, num_pages: int, page_size: int, dtype):
    """Stacked paged pool: one ``(L, num_pages, page_size, Hkv, hd)`` leaf
    per cache kind (docs/DESIGN.md §7).  Only token-input attention
    families page their cache; ssm/hybrid state is per-row and stays on
    the contiguous layout."""
    if cfg.family not in ("dense", "moe", "vlm", "audio"):
        raise NotImplementedError(
            f"paged KV cache supports attention-cache families, not "
            f"{cfg.family!r}")
    per = attention.paged_layer_cache_spec(cfg, num_pages, page_size, dtype)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((cfg.num_layers,) + s.shape, s.dtype),
        per)


def init_paged_stack_cache(cfg, num_pages: int, page_size: int, dtype):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        paged_stack_cache_spec(cfg, num_pages, page_size,
                                               dtype))


def effective_window(cfg, seq_len: int) -> int | None:
    """Window actually used at this sequence length: native sliding window if
    the arch has one; the long-context SWA variant kicks in beyond
    ``cfg.long_context_threshold`` for otherwise-full-attention archs."""
    if cfg.sliding_window:
        return cfg.sliding_window
    if cfg.long_context_window and seq_len >= cfg.long_context_threshold:
        return cfg.long_context_window
    return None


def _scan_stack_with_cache(cfg, blocks, x, cache, layer_body,
                           whole_experts: bool = False):
    """Run ``layer_body`` over all layers with the *whole* stacked cache as
    part of the scan carry (donation-safe zero-copy layout).

    The cache used to stream through the scan as an xs input and come back
    stacked as a ys output — a layout that forces XLA to double-buffer it
    (fresh ys allocation + full-size copies every step) even when the jit
    caller donates the buffer.  Carrying the stack instead and updating
    layer l's slice with ``dynamic_update_index_in_dim`` lets the compiled
    while-loop alias the donated input in place: the decode step's cache
    traffic is exactly one layer-slice write per layer, never a full-cache
    copy (regression-tested against the lowered HLO in
    tests/test_zero_copy.py).

    ``layer_body(x, layer_p, cache_l) -> (x, new_cache_l, routed)``.
    Returns (x, new_cache, routing_ys).

    ``whole_experts``: the MoE expert stacks do not ride the scan;
    ``layer_p`` carries them whole with the layer's index under
    ``"layer"`` (see ``expert_parallel.moe_layer``), so the grouped GEMM
    reads layer l's experts in place instead of from a copied slice.
    Blocks that take another MoE form keep the scan's slices, which
    XLA's dots read in place."""
    experts = blocks["experts"] if whole_experts else None
    if experts is not None:
        blocks = {k: v for k, v in blocks.items() if k != "experts"}

    def body(carry, inp):
        xx, full_cache = carry
        lp, l = inp
        if experts is not None:
            lp = dict(lp, experts=experts, layer=l)
        with jax.named_scope("layer.cache"):
            cl = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, l, axis=0,
                                                       keepdims=False),
                full_cache)
        xx, ncl, routed = layer_body(xx, lp, cl)
        with jax.named_scope("layer.cache"):
            full_cache = jax.tree.map(
                lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, l,
                                                                 axis=0),
                full_cache, ncl)
        return (xx, full_cache), routed

    # layer.loop: the loop's own ops (each layer's weight slices, the
    # counter); layer.cache: the layer's cache slice in and out of the carry
    with jax.named_scope("layer.loop"):
        (x, new_cache), routing = jax.lax.scan(
            body, (x, cache), (blocks, jnp.arange(cfg.num_layers)))
    return x, new_cache, routing


def decode_stack(cfg, mesh, blocks, x, lengths, cache, window,
                 mrope_pos=None, token_mask=None):
    """One-token decode through all layers. x: (B,1,D).

    Returns (x, new_cache, routing) — ``routing`` is the stacked per-layer
    MoE decision (L, B, K) int32 for the moe family, else None.  It rides
    out of the scan as a ys output, so capturing it costs no extra router
    evaluation (the serving engine's tracker consumes it device-side).

    The cache travels through the layer scan as a carry updated in place
    (see ``_scan_stack_with_cache``), so a caller that donates it gets a
    zero-copy steady-state decode step."""
    if cfg.family == "hybrid":
        pat = hybrid_pattern(cfg)
        new_rec, new_attn = [], []
        ri = ai = 0
        for kind in pat:
            if kind == "rec":
                lp = jax.tree.map(lambda a: a[ri], blocks["rec"])
                cl = jax.tree.map(lambda a: a[ri], cache["rec"])
                x, nc, _ = _hybrid_block(cfg, lp, "rec", x, lengths, cl,
                                         decode=True, mesh=mesh)
                new_rec.append(nc)
                ri += 1
            else:
                lp = jax.tree.map(lambda a: a[ai], blocks["attn"])
                cl = jax.tree.map(lambda a: a[ai], cache["attn"])
                x, nc, _ = _hybrid_block(cfg, lp, "attn", x, lengths, cl,
                                         decode=True, mesh=mesh)
                new_attn.append(nc)
                ai += 1
        stack = lambda lst: jax.tree.map(lambda *a: jnp.stack(a), *lst)
        return x, {"rec": stack(new_rec), "attn": stack(new_attn)}, None

    if cfg.family == "ssm":
        def layer_body(xx, lp, cl):
            out, nc, _ = _ssm_block(cfg, lp, xx, cl, decode=True)
            return out, nc, jnp.zeros((), jnp.int32)
        x, new_cache, _ = _scan_stack_with_cache(cfg, blocks, x, cache,
                                                 layer_body)
        return x, new_cache, None

    def layer_body(xx, lp, cl):
        out, nc, _, routed = _attn_mlp_block(cfg, mesh, lp, xx, lengths,
                                             window, mrope_pos, cl,
                                             decode=True,
                                             token_mask=token_mask)
        if routed is None:           # dense/vlm/audio: no capture
            routed = jnp.zeros((), jnp.int32)
        return out, nc, routed

    x, new_cache, routing = _scan_stack_with_cache(cfg, blocks, x, cache,
                                                   layer_body)
    if cfg.family != "moe":
        routing = None
    return x, new_cache, routing


def unified_stack(cfg, mesh, blocks, x, positions, lengths, seg_lens, cache,
                  window, mrope_pos=None, token_mask=None, block_tables=None,
                  paged_kernel=False):
    """Length-agnostic token-block forward through all layers — the ONE
    layer body behind chunked prefill, decode, and mixed prefill/decode
    batches (the prefill/decode twin stacks remain as the
    ``unified_step=False`` reference path).

    x: (B, T, D); positions: (B, T) absolute; lengths/seg_lens: (B,) cache
    offsets and per-row valid-token counts.  Returns (x, new_cache,
    routing) with routing (L, B*T, K) int32 for the moe family (invalid
    tokens read the E_pad sentinel), else None.  The cache rides the layer
    scan as a carry (``_scan_stack_with_cache``), so a donating caller
    keeps the zero-copy hot loop.  With ``block_tables`` (B, NB) the cache
    is the paged pool of ``paged_stack_cache_spec`` and every row reaches
    its slots through its block table (docs/DESIGN.md §7) — same carry,
    same zero-copy property.  ``paged_kernel`` routes the paged path
    through the Pallas block-table attention kernel instead of the
    virtual-cache gather (docs/DESIGN.md §11)."""
    if cfg.family not in ("dense", "moe", "vlm", "audio"):
        raise NotImplementedError(
            f"unified_stack supports attention-cache families, not "
            f"{cfg.family!r} (use the prefill/decode reference path)")

    def layer_body(xx, lp, cl):
        out, nc, _, routed = _attn_mlp_block(cfg, mesh, lp, xx, positions,
                                             window, mrope_pos, cl,
                                             token_mask=token_mask,
                                             block_lens=(lengths, seg_lens),
                                             block_tables=block_tables,
                                             paged_kernel=paged_kernel)
        if routed is None:
            routed = jnp.zeros((), jnp.int32)
        return out, nc, routed

    grouped = cfg.family == "moe" and expert_parallel.block_form(
        cfg, mesh, *x.shape[:2], blocks["experts"]) == "grouped"
    x, new_cache, routing = _scan_stack_with_cache(
        cfg, blocks, x, cache, layer_body, whole_experts=grouped)
    if cfg.family != "moe":
        routing = None
    return x, new_cache, routing


def prefill_stack(cfg, mesh, blocks, x, positions, cache, window,
                  mrope_pos=None, token_mask=None):
    """Full-sequence forward that fills the cache.

    Returns (x, new_cache, routing) — ``routing`` is (L, B*S, K) int32 for
    the moe family (per-layer device-side routing capture), else None."""
    if cfg.family == "hybrid":
        pat = hybrid_pattern(cfg)
        new_rec, new_attn = [], []
        ri = ai = 0
        for kind in pat:
            x = seq_constrain(mesh, x)
            if kind == "rec":
                lp = jax.tree.map(lambda a: a[ri], blocks["rec"])
                cl = jax.tree.map(lambda a: a[ri], cache["rec"])
                x, nc, _ = _hybrid_block(cfg, lp, "rec", x, positions, cl,
                                         mesh=mesh)
                new_rec.append(nc)
                ri += 1
            else:
                lp = jax.tree.map(lambda a: a[ai], blocks["attn"])
                cl = jax.tree.map(lambda a: a[ai], cache["attn"])
                x, nc, _ = _hybrid_block(cfg, lp, "attn", x, positions, cl,
                                         mesh=mesh)
                new_attn.append(nc)
                ai += 1
        stack = lambda lst: jax.tree.map(lambda *a: jnp.stack(a), *lst)
        return x, {"rec": stack(new_rec), "attn": stack(new_attn)}, None

    if cfg.family == "ssm":
        def layer_body(xx, lp, cl):
            out, nc, _ = _ssm_block(cfg, lp, seq_constrain(mesh, xx), cl)
            return out, nc, jnp.zeros((), jnp.int32)
        x, new_cache, _ = _scan_stack_with_cache(cfg, blocks, x, cache,
                                                 layer_body)
        return x, new_cache, None

    def layer_body(xx, lp, cl):
        out, nc, _, routed = _attn_mlp_block(cfg, mesh, lp,
                                             seq_constrain(mesh, xx),
                                             positions, window, mrope_pos,
                                             cl, token_mask=token_mask)
        if routed is None:
            routed = jnp.zeros((), jnp.int32)
        return out, nc, routed

    x, new_cache, routing = _scan_stack_with_cache(cfg, blocks, x, cache,
                                                   layer_body)
    if cfg.family != "moe":
        routing = None
    return x, new_cache, routing
