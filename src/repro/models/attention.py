"""GQA attention with causal / sliding-window masking and a KV cache.

Three entry points share one core:
  * ``attend(q, k, v, ...)``       — masked SDPA, fp32 softmax
  * ``attn_forward(...)``          — train / prefill over a full sequence
  * ``attn_decode_step(...)``      — one new token against a cache

Cache layout (per layer): ``k``/``v`` of shape (B, S_max, H_kv, hd) plus a
shared per-sequence ``lengths`` (B,) kept at the model level.  For sliding
window attention the cache is a ring buffer of size ``window`` and positions
are stored modulo the window.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.models import layers

Array = jax.Array
NEG_INF = -1e9  # large-negative instead of -inf: keeps softmax NaN-free on fully-masked rows


def gqa_repeat(k: Array, q_heads: int) -> Array:
    """(..., H_kv, hd) -> (..., H_q, hd) by repeating each kv head."""
    kv_heads = k.shape[-2]
    if kv_heads == q_heads:
        return k
    rep = q_heads // kv_heads
    return jnp.repeat(k, rep, axis=-2)


def attend(q: Array, k: Array, v: Array, mask: Array, scale: float) -> Array:
    """q: (B,Sq,Hq,hd) k/v: (B,Sk,Hq,hd) mask: (B,1,Sq,Sk) bool -> (B,Sq,Hq,hd)."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# chunked (flash-style) attention — O(S) memory for long sequences
# ---------------------------------------------------------------------------

CHUNK_THRESHOLD = 2048   # switch to chunked attention at/above this S
# K/V are re-read once per q-chunk, so total k/v HBM traffic scales with
# S/Q_CHUNK: larger q-chunks amortize the K pass (measured 2x memory-term
# win on deepseek-67b prefill_32k going 512 -> 2048)
Q_CHUNK = 2048
K_CHUNK = 1024


def attend_chunked(q: Array, k: Array, v: Array, q_pos: Array, k_pos: Array,
                   window: int | None, scale: float,
                   q_chunk: int = Q_CHUNK, k_chunk: int = K_CHUNK) -> Array:
    """Online-softmax attention, never materializing (Sq, Sk) logits.

    q: (B,Sq,H,hd); k/v: (B,Sk,H,hd); q_pos: (B,Sq); k_pos: (B,Sk).
    Causal (k_pos <= q_pos) with optional sliding ``window``.  This is the
    pure-JAX oracle of the Pallas flash kernel (kernels/flash_attn.py) and
    the long-sequence path used by train/prefill.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    qc = min(q_chunk, sq)
    kc = min(k_chunk, sk)
    nq, nk = -(-sq // qc), -(-sk // kc)
    # pad to chunk multiples (positions padded with -1 / huge so masks kill them)
    qp = jnp.pad(q, ((0, 0), (0, nq * qc - sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * kc - sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * kc - sk), (0, 0), (0, 0)))
    qpos = jnp.pad(q_pos, ((0, 0), (0, nq * qc - sq)), constant_values=-1)
    kpos = jnp.pad(k_pos, ((0, 0), (0, nk * kc - sk)),
                   constant_values=2**30)

    qp = qp.reshape(b, nq, qc, h, hd).transpose(1, 0, 2, 3, 4)
    qpos_c = qpos.reshape(b, nq, qc).transpose(1, 0, 2)
    # pre-transpose k/v ONCE into MXU-operand layout — doing it inside the
    # q-loop re-transposes every k-chunk nq times (measured 50% of prefill
    # HBM traffic on deepseek-67b before this hoist)
    kp = kp.reshape(b, nk, kc, h, hd).transpose(1, 0, 3, 4, 2)  # (nk,B,H,hd,kc)
    vp = vp.reshape(b, nk, kc, h, hd).transpose(1, 0, 3, 2, 4)  # (nk,B,H,kc,hd)
    kpos_c = kpos.reshape(b, nk, kc).transpose(1, 0, 2)

    def q_block(args):
        qb, qpb = args                       # (B,qc,H,hd), (B,qc)
        qbt = qb.transpose(0, 2, 1, 3)       # (B,H,qc,hd) once per q-chunk

        def k_step(carry, kargs):
            acc, m, l = carry
            kb, vb, kpb = kargs              # (B,H,hd,kc), (B,H,kc,hd), (B,kc)
            logit = jnp.einsum("bhqd,bhdk->bhqk", qbt, kb,
                               preferred_element_type=jnp.float32) * scale
            msk = kpb[:, None, None, :] <= qpb[:, None, :, None]
            if window is not None:
                msk = msk & (kpb[:, None, None, :]
                             > qpb[:, None, :, None] - window)
            logit = jnp.where(msk, logit, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(logit, axis=-1))
            p = jnp.exp(logit - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, h, qc, hd), jnp.float32)
        m0 = jnp.full((b, h, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, qc), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(k_step, (acc0, m0, l0),
                                      (kp, vp, kpos_c))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(0, 2, 1, 3).astype(q.dtype)   # (B,qc,H,hd)

    # checkpoint each q-block: the inner k-scan's per-step residuals
    # (logits/probs stacks of shape (nq, nk, B, H, qc, kc)) are recomputed
    # in the backward instead of being written to HBM — the flash-attention
    # backward strategy expressed in pure JAX
    out = jax.lax.map(jax.checkpoint(q_block), (qp, qpos_c))  # (nq,B,qc,H,hd)
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, nq * qc, h, hd)
    return out[:, :sq]


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def attn_init(key: Array, cfg, dtype) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(ks[0], d, hq * hd, dtype),
        "wk": layers.dense_init(ks[1], d, hkv * hd, dtype),
        "wv": layers.dense_init(ks[2], d, hkv * hd, dtype),
        "wo": layers.dense_init(ks[3], hq * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(p: dict, cfg, x: Array, positions, mrope_positions=None,
                 mesh=None):
    b, s, _ = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = quant.qdot("bsd,de->bse", x, p["wq"])
    k = quant.qdot("bsd,de->bse", x, p["wk"])
    v = quant.qdot("bsd,de->bse", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    # pin the head layout at the reshape: serve-mode wk/wv shard the
    # flattened Hkv*hd dim, and letting GSPMD keep a mid-head split through
    # the per-head norm/rope below miscompiles on jaxlib 0.4.x CPU SPMD
    # (head_constrain replicates heads whenever H % tp != 0)
    q = head_constrain(mesh, q.reshape(b, s, hq, hd))
    k = head_constrain(mesh, k.reshape(b, s, hkv, hd))
    v = head_constrain(mesh, v.reshape(b, s, hkv, hd))
    if cfg.qk_norm:
        q = layers.rms_norm(p["q_norm"], q)
        k = layers.rms_norm(p["k_norm"], k)
    if cfg.mrope and mrope_positions is not None:
        q = layers.apply_mrope(q, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
        k = layers.apply_mrope(k, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.use_rope:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def head_constrain(mesh, t: Array) -> Array:
    """Pin (B, S, H, hd) activations to head sharding over the 'model' axis —
    forces GSPMD into head-parallel attention (logits (B, H/tp, Sq, Sk) per
    device) instead of keeping sequence sharding through the softmax.

    When the head count does not divide the axis the heads are pinned to
    *replicated* instead of left to GSPMD: the propagated layout would split
    single heads mid-``hd`` (serve-mode wk/wv shard the flattened Hkv*hd
    dim), which is never a layout we want — and the jaxlib 0.4.x CPU SPMD
    partitioner miscompiles per-head norm/rope over such a split."""
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return t
    if t.ndim != 4:
        return t
    from jax.sharding import NamedSharding, PartitionSpec as P
    batch_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    nb = 1
    for a in batch_axes:
        nb *= mesh.shape[a]
    ba = batch_axes if (nb and t.shape[0] % nb == 0) else ()
    head = "model" if t.shape[2] % mesh.shape["model"] == 0 else None
    return jax.lax.with_sharding_constraint(
        t, NamedSharding(mesh, P(ba, None, head, None)))


def attn_forward(p: dict, cfg, x: Array, positions: Array, window: int | None,
                 mrope_positions: Array | None = None, mesh=None) -> Array:
    """x: (B, S, D); positions: (B, S) int32. Returns (B, S, D)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, positions, mrope_positions, mesh)
    k = gqa_repeat(k, cfg.num_heads)
    v = gqa_repeat(v, cfg.num_heads)
    # q is already head-pinned inside _project_qkv; k/v changed head count
    k = head_constrain(mesh, k)
    v = head_constrain(mesh, v)
    if getattr(cfg, "use_flash_kernel", False):
        from repro.kernels import ops as kernel_ops
        out = kernel_ops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True, window=window)
        out = out.transpose(0, 2, 1, 3)
    elif s >= CHUNK_THRESHOLD:
        out = attend_chunked(q, k, v, positions, positions, window,
                             cfg.head_dim ** -0.5)
    else:
        qp = positions[:, None, :, None]  # (B,1,Sq,1)
        kp = positions[:, None, None, :]  # (B,1,1,Sk)
        mask = kp <= qp
        if window is not None:
            mask = mask & (kp > qp - window)
        out = attend(q, k, v, mask, cfg.head_dim ** -0.5)
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
    return quant.qdot("bse,ed->bsd", out, p["wo"])


# ---------------------------------------------------------------------------
# cached decode
# ---------------------------------------------------------------------------

def kv_quantized(cfg) -> bool:
    return getattr(cfg, "kv_cache_dtype", "") == "int8"


def init_layer_cache(cfg, batch: int, cache_len: int, dtype) -> dict:
    shape = (batch, cache_len, cfg.num_kv_heads, cfg.head_dim)
    if kv_quantized(cfg):
        sshape = shape[:-1] + (1,)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float32),
                "v_scale": jnp.zeros(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def layer_cache_spec(cfg, batch: int, cache_len: int, dtype):
    shape = (batch, cache_len, cfg.num_kv_heads, cfg.head_dim)
    if kv_quantized(cfg):
        sshape = shape[:-1] + (1,)
        return {"k": jax.ShapeDtypeStruct(shape, jnp.int8),
                "v": jax.ShapeDtypeStruct(shape, jnp.int8),
                "k_scale": jax.ShapeDtypeStruct(sshape, jnp.float32),
                "v_scale": jax.ShapeDtypeStruct(sshape, jnp.float32)}
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


def quantize_kv(x: Array) -> tuple[Array, Array]:
    """Per-(token, head) symmetric int8: x (..., hd) -> (int8, fp32 scale).

    Thin wrapper over ``core/quant.absmax_quantize`` — ONE quantization
    numeric policy repo-wide (docs/DESIGN.md §8): a single block spanning
    the whole ``hd`` axis reproduces the original per-row absmax/127
    quantizer bit for bit (the block count is 1, so the scale keeps its
    (..., 1) keepdims shape)."""
    return quant.absmax_quantize(x, bits=8, block=x.shape[-1], axis=-1)


def dequantize_kv(q: Array, scale: Array, dtype) -> Array:
    """Inverse wrapper: one block over ``hd`` makes the per-block repeat a
    plain broadcast — bit-identical to the pre-refactor ``q * scale``."""
    return quant.absmax_dequantize(q, scale, block=q.shape[-1], axis=-1,
                                   dtype=dtype)


def _update_cache(cache_kv: Array, new_kv: Array, lengths: Array, ring: bool) -> Array:
    """Insert new_kv (B, 1, Hkv, hd) at per-sequence slot lengths (B,)."""
    cache_len = cache_kv.shape[1]
    slot = lengths % cache_len if ring else lengths

    def upd(c, n, s):
        return jax.lax.dynamic_update_slice(c, n, (s, 0, 0))

    return jax.vmap(upd)(cache_kv, new_kv, slot)


def _attend_grouped_block(cfg, q: Array, k_cache: Array, v_cache: Array,
                          mask: Array) -> Array:
    """Grouped-GQA attention of a (B, Tq) query block over the cache WITHOUT
    materializing ``gqa_repeat``: repeating Hkv cache heads to Hq reads (and,
    in the lowered HLO, copies) the entire KV cache G=Hq/Hkv times per layer
    per step — it was the residual full-cache-sized copy in the decode
    program after buffer donation.  Indexing kv heads per q-head group keeps
    the cache read exactly once (same trick as the CP-decode shard body and
    any TPU flash decode kernel).

    q: (B,Tq,Hq,hd); k_cache/v_cache: (B,S,Hkv,hd); mask: (B,Tq,S) bool.
    Returns (B,Tq,Hq,hd).  Tq=1 is the decode step; Tq>1 is the unified
    chunked-prefill / mixed-batch step (attn_block_step)."""
    hkv = k_cache.shape[2]
    g = cfg.num_heads // hkv
    scale = cfg.head_dim ** -0.5
    tq = q.shape[1]
    qg = q.reshape(q.shape[0], tq, hkv, g, q.shape[-1])      # (B,Tq,Hkv,G,hd)
    logits = jnp.einsum("bqhgd,bshd->bhgqs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    mask5 = mask[:, None, None, :, :]                        # (B,1,1,Tq,S)
    logits = jnp.where(mask5, logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhgqs,bshd->bhgqd", probs.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    b, _, _, _, hd = out.shape
    out = out.astype(q.dtype).transpose(0, 3, 1, 2, 4)       # (B,Tq,Hkv,G,hd)
    return out.reshape(b, tq, hkv * g, hd)


def _attend_grouped_decode(cfg, q: Array, k_cache: Array, v_cache: Array,
                           mask: Array) -> Array:
    """Single-step (Tq=1) grouped-GQA attention; mask: (B,S) bool."""
    return _attend_grouped_block(cfg, q, k_cache, v_cache, mask[:, None, :])


def attn_decode_step(p: dict, cfg, cache: dict, x: Array, lengths: Array,
                     window: int | None,
                     mrope_positions: Array | None = None,
                     mesh=None) -> tuple[Array, dict]:
    """x: (B, 1, D); lengths: (B,) tokens already in cache. Returns (B,1,D), cache'."""
    b = x.shape[0]
    cache_len = cache["k"].shape[1]
    ring = window is not None and cache_len == window
    q, k_new, v_new = _project_qkv(p, cfg, x, lengths[:, None], mrope_positions,
                                   mesh)
    if kv_quantized(cfg):
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        new_cache = {
            "k": _update_cache(cache["k"], kq, lengths, ring),
            "v": _update_cache(cache["v"], vq, lengths, ring),
            "k_scale": _update_cache(cache["k_scale"], ks, lengths, ring),
            "v_scale": _update_cache(cache["v_scale"], vs, lengths, ring),
        }
        k_cache = dequantize_kv(new_cache["k"], new_cache["k_scale"], x.dtype)
        v_cache = dequantize_kv(new_cache["v"], new_cache["v_scale"], x.dtype)
    else:
        k_cache = _update_cache(cache["k"], k_new, lengths, ring)
        v_cache = _update_cache(cache["v"], v_new, lengths, ring)
        new_cache = {"k": k_cache, "v": v_cache}

    idx = jnp.arange(cache_len)[None, :]  # (1, S)
    if ring:
        # slot i holds absolute position: valid iff that position is within
        # the last `window` tokens of [0, lengths].
        n_valid = jnp.minimum(lengths[:, None] + 1, cache_len)
        # with ring writes, every slot < n_valid is a live position
        mask = idx < n_valid
    else:
        mask = idx <= lengths[:, None]
        if window is not None:
            mask = mask & (idx > lengths[:, None] - window)
    out = _attend_grouped_decode(cfg, q, k_cache, v_cache, mask)
    out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim)
    out = quant.qdot("bse,ed->bsd", out, p["wo"])
    return out, new_cache


def _update_cache_block(cache_kv: Array, new_kv: Array, lengths: Array,
                        seg_lens: Array, ring: bool) -> Array:
    """Insert new_kv (B, T, Hkv, hd) at per-row offsets ``lengths`` (B,),
    keeping only each row's first ``seg_lens[b]`` tokens — the block
    generalization of ``_update_cache``'s single-slot write.

    Non-ring path: a per-row read-modify-write of one T-sized block via
    ``dynamic_slice`` + ``dynamic_update_slice`` (donation-friendly: the
    only cache traffic is the T-block, never a full-cache copy).  The slice
    start is clamped to ``S - T`` so rows whose offset sits near the cache
    end never smear earlier slots; the in-block merge keeps the original
    value everywhere the (clamped) window does not hold a valid new token.

    Ring path (cache_len == window): slots wrap, so a masked per-token
    scatter writes position p at slot p % S and drops invalid tokens via an
    out-of-bounds sentinel index."""
    b, t = new_kv.shape[:2]
    cache_len = cache_kv.shape[1]
    if t > cache_len:
        raise ValueError(f"block length {t} exceeds cache length {cache_len}")
    new_kv = new_kv.astype(cache_kv.dtype)
    if ring:
        slots = lengths[:, None] + jnp.arange(t)[None, :]
        valid = jnp.arange(t)[None, :] < seg_lens[:, None]
        slots = jnp.where(valid, slots % cache_len, cache_len)  # OOB -> drop
        return jax.vmap(lambda c, n, s: c.at[s].set(n, mode="drop"))(
            cache_kv, new_kv, slots)

    def upd(c_row, n_row, off, sl):
        s0 = jnp.clip(off, 0, cache_len - t)
        old = jax.lax.dynamic_slice_in_dim(c_row, s0, t, axis=0)
        ci = s0 + jnp.arange(t) - off            # index into the new block
        ok = (ci >= 0) & (ci < sl)
        new = jnp.take(n_row, jnp.clip(ci, 0, t - 1), axis=0)
        blk = jnp.where(ok.reshape((t,) + (1,) * (n_row.ndim - 1)), new, old)
        return jax.lax.dynamic_update_slice_in_dim(c_row, blk, s0, axis=0)

    return jax.vmap(upd)(cache_kv, new_kv, lengths, seg_lens)


def block_slot_positions(lengths: Array, seg_lens: Array, cache_len: int,
                         ring: bool) -> Array:
    """Absolute position held by each cache slot after a block write.

    Non-ring caches store position p at slot p, so the map is just the slot
    index.  Ring caches store p at slot p % S; under the write invariant the
    slot holds the *largest* position <= hi = lengths + seg_lens - 1 congruent
    to it, and slots whose implied position is negative were never written.
    Returns (B, S) int32 (negative = slot not yet written)."""
    sidx = jnp.arange(cache_len, dtype=jnp.int32)[None, :]
    if not ring:
        return jnp.broadcast_to(sidx, (lengths.shape[0], cache_len))
    hi = (lengths + seg_lens - 1)[:, None]
    return hi - ((hi - sidx) % cache_len)


def attn_block_step(p: dict, cfg, cache: dict, x: Array, positions: Array,
                    lengths: Array, seg_lens: Array, window: int | None,
                    mrope_positions: Array | None = None,
                    mesh=None) -> tuple[Array, dict]:
    """Unified length-agnostic cached attention over a (B, T) token block.

    Each row b holds ``seg_lens[b]`` valid tokens (0..T) that continue its
    sequence at cache offset ``lengths[b]`` — T=1 with seg_lens=1 is a
    decode step, seg_lens=T at lengths=0 is whole-prompt prefill, and any
    mix of per-row values is a chunked-prefill / mixed prefill+decode batch
    (docs/DESIGN.md §6).  Position-offset causal masking makes token t of
    row b (absolute position ``positions[b, t]``) attend exactly the cache
    slots holding positions <= its own (and > pos - window under SWA);
    invalid tokens (t >= seg_lens[b]) get a fully-masked row, a dropped
    cache write, and garbage output the caller must ignore (the MoE layer
    dead-routes them via token_mask).

    x: (B, T, D); positions: (B, T) int32 absolute; lengths/seg_lens: (B,).
    Returns ((B, T, D), cache')."""
    b, t, _ = x.shape
    cache_len = cache["k"].shape[1]
    ring = window is not None and cache_len == window
    if ring and t > 1:
        # a multi-token chunk written into a wrapped ring BEFORE attention
        # overwrites slots whose old positions are still inside earlier
        # chunk tokens' windows — those keys would be silently lost.  Ring
        # caches therefore only take width-1 blocks (== the decode step);
        # the engine falls back to the reference path for ring-cache archs.
        raise ValueError(
            f"ring KV cache (window == cache_len == {cache_len}) supports "
            f"only width-1 blocks, got T={t}")
    q, k_new, v_new = _project_qkv(p, cfg, x, positions, mrope_positions,
                                   mesh)
    if kv_quantized(cfg):
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        new_cache = {
            kk: _update_cache_block(cache[kk], nn, lengths, seg_lens, ring)
            for kk, nn in (("k", kq), ("v", vq),
                           ("k_scale", ks), ("v_scale", vs))
        }
        k_cache = dequantize_kv(new_cache["k"], new_cache["k_scale"], x.dtype)
        v_cache = dequantize_kv(new_cache["v"], new_cache["v_scale"], x.dtype)
    else:
        k_cache = _update_cache_block(cache["k"], k_new, lengths, seg_lens,
                                      ring)
        v_cache = _update_cache_block(cache["v"], v_new, lengths, seg_lens,
                                      ring)
        new_cache = {"k": k_cache, "v": v_cache}

    slot_pos = block_slot_positions(lengths, seg_lens, cache_len, ring)
    valid = jnp.arange(t)[None, :] < seg_lens[:, None]
    qp = jnp.where(valid, positions, -1)                     # (B, T)
    mask = (slot_pos[:, None, :] >= 0) \
        & (slot_pos[:, None, :] <= qp[:, :, None])           # (B, T, S)
    if window is not None:
        mask = mask & (slot_pos[:, None, :] > qp[:, :, None] - window)
    out = _attend_grouped_block(cfg, q, k_cache, v_cache, mask)
    out = out.reshape(b, t, cfg.num_heads * cfg.head_dim)
    return quant.qdot("bse,ed->bsd", out, p["wo"]), new_cache


# ---------------------------------------------------------------------------
# paged KV cache: page pool + block-table attention (docs/DESIGN.md §7)
# ---------------------------------------------------------------------------

def paged_layer_cache_spec(cfg, num_pages: int, page_size: int, dtype):
    """Per-layer paged pool: ``(num_pages, page_size, Hkv, hd)``.  Unlike the
    contiguous layout there is no batch dimension — rows map logical blocks
    to physical pages through a per-row block table, so pool bytes buy
    tokens wherever they are needed instead of ``max_cache`` slots per
    admitted request."""
    shape = (num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
    if kv_quantized(cfg):
        sshape = shape[:-1] + (1,)
        return {"k": jax.ShapeDtypeStruct(shape, jnp.int8),
                "v": jax.ShapeDtypeStruct(shape, jnp.int8),
                "k_scale": jax.ShapeDtypeStruct(sshape, jnp.float32),
                "v_scale": jax.ShapeDtypeStruct(sshape, jnp.float32)}
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


def _paged_scatter(pool: Array, new: Array, page: Array, slot: Array) -> Array:
    """Write new (B, T, ...) rows at (page, slot) pairs (B, T) into pool
    (P, ps, ...).  Out-of-range page ids drop the write (invalid tokens are
    routed to the ``num_pages`` sentinel).  An in-place scatter on the scan
    carry, so a donating caller keeps the zero-copy hot loop."""
    return pool.at[page, slot].set(new.astype(pool.dtype), mode="drop")


def attn_block_step_paged(p: dict, cfg, cache: dict, x: Array,
                          positions: Array, lengths: Array, seg_lens: Array,
                          block_tables: Array, window: int | None,
                          mrope_positions: Array | None = None,
                          mesh=None, use_kernel: bool = False
                          ) -> tuple[Array, dict]:
    """``attn_block_step`` over a paged KV cache.

    cache: pool leaves ``(num_pages, page_size, Hkv, hd)`` shared by every
    row; ``block_tables`` (B, NB) int32 maps row b's logical block i (cache
    positions [i*page_size, (i+1)*page_size)) to a physical page.  Rows
    sharing a prompt prefix point their leading entries at the same pages
    (serving/paging.PrefixCache), which is exact: causal attention makes a
    prefix's K/V a pure function of the prefix tokens.

    Token j of row b (absolute position ``positions[b, j]``) writes its
    K/V at page ``block_tables[b, pos // ps]`` slot ``pos % ps`` — an
    in-place scatter on the scan-carry pool (invalid tokens drop via an
    out-of-range page sentinel, exactly like the ring path of
    ``_update_cache_block``).  Attention then gathers each row's pages
    into a (B, NB*ps, Hkv, hd) virtual cache whose slot s holds absolute
    position s, so the position-offset causal mask of the contiguous path
    applies unchanged (the gather is the pure-JAX form of a paged-attention
    kernel's block-table indirection; it reads at most the same bytes the
    contiguous layout's full-cache attention read).  With
    ``use_kernel=True`` the Pallas kernel (kernels/paged_attn.py) replaces
    the gather: it walks the block table page by page in VMEM, so the
    virtual cache is never materialized and attention bytes scale with
    ``lengths`` instead of pool size (docs/DESIGN.md §11).  The kernel
    path requires the unified scheduler's position contract
    ``positions[b, j] == lengths[b] + j``, which ``forward_routed``
    guarantees.  Ring caches (sliding window == cache length) are never
    paged — the engine keeps the reference path for those archs — but
    plain position windows (the long-context SWA variant) mask exactly as
    in ``attn_block_step``.

    x: (B, T, D); positions: (B, T) absolute; lengths/seg_lens: (B,).
    Returns ((B, T, D), cache')."""
    b, t, _ = x.shape
    num_pages, page_size = cache["k"].shape[:2]
    nb = block_tables.shape[1]
    q, k_new, v_new = _project_qkv(p, cfg, x, positions, mrope_positions,
                                   mesh)
    valid = jnp.arange(t)[None, :] < seg_lens[:, None]          # (B, T)
    blk = positions // page_size
    page = jnp.take_along_axis(block_tables, jnp.clip(blk, 0, nb - 1),
                               axis=1)
    # invalid tokens and positions beyond the table drop their write
    page = jnp.where(valid & (blk < nb), page, num_pages)
    slot = positions % page_size

    if kv_quantized(cfg):
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        new_cache = {
            kk: _paged_scatter(cache[kk], nn, page, slot)
            for kk, nn in (("k", kq), ("v", vq),
                           ("k_scale", ks), ("v_scale", vs))
        }
    else:
        new_cache = {"k": _paged_scatter(cache["k"], k_new, page, slot),
                     "v": _paged_scatter(cache["v"], v_new, page, slot)}

    if use_kernel:
        from repro.kernels import ops as kernel_ops
        with jax.named_scope("attn.gather"):
            out = kernel_ops.paged_attention(q, new_cache, block_tables,
                                             lengths, seg_lens, window=window)
        out = out.reshape(b, t, cfg.num_heads * cfg.head_dim)
        return quant.qdot("bse,ed->bsd", out, p["wo"]), new_cache

    bt = jnp.clip(block_tables, 0, num_pages - 1)

    def gather(pool):
        pages = jnp.take(pool, bt, axis=0)          # (B, NB, ps, Hkv, ·)
        return pages.reshape((b, nb * page_size) + pool.shape[2:])

    # virtual slot s holds absolute position s: the linear-cache mask
    slot_pos = jnp.arange(nb * page_size, dtype=jnp.int32)[None, None, :]
    qp = jnp.where(valid, positions, -1)[:, :, None]            # (B, T, 1)
    mask = slot_pos <= qp                                       # (B, T, S)
    if window is not None:
        mask = mask & (slot_pos > qp - window)

    with jax.named_scope("attn.gather"):
        if kv_quantized(cfg):
            # dequantize only the slots some token attends (the per-row
            # union of the mask): zeroing the int8 payload elsewhere first
            # is bit-exact for every attended slot — excluded slots'
            # logits are overwritten with NEG_INF regardless of their K/V
            # content — and spares the multiply over the pool-sized dead
            # tail
            attended = jnp.any(mask, axis=1)[:, :, None, None]  # (B,S,1,1)
            dq = lambda kk: dequantize_kv(
                jnp.where(attended, gather(new_cache[kk]), 0),
                gather(new_cache[kk + "_scale"]), x.dtype)
            k_cache, v_cache = dq("k"), dq("v")
        else:
            k_cache, v_cache = (gather(new_cache["k"]),
                                gather(new_cache["v"]))

    out = _attend_grouped_block(cfg, q, k_cache, v_cache, mask)
    out = out.reshape(b, t, cfg.num_heads * cfg.head_dim)
    return quant.qdot("bse,ed->bsd", out, p["wo"]), new_cache


def attn_decode_step_cp(p: dict, cfg, cache: dict, x: Array, lengths: Array,
                        window: int | None, mesh,
                        mrope_positions: Array | None = None
                        ) -> tuple[Array, dict]:
    """Decode-time context parallelism: the KV cache is sequence-sharded over
    the "model" axis; each shard attends its local chunk and the partial
    (acc, m, l) online-softmax stats are merged with a pmax + two psums of
    (B, Hq, 1, ·) — a few hundred KB instead of gathering the full cache.

    This is the paper's decentralized one-all-reduce design (§4.3) applied
    to attention: replicate the small operands (q, new k/v), shard the big
    state, reduce once.  Projections (wq..wo) run OUTSIDE under GSPMD, so
    head-sharded weights keep working unchanged.
    """
    from jax.sharding import PartitionSpec as P

    b = x.shape[0]
    cache_len = cache["k"].shape[1]
    tp = mesh.shape["model"]
    ring = window is not None and cache_len == window
    q, k_new, v_new = _project_qkv(p, cfg, x, lengths[:, None], mrope_positions,
                                   mesh)
    kv_q = kv_quantized(cfg)
    if kv_q:
        kq, ksc = quantize_kv(k_new)
        vq, vsc = quantize_kv(v_new)
        new_tree = {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc}
    else:
        new_tree = {"k": k_new, "v": v_new}
    cache_tree = {kk: cache[kk] for kk in new_tree}

    batch_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    nb = 1
    for a in batch_axes:
        nb *= mesh.shape[a]
    ba = batch_axes if (nb and b % nb == 0) else ()
    scale = cfg.head_dim ** -0.5

    def body(q_, new_t, cache_t, lens):
        kc = cache_t["k"]
        s_loc = kc.shape[1]
        start = jax.lax.axis_index("model") * s_loc
        slot_global = lens % cache_len if ring else lens
        local_slot = slot_global - start
        in_range = (local_slot >= 0) & (local_slot < s_loc)

        def upd(c, n, s, ok):
            s_cl = jnp.clip(s, 0, s_loc - 1)
            new = jax.lax.dynamic_update_slice(c, n, (s_cl, 0, 0))
            return jnp.where(ok, new, c)

        cache_t = jax.tree.map(
            lambda c, n: jax.vmap(upd)(c, n, local_slot, in_range),
            cache_t, new_t)
        if kv_q:
            kc = dequantize_kv(cache_t["k"], cache_t["k_scale"], q_.dtype)
            vc = dequantize_kv(cache_t["v"], cache_t["v_scale"], q_.dtype)
        else:
            kc, vc = cache_t["k"], cache_t["v"]

        # grouped-GQA attention WITHOUT materializing gqa_repeat: repeating
        # 4 kv heads to 32 q heads would read the cache 8x (measured as the
        # top HBM term of MoE decode) — index kv heads per q-head group
        # instead, exactly what a TPU flash kernel does
        hkv = kc.shape[2]
        g = cfg.num_heads // hkv
        qg = q_.reshape(q_.shape[0], 1, hkv, g, q_.shape[-1])  # (B,1,Hkv,G,hd)
        logits = jnp.einsum("bqhgd,bshd->bhgqs", qg, kc,
                            preferred_element_type=jnp.float32) * scale
        gidx = start + jnp.arange(s_loc)[None, :]          # (1, s_loc) global
        if ring:
            n_valid = jnp.minimum(lens[:, None] + 1, cache_len)
            mask = gidx < n_valid
        else:
            mask = gidx <= lens[:, None]
            if window is not None:
                mask = mask & (gidx > lens[:, None] - window)
        mask5 = mask[:, None, None, None, :]               # (B,1,1,1,s_loc)
        logits = jnp.where(mask5, logits, NEG_INF)
        m_loc = jnp.max(logits, axis=-1)                   # (B,Hkv,G,1)
        pr = jnp.exp(logits - m_loc[..., None])
        pr = jnp.where(mask5, pr, 0.0)
        l_loc = jnp.sum(pr, axis=-1)
        acc_loc = jnp.einsum("bhgqs,bshd->bhgqd", pr.astype(vc.dtype), vc,
                             preferred_element_type=jnp.float32)
        m_g = jax.lax.pmax(m_loc, "model")
        corr = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * corr, "model")
        acc_g = jax.lax.psum(acc_loc * corr[..., None], "model")
        out = acc_g / jnp.maximum(l_g[..., None], 1e-30)   # (B,Hkv,G,1,hd)
        b_, _, _, _, hd = out.shape
        out = out.astype(q_.dtype).transpose(0, 3, 1, 2, 4)
        return out.reshape(b_, 1, hkv * g, hd), cache_t    # (B,1,H,hd)

    rep = jax.tree.map(lambda a: P(*([ba] + [None] * (a.ndim - 1))), new_tree)
    shd = jax.tree.map(lambda a: P(ba, "model", *([None] * (a.ndim - 2))),
                       cache_tree)
    out, new_cache = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(ba, None, None, None), rep, shd, P(ba)),
        out_specs=(P(ba, None, None, None), shd),
        check_vma=True,
    )(q, new_tree, cache_tree, lengths)
    out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim)
    out = quant.qdot("bse,ed->bsd", out, p["wo"])
    return out, new_cache


def use_cp_decode(cfg, mesh, cache_len: int) -> bool:
    """Sequence-sharded decode applies when a mesh with a 'model' axis is
    present, the cache length divides it, and the config opts in."""
    return (mesh is not None
            and "model" in getattr(mesh, "axis_names", ())
            and getattr(cfg, "kv_cache_shard", "seq") == "seq"
            and cache_len % mesh.shape["model"] == 0)


def attn_prefill(p: dict, cfg, cache: dict, x: Array, positions: Array,
                 window: int | None,
                 mrope_positions: Array | None = None,
                 mesh=None) -> tuple[Array, dict]:
    """Full-sequence forward that also fills the cache (non-ring layout only
    when S <= cache_len; for ring caches the last `window` tokens are kept)."""
    b, s, _ = x.shape
    cache_len = cache["k"].shape[1]
    q, k, v = _project_qkv(p, cfg, x, positions, mrope_positions, mesh)
    kr = gqa_repeat(k, cfg.num_heads)
    vr = gqa_repeat(v, cfg.num_heads)
    # q is already head-pinned inside _project_qkv; kr/vr changed head count
    kr = head_constrain(mesh, kr)
    vr = head_constrain(mesh, vr)
    if s >= CHUNK_THRESHOLD:
        out = attend_chunked(q, kr, vr, positions, positions, window,
                             cfg.head_dim ** -0.5)
    else:
        qp = positions[:, None, :, None]
        kp = positions[:, None, None, :]
        mask = kp <= qp
        if window is not None:
            mask = mask & (kp > qp - window)
        out = attend(q, kr, vr, mask, cfg.head_dim ** -0.5)
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
    out = quant.qdot("bse,ed->bsd", out, p["wo"])
    if s >= cache_len:
        # ring layout invariant: position p lives at slot p % cache_len, so the
        # kept tail [s-cache_len, s) must be rolled to line up with future
        # decode writes at slot (lengths % cache_len).
        k_keep = jnp.roll(k[:, s - cache_len:], shift=s, axis=1)
        v_keep = jnp.roll(v[:, s - cache_len:], shift=s, axis=1)
        if kv_quantized(cfg):
            kq, ks = quantize_kv(k_keep)
            vq, vs = quantize_kv(v_keep)
            cache = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        else:
            cache = {"k": k_keep.astype(cache["k"].dtype),
                     "v": v_keep.astype(cache["v"].dtype)}
    elif kv_quantized(cfg):
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        cache = {
            kk: jax.lax.dynamic_update_slice(cache[kk], nn, (0, 0, 0, 0))
            for kk, nn in (("k", kq), ("v", vq),
                           ("k_scale", ks), ("v_scale", vs))
        }
    else:
        cache = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
        }
    return out, cache
