"""Public model API: build_model(cfg) -> Model with pure-functional entry
points used by the launcher, serving engine, tests and benchmarks.

  init(rng)                          -> params
  forward(params, batch, mesh)       -> (logits, aux_loss)      full sequence
  loss(params, batch, mesh)          -> (scalar, metrics)       training loss
  prefill(params, batch, cache, mesh)-> (logits_last, cache)
  decode_step(params, cache, batch, mesh) -> (logits, cache)    one token
  cache_specs(batch, cache_len)      -> ShapeDtypeStruct pytree

Routing-capture variants (device-side aux outputs, zero extra router
evaluations — the serving engine's hot loop consumes these so expert
statistics never require a host-side router replay):

  prefill_routed(params, batch, cache, mesh)
      -> (logits_last, cache, routing)   routing: (L, B*S, K) int32 | None
  decode_step_routed(params, cache, batch, mesh)
      -> (logits, cache, routing)        routing: (L, B, K) int32 | None

Both routed entry points honour an optional ``batch["token_mask"]``
((B, S) bool): False tokens are dead-routed past the MoE dispatch so they
consume no expert capacity (how the serving engine's batched prefill keeps
garbage/in-flight rows from perturbing real requests); their ``routing``
entries read E_pad.

Donation safety: ``prefill_routed`` / ``decode_step_routed`` update the
cache exclusively via ``dynamic_update_slice`` on a scan carry
(transformer._scan_stack_with_cache) — a caller that jits with the cache
in ``donate_argnums`` gets in-place aliasing and a zero-copy decode step
(tests/test_zero_copy.py).  ``lengths`` is a separate, never-donated
operand, preserving the engine's host-snapshot race fix (the host may
mutate its own lengths array after dispatch; the device sees the
snapshot).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.models import layers, transformer

Array = jax.Array


def sinusoidal_embedding(positions: Array, d: int) -> Array:
    """positions: (B, S) -> (B, S, d) fp32 sinusoidal table."""
    half = d // 2
    freq = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any
    init: Callable
    forward: Callable
    loss: Callable
    prefill: Callable
    decode_step: Callable
    cache_specs: Callable
    init_cache: Callable
    prefill_routed: Callable
    decode_step_routed: Callable
    forward_routed: Callable
    paged_cache_specs: Callable
    init_paged_cache: Callable


def _embed_inputs(cfg, params, batch) -> tuple[Array, Array, Array | None, Array]:
    """Returns (x (B,S,D), positions (B,S), mrope_pos or None, loss_mask (B,S))."""
    dt = cfg.dtype_jnp
    if cfg.family == "audio":
        x = batch["frame_embeds"].astype(dt)
        b, s, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        mask = jnp.ones((b, s), jnp.float32)
    elif cfg.family == "vlm":
        tok = jnp.take(params["embed"],
                       jnp.clip(batch["tokens"], 0, cfg.vocab_size - 1), axis=0)
        x = jnp.concatenate([batch["patch_embeds"].astype(dt), tok.astype(dt)],
                            axis=1)
        b, s, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        mask = (jnp.arange(s)[None] >= cfg.num_patch_tokens).astype(jnp.float32)
        mask = jnp.broadcast_to(mask, (b, s))
        return x, pos, batch.get("mrope_positions"), mask
    else:
        x = jnp.take(params["embed"],
                     jnp.clip(batch["tokens"], 0, cfg.vocab_size - 1),
                     axis=0).astype(dt)
        b, s = batch["tokens"].shape
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        mask = jnp.ones((b, s), jnp.float32)
    if cfg.positional == "sinusoidal":
        x = x + sinusoidal_embedding(pos, cfg.d_model).astype(dt)
    return x, pos, None, mask


def _lm_head(cfg, params, x: Array) -> Array:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return quant.qdot("bsd,dv->bsv", x, w, weight_dtype=x.dtype,
                      preferred_element_type=jnp.float32)


def build_model(cfg) -> Model:
    dt = cfg.dtype_jnp
    pdt = cfg.param_dtype_jnp

    # ---- init -----------------------------------------------------------
    def init(rng: Array) -> dict:
        k_emb, k_blocks, k_head = jax.random.split(rng, 3)
        params = {
            "embed": layers.embed_init(k_emb, cfg.vocab_padded, cfg.d_model, pdt),
            "blocks": transformer.init_blocks(cfg, k_blocks),
            "final_norm": layers.norm_init(cfg.norm, cfg.d_model, pdt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = layers.dense_init(
                k_head, cfg.d_model, cfg.vocab_padded, pdt)
        return params

    # ---- full-sequence forward -------------------------------------------
    def forward(params, batch, mesh=None):
        x, pos, mrope, _ = _embed_inputs(cfg, params, batch)
        window = transformer.effective_window(cfg, x.shape[1])
        x, aux = transformer.forward_stack(cfg, mesh, params["blocks"], x, pos,
                                           window, mrope)
        x = layers.norm_apply(cfg.norm, params["final_norm"], x)
        return _lm_head(cfg, params, x), aux

    def _chunked_ce(params, x, labels, chunk: int = 512):
        """lm_head + CE one sequence chunk at a time — never materializes the
        full (B, S, V) logits (V can be 150k+)."""
        b, s, d = x.shape
        if s <= chunk or s % chunk != 0:
            return layers.softmax_cross_entropy(
                _lm_head(cfg, params, x), labels, cfg.vocab_size)
        nc = s // chunk
        xc = x.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
        lc = labels.reshape(b, nc, chunk).transpose(1, 0, 2)

        def body(args):
            xx, ll = args
            return layers.softmax_cross_entropy(
                _lm_head(cfg, params, xx), ll, cfg.vocab_size)

        ce = jax.lax.map(jax.checkpoint(body), (xc, lc))     # (nc, B, chunk)
        return ce.transpose(1, 0, 2).reshape(b, s)

    def loss(params, batch, mesh=None):
        x, pos, mrope, mask = _embed_inputs(cfg, params, batch)
        window = transformer.effective_window(cfg, x.shape[1])
        x, aux = transformer.forward_stack(cfg, mesh, params["blocks"], x, pos,
                                           window, mrope)
        x = layers.norm_apply(cfg.norm, params["final_norm"], x)
        ce = _chunked_ce(params, x, batch["labels"])
        ce = jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        total = ce + 0.01 * aux
        return total, {"ce": ce, "aux": aux}

    # ---- cache ------------------------------------------------------------
    def cache_specs(batch: int, cache_len: int):
        return transformer.stack_cache_spec(cfg, batch, cache_len, dt)

    def init_cache(batch: int, cache_len: int):
        return transformer.init_stack_cache(cfg, batch, cache_len, dt)

    # paged pool (docs/DESIGN.md §7): one (L, num_pages, page_size, Hkv,
    # hd) leaf per cache kind, shared across rows via per-row block tables
    # (batch["block_tables"] in forward_routed)
    def paged_cache_specs(num_pages: int, page_size: int):
        return transformer.paged_stack_cache_spec(cfg, num_pages, page_size,
                                                  dt)

    def init_paged_cache(num_pages: int, page_size: int):
        return transformer.init_paged_stack_cache(cfg, num_pages, page_size,
                                                  dt)

    # ---- prefill ------------------------------------------------------------
    def prefill_routed(params, batch, cache, mesh=None):
        x, pos, mrope, _ = _embed_inputs(cfg, params, batch)
        window = transformer.effective_window(cfg, x.shape[1])
        x, cache, routing = transformer.prefill_stack(
            cfg, mesh, params["blocks"], x, pos, cache, window, mrope,
            token_mask=batch.get("token_mask"))
        x = layers.norm_apply(cfg.norm, params["final_norm"], x[:, -1:])
        return _lm_head(cfg, params, x), cache, routing

    def prefill(params, batch, cache, mesh=None):
        logits, cache, _ = prefill_routed(params, batch, cache, mesh)
        return logits, cache

    # ---- decode -------------------------------------------------------------
    def decode_step_routed(params, cache, batch, mesh=None, context_len=None):
        tok = jnp.clip(batch["tokens"], 0, cfg.vocab_size - 1)
        x = jnp.take(params["embed"], tok, axis=0).astype(dt)
        lengths = batch["lengths"]
        if cfg.positional == "sinusoidal":
            x = x + sinusoidal_embedding(lengths[:, None], cfg.d_model).astype(dt)
        # windowing decision is made at the *logical* context length
        # (cache extent may already be clipped to the window => ring buffer)
        cache_len = _attn_cache_len(cfg, cache)
        window = (transformer.effective_window(cfg, context_len or cache_len)
                  if cache_len is not None else cfg.sliding_window)
        x, cache, routing = transformer.decode_stack(
            cfg, mesh, params["blocks"], x, lengths, cache, window,
            batch.get("mrope_positions"), token_mask=batch.get("token_mask"))
        x = layers.norm_apply(cfg.norm, params["final_norm"], x)
        return _lm_head(cfg, params, x), cache, routing

    def decode_step(params, cache, batch, mesh=None, context_len=None):
        logits, cache, _ = decode_step_routed(params, cache, batch, mesh,
                                              context_len)
        return logits, cache

    # ---- unified token-budget forward -----------------------------------
    def forward_routed(params, batch, cache, mesh=None, context_len=None,
                       paged_kernel=False):
        """Length-agnostic unified step: one (B, T) token block at arbitrary
        per-row cache offsets (docs/DESIGN.md §6).

        batch: {"tokens": (B, T) int32, "lengths": (B,) int32 cache offsets,
        "seg_lens": (B,) int32 valid-token counts, optional "token_mask",
        optional "block_tables"}.  Row b appends its first seg_lens[b]
        tokens at positions lengths[b]..lengths[b]+seg_lens[b]-1;
        T=1/seg_lens=1 is a decode step, seg_lens=T at lengths=0 is
        whole-prompt prefill, and per-row mixes are chunked-prefill /
        mixed prefill+decode batches.  The prefill/decode twins above
        remain as the two-program reference.

        With ``block_tables`` (B, NB) int32 the cache is the paged pool of
        ``init_paged_cache`` (docs/DESIGN.md §7): row b's logical block i
        lives on physical page block_tables[b, i], so rows sharing a
        prompt prefix alias the same pages and the pool is sized in pages,
        not max_batch x max_cache slots.  Block tables are host-scheduler
        state handed to the device like ``lengths`` — never donated.
        ``paged_kernel`` (static) attends through the Pallas block-table
        kernel instead of the virtual-cache gather (docs/DESIGN.md §11).

        Returns (logits (B, V) at each row's LAST VALID position, cache',
        routing (L, B*T, K) int32 | None).  The cache is updated via
        dynamic-slice writes on the layer-scan carry, so donating callers
        keep the zero-copy hot loop; ``lengths``/``seg_lens`` stay
        undonated host snapshots (same race rule as decode)."""
        if cfg.family not in ("dense", "moe"):
            raise NotImplementedError(
                f"forward_routed supports token-input attention families, "
                f"not {cfg.family!r}")
        tokens = batch["tokens"]
        lengths = batch["lengths"]
        seg_lens = batch["seg_lens"]
        b, t = tokens.shape
        with jax.named_scope("embed"):
            tok = jnp.clip(tokens, 0, cfg.vocab_size - 1)
            x = jnp.take(params["embed"], tok, axis=0).astype(dt)
            positions = (lengths[:, None]
                         + jnp.arange(t, dtype=jnp.int32)[None])
            if cfg.positional == "sinusoidal":
                x = x + sinusoidal_embedding(positions,
                                             cfg.d_model).astype(dt)
            token_mask = batch.get("token_mask")
            if token_mask is None:
                token_mask = jnp.arange(t)[None] < seg_lens[:, None]
        block_tables = batch.get("block_tables")
        if block_tables is not None:
            # paged pool leaves are (L, P, page_size, ...): the per-row
            # cache extent is the block table's reach.  NB: this rounds
            # UP to whole pages — callers whose logical context is not
            # page-aligned should pass ``context_len`` so the windowing
            # decision (effective_window) matches the contiguous layout
            cache_len = block_tables.shape[1] * cache["k"].shape[2]
        else:
            cache_len = _attn_cache_len(cfg, cache)
        window = (transformer.effective_window(cfg, context_len or cache_len)
                  if cache_len is not None else cfg.sliding_window)
        x, cache, routing = transformer.unified_stack(
            cfg, mesh, params["blocks"], x, positions, lengths, seg_lens,
            cache, window, token_mask=token_mask, block_tables=block_tables,
            paged_kernel=paged_kernel and block_tables is not None)
        with jax.named_scope("lm_head"):
            sel = jnp.clip(seg_lens - 1, 0, t - 1)
            x_sel = jnp.take_along_axis(x, sel[:, None, None], axis=1)
            x_sel = layers.norm_apply(cfg.norm, params["final_norm"], x_sel)
            logits = _lm_head(cfg, params, x_sel)          # (B, 1, V)
        return logits[:, 0], cache, routing

    # init is jitted so XLA fuses each f32 normal draw into its cast: run
    # eagerly, every expert stack first materializes in f32 (4.8 GB per
    # weight kind at Granite-3B widths, more than bf16 HBM headroom allows)
    return Model(cfg, jax.jit(init), forward, loss, prefill, decode_step,
                 cache_specs, init_cache, prefill_routed, decode_step_routed,
                 forward_routed, paged_cache_specs, init_paged_cache)


def _attn_cache_len(cfg, cache) -> int | None:
    if cfg.family == "ssm":
        return None
    if cfg.family == "hybrid":
        return cache["attn"]["k"].shape[2]
    return cache["k"].shape[2]
