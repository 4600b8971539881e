"""Plain float32 reference of the served mixture-of-experts transformer,
and the comparison that decides a run's ``correct``.

It follows the configuration's published description as the served model
applies it: RMSNorm before attention and before the expert layer, GQA
attention with rotary positions (rotate-half form) and, where the layout
says so, RMSNorm over each query and key head before the rotation; a
softmax router whose top-k weights are renormalised to sum to one; SwiGLU
experts; a final RMSNorm and the LM head (the embedding, transposed, where
the two are tied).  Every matrix product runs in float32 at
``Precision.HIGHEST``.  It imports nothing of the program: it reads the
weights the benchmark made and the sizes in ``bench/spec.py``.

It runs layer by layer (a scan over the stacked layer axis) over packed
sequences: the sampled requests' prompts and served tokens laid end to
end, each attending causally within its own segment.  The experts are
computed densely, a group at a time, with each token weighted by its
router weight for the experts it chose and zero for the rest, so no routed
token is dropped and nothing depends on a capacity.

``mode="fp8"`` is the control: the same computation with every input of a
weight product (activations per row, weights per output column) rounded
to fp8 e4m3 -- three mantissa bits after scaling the row or column to the
format's largest value, 448.  The router stays float32, as a quantised
serving path keeps it.  ``mode="bf16"`` rounds the activations entering
each weight product to bfloat16, the precision the served model runs in;
the tests use it to show that the comparison tells the two apart.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.spec import ModelSpec

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
Q_BLOCK = 512          # query rows per attention block
HEAD_BLOCK = 512       # positions per LM-head block
E4M3_MAX = 448.0


def fp8_round(x: jax.Array, axis: int) -> jax.Array:
    """Round to fp8 e4m3 after scaling ``axis``'s largest magnitude to 448:
    three mantissa bits for normals, a quantum of 2^-9 below 2^-6."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    y = x / scale
    _, e = jnp.frexp(y)                         # |y| = m * 2^e, m in [0.5, 1)
    quantum = jnp.exp2(jnp.maximum(e.astype(jnp.float32) - 4.0, -9.0))
    y = jnp.clip(jnp.round(y / quantum) * quantum, -E4M3_MAX, E4M3_MAX)
    return y * scale


def _mm(eq: str, a, w, mode: str, w_axis: int):
    """A weight product in float32; under fp8, both inputs rounded first
    (activations over their last axis, weights over ``w_axis``)."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if mode == "fp8":
        a = fp8_round(a, -1)
        w = fp8_round(w, w_axis)
    elif mode == "bf16":
        a = a.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.einsum(eq, a, w, precision=HI)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: (T, H, hd); rotate-half rotary embedding at positions pos (T,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv             # (T, hd/2)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(s: ModelSpec, lp, h, pos, seg, mode):
    t = h.shape[0]
    hd, hkv = s.head_dim, s.kv_heads
    g = s.heads // hkv
    q = _mm("td,de->te", h, lp["wq"], mode, 0).reshape(t, s.heads, hd)
    k = _mm("td,de->te", h, lp["wk"], mode, 0).reshape(t, hkv, hd)
    v = _mm("td,de->te", h, lp["wv"], mode, 0).reshape(t, hkv, hd)
    if s.qk_norm:
        q = _rms(q, lp["q_norm"], s.rms_eps)
        k = _rms(k, lp["k_norm"], s.rms_eps)
    q = _rope(q, pos, s.rope_theta).reshape(t, hkv, g, hd)
    k = _rope(k, pos, s.rope_theta)
    nb = t // Q_BLOCK

    def block(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * Q_BLOCK, Q_BLOCK)
        qb, qp, qs = sl(q), sl(pos), sl(seg)
        logit = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=HI) * hd ** -0.5
        ok = (seg[None, :] == qs[:, None]) & (pos[None, :] <= qp[:, None])
        p = jax.nn.softmax(jnp.where(ok[None, None], logit, NEG), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(nb)).reshape(t, s.heads * hd)
    return _mm("te,ed->td", o, lp["wo"], mode, 0)


def _experts(s: ModelSpec, lp, h, group: int, mode):
    """Top-k routed SwiGLU experts, computed densely a group at a time."""
    logits = jnp.einsum("td,de->te", h,
                        lp["router"][:, :s.experts].astype(jnp.float32),
                        precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, s.top_k)
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_i].set(top_w)      # (T, E)
    ex = lp["experts"]
    ng = s.experts // group
    grp = lambda a: a[:s.experts].reshape((ng, group) + a.shape[1:])
    gate_g = gate.reshape(h.shape[0], ng, group).transpose(1, 0, 2)

    def body(y, xs):
        wg, wu, wd, gw = xs
        a = _mm("td,edf->tef", h, wg, mode, 1)
        b = _mm("td,edf->tef", h, wu, mode, 1)
        act = jax.nn.silu(a) * b * gw[:, :, None]
        return y + _mm("tk,kd->td", act.reshape(h.shape[0], -1),
                       wd.reshape(-1, wd.shape[-1]), mode, 0), None

    y0 = jnp.zeros(h.shape, jnp.float32)
    y, _ = jax.lax.scan(body, y0, (grp(ex["w_gate"]), grp(ex["w_up"]),
                                   grp(ex["w_down"]), gate_g))
    return y


@functools.partial(jax.jit, static_argnames=("s", "group", "mode"))
def hidden(weights, tokens, pos, seg, *, s: ModelSpec, group: int,
           mode: str = "f32"):
    """Final-normed hidden states (T, D) of the packed sequence.  T must be
    a multiple of ``Q_BLOCK``; padding positions carry segment -1."""
    x = jnp.take(weights["embed"], jnp.clip(tokens, 0, s.vocab - 1),
                 axis=0).astype(jnp.float32)

    def layer(x, lp):
        x = x + _attention(s, lp["attn"], _rms(x, lp["ln1"], s.rms_eps),
                           pos, seg, mode)
        x = x + _experts(s, lp, _rms(x, lp["ln2"], s.rms_eps), group, mode)
        return x, None

    x, _ = jax.lax.scan(layer, x, weights["blocks"])
    return _rms(x, weights["final_norm"], s.rms_eps)


def _head(weights, s: ModelSpec):
    w = weights["embed"].T if s.tied else weights["lm_head"]
    return w[:, :s.vocab]


@functools.partial(jax.jit, static_argnames=("s",))
def served_gaps(weights, h, rows, toks, *, s: ModelSpec):
    """How far below the reference's best logit each served token lies:
    ``max(logits[row]) - logits[row, tok]`` for each (row, tok).  ``rows``
    is a multiple of ``HEAD_BLOCK`` long."""
    w = _head(weights, s)

    def block(i):
        r = jax.lax.dynamic_slice_in_dim(rows, i * HEAD_BLOCK, HEAD_BLOCK)
        t = jax.lax.dynamic_slice_in_dim(toks, i * HEAD_BLOCK, HEAD_BLOCK)
        lg = jnp.einsum("nd,dv->nv", h[r], w.astype(jnp.float32),
                        precision=HI)
        return jnp.max(lg, -1) - jnp.take_along_axis(lg, t[:, None], -1)[:, 0]

    return jax.lax.map(block, jnp.arange(rows.shape[0] // HEAD_BLOCK)
                       ).reshape(-1)


@functools.partial(jax.jit, static_argnames=("s",))
def control_tokens(weights, h_ctrl, rows, *, s: ModelSpec):
    """The token the fp8 control puts first at each row."""
    w = _head(weights, s)

    def block(i):
        r = jax.lax.dynamic_slice_in_dim(rows, i * HEAD_BLOCK, HEAD_BLOCK)
        lg = _mm("nd,dv->nv", h_ctrl[r], w, "fp8", 0)
        return jnp.argmax(lg, -1).astype(jnp.int32)

    return jax.lax.map(block, jnp.arange(rows.shape[0] // HEAD_BLOCK)
                       ).reshape(-1)


def pack(samples, length: int):
    """Lay sequences end to end.  ``samples``: (prompt, served) pairs of
    int arrays.  Returns (tokens, pos, seg, rows, toks, n): the packed
    inputs padded to ``length``; for every served token, the row whose
    logits produced it and the token itself, padded to a multiple of
    ``HEAD_BLOCK``; and the number of served tokens."""
    tokens = np.zeros(length, np.int32)
    pos = np.zeros(length, np.int32)
    seg = np.full(length, -1, np.int32)
    rows, toks = [], []
    at = 0
    for i, (prompt, served) in enumerate(samples):
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        if at + len(seq) > length:
            raise ValueError("samples do not fit the packed length")
        tokens[at:at + len(seq)] = seq
        pos[at:at + len(seq)] = np.arange(len(seq))
        seg[at:at + len(seq)] = i
        rows.extend(at + len(prompt) - 1 + np.arange(len(served)))
        toks.extend(served)
        at += len(seq)
    n = len(rows)
    m = -(-max(n, 1) // HEAD_BLOCK) * HEAD_BLOCK
    rows = np.array(rows + [0] * (m - n), np.int32)
    toks = np.array(list(toks) + [0] * (m - n), np.int32)
    return tokens, pos, seg, rows, toks, n


def gaps(weights, s: ModelSpec, samples, length: int,
         group: int) -> np.ndarray:
    """For every served token of ``samples``, the gap by which its logit
    lies below the reference's best."""
    tokens, pos, seg, rows, toks, n = pack(samples, length)
    h = hidden(weights, tokens, pos, seg, s=s, group=group)
    return np.asarray(served_gaps(weights, h, rows, toks, s=s))[:n]


def control_gaps(weights, s: ModelSpec, samples, length: int,
                 group: int) -> np.ndarray:
    """The same gaps for the fp8 control: at each served position, the
    gap of the token the control puts first."""
    tokens, pos, seg, rows, _, n = pack(samples, length)
    h_ref = hidden(weights, tokens, pos, seg, s=s, group=group)
    h_ctl = hidden(weights, tokens, pos, seg, s=s, group=group, mode="fp8")
    ctl = control_tokens(weights, h_ctl, rows, s=s)
    return np.asarray(served_gaps(weights, h_ref, rows, ctl, s=s))[:n]
