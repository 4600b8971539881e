"""Served weights made from the run's seed, on the device, in one jitted
call and in the type they are served in.  The layout is the one the
serving engine takes as ``params`` (a stacked layer axis, experts stacked
per layer); the benchmark makes the weights so that the reference computes
from the same arrays without taking anything the program made."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.spec import ModelSpec


def run_key(seed: int) -> jax.Array:
    """A PRNG key for any seed of up to 64 bits (``jax.random.key`` keeps
    only the low 32)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def shapes(s: ModelSpec) -> dict:
    """Shape of every served weight, keyed like the engine's params."""
    L, d, f = s.layers, s.d_model, s.expert_ff
    attn = {"wq": (L, d, s.heads * s.head_dim),
            "wk": (L, d, s.kv_heads * s.head_dim),
            "wv": (L, d, s.kv_heads * s.head_dim),
            "wo": (L, s.heads * s.head_dim, d)}
    if s.qk_norm:
        attn.update(q_norm=(L, s.head_dim), k_norm=(L, s.head_dim))
    out = {
        "embed": (s.vocab_held, d),
        "blocks": {
            "ln1": (L, d), "attn": attn, "ln2": (L, d),
            "router": (L, d, s.experts_held),
            "experts": {"w_gate": (L, s.experts_held, d, f),
                        "w_up": (L, s.experts_held, d, f),
                        "w_down": (L, s.experts_held, f, d)},
        },
        "final_norm": (d,),
    }
    if not s.tied:
        out["lm_head"] = (d, s.vocab_held)
    return out


def _scale(path: tuple, shape: tuple) -> tuple[float, float]:
    """(mean, std) of one weight: norms scatter round 1, the embedding has
    the published initializer range, matrices 1/sqrt(fan-in)."""
    name = path[-1]
    if name in ("ln1", "ln2", "final_norm", "q_norm", "k_norm"):
        return 1.0, 0.1
    if name == "embed":
        return 0.0, 0.02
    fan_in = shape[-2]
    return 0.0, fan_in ** -0.5


def make(s: ModelSpec, seed: int) -> dict:
    """All served weights for ``seed``, made on the device in one call."""
    dtype = jnp.dtype(s.dtype)
    tree = shapes(s)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(i, int) for i in x))

    def gen(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            names = tuple(getattr(p, "key", str(p)) for p in path)
            mean, std = _scale(names, shape)
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out.append((mean + std * z).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.block_until_ready(jax.jit(gen)(run_key(seed)))
