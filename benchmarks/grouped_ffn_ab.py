"""One MoE layer's routed expert FFN on the chip: grouped GEMM forms
against the capacity layout.

    python benchmarks/grouped_ffn_ab.py [--t 1024] [--experts 128]
        [--d 2048] [--f 768] [--k 8] [--real 100] [--iters 50]

Defaults are Qwen3-30B-A3B's mixed serving block: T = 32 rows x 32
columns, top-8 of 128 experts, of which ``--real`` tokens are routed and
the rest dead-routed as padding.  Times, in ms per call (median of
``--iters`` after a warm-up), with ``block_until_ready``:

* ``ffn.gmm``: gate, up and down through ``kernels.ops.grouped_matmul``
  (megablox ``gmm``, 128-row tiles) over the T*K rows sorted by expert;
* ``ffn.ragged_dot``: the same three products through ``lax.ragged_dot``;
* ``layer.grouped`` / ``layer.dispatch``: the whole local layer,
  ``core.moe.grouped_moe`` against ``dispatch_moe`` at dropless capacity.

Each form runs twice: with the block's real tokens only (``real``) and
with every token routed (``full``).  One JSON line per form and routing,
the device named in each.  Meant for a TPU; elsewhere the kernels run in
interpret mode and the times measure nothing.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp

from repro.core import moe
from repro.kernels import ops


def _time(fn, args, iters: int) -> float:
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e3


def _ffn(matmul):
    def ffn(experts, xs, sizes):
        g = matmul(xs, experts["w_gate"], sizes)
        u = matmul(xs, experts["w_up"], sizes)
        h = (jax.nn.silu(g) * u).astype(xs.dtype)
        return matmul(h, experts["w_down"], sizes).astype(xs.dtype)
    return jax.jit(ffn)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--t", type=int, default=1024)
    ap.add_argument("--experts", type=int, default=128)
    ap.add_argument("--d", type=int, default=2048)
    ap.add_argument("--f", type=int, default=768)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--real", type=int, default=100)
    ap.add_argument("--iters", type=int, default=50)
    a = ap.parse_args(argv)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    bf = jnp.bfloat16
    experts = {
        "w_gate": jax.random.normal(keys[0], (a.experts, a.d, a.f), bf) * .02,
        "w_up": jax.random.normal(keys[1], (a.experts, a.d, a.f), bf) * .02,
        "w_down": jax.random.normal(keys[2], (a.experts, a.f, a.d), bf) * .02}
    x = jax.random.normal(keys[3], (a.t, a.d), bf)
    scores = jax.random.normal(keys[4], (a.t, a.experts))
    top_w, top_idx = jax.lax.top_k(jax.nn.softmax(scores), a.k)
    top_idx = top_idx.astype(jnp.int32)
    ragged = lambda xs, w, gs: jax.lax.ragged_dot(
        xs, w, gs, preferred_element_type=jnp.float32)
    gmm = lambda xs, w, gs: ops.grouped_matmul(xs, w, gs,
                                               moe.GROUPED_ROW_TILE)
    forms = {"ffn.gmm": _ffn(gmm),
             "ffn.ragged_dot": _ffn(ragged)}
    grouped = jax.jit(lambda ex, x_, i, w: moe.grouped_moe(ex, x_, i, w, 0))
    dispatch = jax.jit(lambda ex, x_, i, w: moe.dispatch_moe(
        ex, x_, i, w, a.experts, 0, a.t))
    for routing, n_real in (("real", a.real), ("full", a.t)):
        idx = jnp.where(jnp.arange(a.t)[:, None] < n_real, top_idx,
                        a.experts)                  # dead-route the rest
        w = jnp.where(idx < a.experts, top_w, 0.0)
        order, sizes, _ = moe.group_by_expert(idx, 0, a.experts)
        xs = x[order // a.k]
        out = {}
        for name, fn in forms.items():
            out[name] = _time(fn, (experts, xs, sizes), a.iters)
        out["layer.grouped"] = _time(grouped, (experts, x, idx, w), a.iters)
        out["layer.dispatch"] = _time(dispatch, (experts, x, idx, w),
                                      a.iters)
        for name, ms in out.items():
            print(json.dumps({"form": name, "routing": routing,
                              "rows": n_real * a.k, "ms": ms,
                              "t": a.t, "experts": a.experts, "d": a.d,
                              "f": a.f, "k": a.k, "device": device}))


if __name__ == "__main__":
    main()
