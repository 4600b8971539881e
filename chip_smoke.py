#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU at Granite-3.0-3B-A800M's
published widths and depth (32 layers, d 1536, 40 experts padded to 48,
top-8, vocab 49155), bf16, random weights from ``--seed``.

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chip  # (1, 4) expert-parallel mesh vs one chip

The one-chip run serves a handful of requests through
``ServingEngine.run_until_done()`` on the paged unified engine (chunked
prefill and mixed prefill/decode steps over the page pool, gather
attention) and fails unless every request ends ``done``, every logit row
the engine sampled from is finite, and the engine's first-token logits
for one prompt agree with a plain ``Model.forward`` of that prompt.

``--four-chip`` runs only the expert-parallel check: the same engine over
a (data 1, model 4) mesh with the decentralized schedule, each chip
holding 12 of the 48 padded experts, must produce the same greedy tokens
as the one-chip engine on the same requests and weights.

Lines before the last are informational.  The last line of stdout is one
JSON object naming the device; it is printed only when every check
passed.  A host without a TPU exits non-zero before building anything.
The whole run is one process: it holds the chip throughout.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.serving.engine import EngineConfig, ServingEngine  # noqa: E402

ARCH = "granite_moe_3b_a800m"

# Relative L2 distance allowed between the engine's first-token logits and
# Model.forward's.  Both run in bf16; they differ in how the prompt is cut
# (32-token chunks through the page pool vs one causal pass) and so in
# rounding order.  One bf16 rounding is 2^-9 relative; the residual stream
# takes 64 bf16 adds over 32 layers, so a few 1e-2 is the expected spread,
# while a wrong position, mask, page or cache write moves the logits by
# O(1).
LOGIT_REL_TOL = 5e-2


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Plan:
    """Requests and pool of one smoke run."""
    prompt_lens: tuple = (300, 200, 260, 330, 120, 240)
    new_tokens: int = 32
    max_batch: int = 4
    chunk_len: int = 32
    page_size: int = 16
    num_pages: int = 512          # 512 x 16 tokens x 64 KiB = 512 MiB of KV
    max_cache: int = 512
    warmup_len: int = 40          # two prefill chunks, then decode


def smoke_config(cfg):
    """The capacity factor E_pad / k makes every expert's capacity equal
    the step's token count, so dispatch can drop nothing: engine, plain
    forward and mesh all compute every routed token."""
    return cfg.replace(
        capacity_factor=cfg.num_experts_padded / cfg.experts_per_token)


def engine_config(plan: Plan) -> EngineConfig:
    return EngineConfig(
        max_batch=plan.max_batch, max_cache=plan.max_cache,
        chunk_len=plan.chunk_len, unified_step=True, paged=True,
        paged_kernel=False, page_size=plan.page_size,
        num_pages=plan.num_pages, nan_guard=True)


def make_prompts(vocab: int, plan: Plan, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n, dtype=np.int32)
            for n in plan.prompt_lens]


class _LogitProbeEngine(ServingEngine):
    """ServingEngine whose step program also hands row 0's logits to the
    host, keyed by the step index.  Sampling itself is unchanged."""

    def __init__(self, *args, **kw):
        self.row0_logits: dict[int, np.ndarray] = {}
        super().__init__(*args, **kw)

    def _keep(self, step_idx, row0):
        self.row0_logits[int(step_idx)] = np.asarray(row0)

    def _sample_next(self, logits, temps, topks, step_idx, sampling):
        jax.debug.callback(self._keep, step_idx, logits[0])
        return super()._sample_next(logits, temps, topks, step_idx, sampling)


def _serve(eng: ServingEngine, prompts, plan: Plan) -> tuple[list, float]:
    uids = [eng.submit(p, max_new_tokens=plan.new_tokens) for p in prompts]
    t0 = time.perf_counter()
    done = eng.run_until_done()
    wall = time.perf_counter() - t0
    by_uid = {r.uid: r for r in done}
    missing = [u for u in uids if u not in by_uid]
    check(not missing, f"requests {missing} did not finish")
    reqs = [by_uid[u] for u in uids]
    for r in reqs:
        check(r.status == "done" and len(r.generated) == plan.new_tokens,
              f"request {r.uid} ended {r.status!r} with "
              f"{len(r.generated)}/{plan.new_tokens} tokens")
    return reqs, wall


def one_chip(cfg, plan: Plan, seed: int) -> None:
    """Serve the plan's requests on the default device and check them."""
    model_cfg = smoke_config(cfg)
    prompts = make_prompts(cfg.vocab_size, plan, seed)
    check(all(n > plan.chunk_len for n in plan.prompt_lens),
          "every prompt must span several prefill chunks")
    check(len(prompts) > plan.max_batch,
          "more requests than slots, so admission overlaps decode")

    t0 = time.perf_counter()
    eng = _LogitProbeEngine(model_cfg, engine_config(plan),
                            rng=jax.random.PRNGKey(seed))
    jax.block_until_ready(eng.params)
    setup_s = time.perf_counter() - t0
    mem = eng.memory_stats()
    print(f"setup_s {setup_s:.3f}  weight_bytes {mem['weight_bytes']}  "
          f"kv_pool_bytes {mem['kv_pool_bytes']}", flush=True)

    # warm-up request: traces and compiles the chunk-width and the
    # decode-width step programs before the served window
    warm = np.random.default_rng(seed + 1).integers(
        0, cfg.vocab_size, plan.warmup_len, dtype=np.int32)
    t0 = time.perf_counter()
    _serve(eng, [warm], dataclasses.replace(plan, new_tokens=2))
    jax.effects_barrier()
    compile_s = time.perf_counter() - t0
    traces = dict(eng.trace_counts)
    print(f"compile_s {compile_s:.3f}  traces {traces}", flush=True)

    first_step = max(eng.row0_logits) + 1
    reqs, wall = _serve(eng, prompts, plan)
    jax.effects_barrier()
    n_tok = sum(len(r.generated) for r in reqs)
    print(f"requests {len(reqs)}  tokens_generated {n_tok}  "
          f"wall_s {wall:.3f}  prefill_tokens {eng.stats['prefill_tokens']}  "
          f"mixed_decode_tokens {eng.stats['mixed_decode_tokens']}",
          flush=True)
    check(dict(eng.trace_counts) == traces,
          f"step programs re-traced while serving: {dict(eng.trace_counts)}")
    check(eng.stats["mixed_decode_tokens"] > 0,
          "no step mixed prefill chunks with decode rows")
    res = eng.resilience_stats()
    check(res["nan_quarantines"] == 0 and res["failed"] == 0,
          f"non-finite logits in served rows: {res}")

    # The first request submitted takes slot 0 and, with no token budget,
    # streams one chunk per step from the first step on, so its first
    # token comes from step first_step + ceil(len / chunk_len) - 1.
    probe = reqs[0]
    step = first_step + math.ceil(len(probe.prompt) / plan.chunk_len) - 1
    v = cfg.vocab_size
    got = eng.row0_logits[step][:v]
    check(bool(np.all(np.isfinite(got))), "engine first-token logits "
          "are not finite")
    check(int(np.argmax(got)) == probe.generated[0],
          "probe step does not hold the probe's first token")
    forward = jax.jit(lambda p, t: eng.model.forward(p, {"tokens": t})[0][0, -1])
    want = np.asarray(forward(eng.params, jnp.asarray(probe.prompt)[None]),
                      np.float32)[:v]
    check(bool(np.all(np.isfinite(want))), "Model.forward logits are not "
          "finite")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    print(f"first_token_logits rel_l2 {rel:.6g} (tolerance {LOGIT_REL_TOL})  "
          f"max_abs {float(np.max(np.abs(got - want))):.6g}  "
          f"ref_max_abs {float(np.max(np.abs(want))):.6g}  "
          f"argmax_equal {int(np.argmax(got)) == int(np.argmax(want))}",
          flush=True)
    check(rel <= LOGIT_REL_TOL,
          f"engine first-token logits differ from Model.forward: rel_l2 "
          f"{rel:.4g} > {LOGIT_REL_TOL}")


def four_chip(cfg, plan: Plan, seed: int) -> None:
    """Greedy tokens of the engine on a (1, 4) expert-parallel mesh equal
    those of the one-chip engine, on the same weights and requests."""
    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chip needs 4 devices, found "
          f"{len(devices)}")
    # Activations in float32 over the bf16 weights, with float32 matmuls:
    # in bf16 the mesh's per-chip partial sums (row-parallel attention
    # output, expert combine) round differently from one chip's single
    # reduction, and greedy decoding with random weights then parts ways
    # at a near-tie although both are right.  In float32 the two differ
    # by reduction order alone, far below any logit gap.
    model_cfg = smoke_config(cfg).replace(expert_parallel="decentralized",
                                          dtype="float32")
    check(model_cfg.num_experts_padded % 4 == 0,
          "padded experts must divide over 4 chips")
    prompts = make_prompts(cfg.vocab_size, plan, seed)
    ecfg = engine_config(plan)
    # one set of weights for both engines
    params = build_model(model_cfg).init(jax.random.PRNGKey(seed))
    print(f"weights {model_cfg.param_dtype}  activations {model_cfg.dtype}  "
          f"matmul precision highest", flush=True)
    out = {}
    for name, mesh in (("one_chip", None),
                       ("mesh_1x4", make_mesh((1, 4), ("data", "model"),
                                              devices=devices[:4]))):
        t0 = time.perf_counter()
        eng = ServingEngine(model_cfg, ecfg, params=params, mesh=mesh)
        with jax.default_matmul_precision("highest"):
            reqs, wall = _serve(eng, prompts, plan)
        out[name] = [list(r.generated) for r in reqs]
        held = (model_cfg.num_experts_padded if mesh is None
                else model_cfg.num_experts_padded // mesh.shape["model"])
        print(f"{name}: experts_per_chip {held}  wall_s {wall:.3f}  "
              f"total_s {time.perf_counter() - t0:.3f}  tokens "
              f"{sum(len(g) for g in out[name])}", flush=True)
        del eng
    mismatched = [i for i, (a, b) in enumerate(zip(out["one_chip"],
                                                   out["mesh_1x4"])) if a != b]
    for i in mismatched:
        a, b = out["one_chip"][i], out["mesh_1x4"][i]
        first = next(j for j in range(len(a)) if a[j] != b[j])
        print(f"request {i}: first differing token {first}: one_chip "
              f"{a[first:first + 4]} mesh {b[first:first + 4]}", flush=True)
    check(not mismatched, f"greedy tokens differ in requests {mismatched}")
    print(f"greedy tokens equal over {len(prompts)} requests", flush=True)


def main(argv=None, *, cfg=None, plan: Plan | None = None,
         platform: str = "tpu") -> int:
    """Run the smoke test.  ``cfg``, ``plan`` and ``platform`` exist for
    the CPU test of this script's own checks; the command line always
    runs the full-size configuration and requires a TPU."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the (1, 4) expert-parallel mesh check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != platform:
        print(f"chip_smoke: needs a {platform} device, JAX found "
              f"{dev.platform!r} ({devices})", file=sys.stderr)
        return 1
    print(f"devices {devices}", flush=True)
    print(f"device_kind {dev.device_kind}", flush=True)
    print(f"compile_cache {jax.config.jax_compilation_cache_dir}",
          flush=True)

    cfg = cfg if cfg is not None else get_config(ARCH)
    plan = plan or Plan()
    print(f"model {cfg.name}  layers {cfg.num_layers}  d_model {cfg.d_model}  "
          f"experts {cfg.num_experts}/{cfg.num_experts_padded} top-"
          f"{cfg.experts_per_token}  vocab {cfg.vocab_size}  dtype "
          f"{cfg.dtype}", flush=True)
    (four_chip if args.four_chip else one_chip)(cfg, plan, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
