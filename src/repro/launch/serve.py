"""Serving launcher: continuous batching through the redesigned ServingEngine
(batched one-jit-call prefill, async decode, device-side routing capture).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-moe-30b-a3b \
        --reduced --requests 8 --new-tokens 16

Reports the paper's §5.2-style breakdown: prompt-evaluation and
token-generation throughput, plus the measured E[#exec experts/node/layer]
statistic that feeds the perf model (Table 1).  The statistic is *exact*:
it is computed from the routing decisions the device returns as auxiliary
forward-pass outputs, not from a host-side router replay (the decode hot
loop performs zero host-side router evaluations).

The default engine is the unified token-budget scheduler
(``EngineConfig.unified_step``): chunked prefill streamed through the cache
and mixed prefill/decode batches in one jit program, so admissions never
stall decode and TTFT/stall are reported honestly.  ``--reference``
restores the two-program engine (padded whole-prompt prefill + decode);
``--legacy`` additionally restores the seed engine's behaviour
(per-request batch-1 prefill, a blocking host sync every decode step) —
``python -m benchmarks.serving_engine`` automates the comparison.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.configs.base import get_config
from repro.core import perf_model
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import EngineConfig, ServingEngine


def serve_demo(cfg, *, requests: int, new_tokens: int, prompt_len: int,
               max_batch: int = 4, seed: int = 0, legacy: bool = False,
               unified: bool = True, chunk_len: int = 32,
               token_budget: int = 0, temperature: float = 0.0,
               top_k: int = 0, paged: bool = False, page_size: int = 16,
               num_pages: int = 0, paged_kernel: bool = False,
               shared_prefix: int = 0,
               weight_quant: str | None = None, fit_cfg=None,
               priorities=None, deadline_ms: float | None = None,
               overcommit: bool = False):
    if weight_quant is not None:
        cfg = cfg.replace(weight_quant=weight_quant)
    fit_cfg = fit_cfg or cfg
    eng = ServingEngine(cfg, EngineConfig(
        max_batch=max_batch, prefill_len=prompt_len,
        max_cache=prompt_len + new_tokens + 8,
        batched_prefill=not legacy, async_steps=not legacy,
        unified_step=unified and not legacy, chunk_len=chunk_len,
        token_budget=token_budget, paged=paged, page_size=page_size,
        num_pages=num_pages, paged_kernel=paged_kernel,
        overcommit=overcommit))
    rng = np.random.default_rng(seed)
    sysp = rng.integers(0, cfg.vocab_size, shared_prefix)
    for k in range(requests):
        plen = int(rng.integers(prompt_len // 2, prompt_len + 1))
        plen = max(plen, min(shared_prefix + 1, prompt_len))
        tail = rng.integers(0, cfg.vocab_size, max(plen - shared_prefix, 1))
        eng.submit(np.concatenate([sysp, tail])[:prompt_len], new_tokens,
                   temperature=temperature, top_k=top_k,
                   priority=(priorities[k % len(priorities)]
                             if priorities else 0),
                   deadline_ms=deadline_ms)
    done = eng.run_until_done()
    tp = eng.throughput()
    mode = ("legacy (seq prefill, sync)" if legacy
            else "paged unified" if paged
            else "unified token-budget" if eng.unified
            else "batched + async (reference)")
    print(f"completed {len(done)} requests [{mode}]")
    print(f"prompt-eval throughput : {tp['prefill_tok_per_s']:.1f} tok/s")
    print(f"generation throughput  : {tp['decode_tok_per_s']:.1f} tok/s")
    print(f"overall throughput     : {tp['total_tok_per_s']:.1f} tok/s")
    print(f"prefill padding overhead: {tp['prefill_padding_overhead']:.1%}  "
          f"decode stall: {tp['decode_stall_s'] * 1e3:.1f} ms")
    ms = eng.memory_stats()
    print(f"device memory          : weights {ms['weight_bytes'] / 1e6:.2f} "
          f"MB (weight_quant={ms['weight_quant']}), KV pool "
          f"{ms['kv_pool_bytes'] / 1e6:.2f} MB, total "
          f"{ms['total_bytes'] / 1e6:.2f} MB")
    tt = eng.ttft()
    if tt["n"]:
        print(f"TTFT p50/p95           : {tt['p50'] * 1e3:.1f} / "
              f"{tt['p95'] * 1e3:.1f} ms over {tt['n']} requests")
    ps = eng.paged_stats()
    if ps.get("paged"):
        print(f"page pool              : {ps['pages_in_use']}/"
              f"{ps['num_pages']} pages in use, high-water "
              f"{ps['pages_hwm']} ({ps['pool_utilization']:.1%} of pool), "
              f"page_size {ps['page_size']}")
        if ps.get("paged_kernel"):
            # attention-read model at end-of-generation context: what the
            # block-table kernel reads vs what the gather path would have
            # mean over the decode trajectory, not the end-of-decode
            # snapshot — at the last step every row fills its block table
            # and the two paths read the same bytes by construction
            rb = perf_model.paged_attention_read_bytes(
                cfg, lengths=[prompt_len + i for i in range(new_tokens)
                              for _ in range(max_batch)],
                page_size=page_size, max_blocks=eng.max_blocks)
            steps = max(new_tokens, 1)
            print(f"paged-attention kernel : block-table decode in VMEM, "
                  f"{rb['kernel_bytes'] / steps / 1e6:.2f} MB/step "
                  f"attention reads vs "
                  f"{rb['gather_bytes'] / steps / 1e6:.2f} MB gather "
                  f"({rb['ratio']:.1f}x)")
        print(f"prefix cache           : hit rate {ps['prefix_hit_rate']:.1%}"
              f" ({ps['prefix_hits']}/{ps['prefix_lookups']} lookups), "
              f"{ps['prefix_hit_tokens']} prefill tokens skipped, "
              f"{ps['prefix_cached_pages']} pages cached, "
              f"{ps['prefix_evictions']} evictions, "
              f"{ps['cow_copies']} CoW copies")
    rs = eng.resilience_stats()
    n_done = sum(1 for r in done if r.status == "done")
    if any(rs.values()) or n_done != len(done):
        print(f"resilience             : {n_done}/{len(done)} done, "
              f"{rs['expired']} expired, {rs['cancelled']} cancelled, "
              f"{rs['failed']} failed; {rs['preemptions']} preemptions / "
              f"{rs['restores']} restores "
              f"({rs['restore_hit_tokens']} tokens restored from prefix "
              f"cache), admitted high-water {rs['active_hwm']}")
    if cfg.is_moe:
        for n in (2, 3, 4):
            e = eng.expected_experts_per_node(n)
            est = perf_model.estimate(
                perf_model.MoEWorkload.from_config(cfg),
                perf_model.M2_ULTRA_10GBE, n, expected_experts=e)
            print(f"E[#exec experts/node/layer] @ {n} nodes: {e:.2f}  "
                  f"(paper-model bound {est.throughput:.1f} tok/s)")
        # the weight-bytes capacity term (docs/DESIGN.md §8): which quant
        # level lets N Table-2 nodes host the arch — always computed from
        # ``fit_cfg`` (main() passes the FULL-SIZE config, so --reduced
        # demos still print the real capacity answer)
        try:
            fit = perf_model.max_model_at_budget(fit_cfg, n_nodes=2)
            lv = fit["level"] or "does not fit (even int4)"
            print(f"memory fit @ 2 M2-Ultra nodes ({fit_cfg.name}): {lv}  "
                  + " ".join(f"{k}={v / 1e9:.1f}GB"
                             for k, v in fit["per_node_bytes"].items()))
        except ValueError:
            pass                       # non-attention family: no model
    return eng, done


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--legacy", action="store_true",
                    help="seed-engine behaviour: per-request prefill + "
                         "per-step host sync (for A/B comparison)")
    ap.add_argument("--reference", action="store_true",
                    help="two-program reference engine (batched padded "
                         "prefill + decode; unified_step=False)")
    ap.add_argument("--chunk-len", type=int, default=32,
                    help="unified mode: prefill chunk / block width")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="unified mode: per-iteration prefill-token cap "
                         "(0 = unlimited; decode rows are exempt)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="per-request top-k cut (0 = full vocab)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: page pool + block tables + "
                         "prefix-cache reuse (docs/DESIGN.md §7; implies "
                         "the unified scheduler)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged mode: tokens per page")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="paged mode: pool size in pages (0 = auto: the "
                         "contiguous layout's token capacity)")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="paged mode: attend through the Pallas "
                         "block-table kernel (kernels/paged_attn.py) "
                         "instead of gathering a virtual cache — "
                         "attention reads scale with row lengths, not "
                         "pool size (docs/DESIGN.md §11)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens of system prompt shared by every request "
                         "(exercises the prefix cache in --paged mode)")
    ap.add_argument("--priority", type=int, nargs="+", default=None,
                    help="admission priorities, cycled across requests "
                         "(e.g. --priority 0 5: every other request is "
                         "high-priority; higher admits first and, with "
                         "--overcommit, may preempt lower)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request wall-clock deadline from submit; "
                         "unfinished requests expire and release pages")
    ap.add_argument("--overcommit", action="store_true",
                    help="paged mode: admit on current context instead of "
                         "reserving the full lifetime; under pool pressure "
                         "the scheduler preempts low-priority rows into "
                         "the prefix cache and restores them later "
                         "(docs/DESIGN.md §10)")
    ap.add_argument("--weight-quant", choices=["none", "int8", "int4"],
                    default=None,
                    help="blockwise quantized weight store "
                         "(docs/DESIGN.md §8): weights load as int8 / "
                         "packed-int4 QuantTensor leaves with per-block "
                         "fp32 scales; router and embedding stay fp")
    args = ap.parse_args()
    if args.overcommit and not args.paged:
        ap.error("--overcommit requires --paged (it is a page-pool "
                 "admission policy)")
    if args.paged_kernel and not args.paged:
        ap.error("--paged-kernel requires --paged (the kernel attends "
                 "through the page pool's block tables)")

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    serve_demo(cfg, requests=args.requests, new_tokens=args.new_tokens,
               prompt_len=args.prompt_len, max_batch=args.max_batch,
               legacy=args.legacy, unified=not args.reference,
               chunk_len=args.chunk_len, token_budget=args.token_budget,
               temperature=args.temperature, top_k=args.top_k,
               paged=args.paged, page_size=args.page_size,
               num_pages=args.num_pages, paged_kernel=args.paged_kernel,
               shared_prefix=args.shared_prefix,
               weight_quant=args.weight_quant,
               fit_cfg=get_config(args.arch), priorities=args.priority,
               deadline_ms=args.deadline_ms, overcommit=args.overcommit)


if __name__ == "__main__":
    main()
