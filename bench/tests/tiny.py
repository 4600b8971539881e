"""Cells of the benchmark's configurations and traffic mixes cut to a size
a CPU test can run: the same files, with the widths, the engine and the
traffic made small."""
from __future__ import annotations

import copy

from bench import cells

# (configuration, traffic mix) of each family the tests run
CHAT = ("granite-3b-a800m", "chat-sessions")
REASONING = ("qwen3-30b-a3b-l8", "reasoning")


def tiny_cell(pair: tuple, dtype: str = "bfloat16", limit: float | None = None) -> cells.Cell:
    """``limit``: the logit-gap limit at this size (the file's limit is
    set for the published widths, whose logits have another scale)."""
    bench = cells.load_benchmark()
    cfg = copy.deepcopy(cells.load_config(pair[0]))
    e2e = tuple(cells.Metric(m["name"], m["unit"])
                for m in bench["end_to_end"])
    c = cells.Cell("-".join(pair), cfg["chips"], cfg,
                   cells.load_traffic(pair[1]), e2e, ())
    cfg.update(hidden_size=128, num_attention_heads=4,
               num_key_value_heads=2,
               num_hidden_layers=2, vocab_size=500, torch_dtype=dtype)
    if "head_dim" in cfg:
        cfg["head_dim"] = 32
    if "moe_intermediate_size" in cfg:
        cfg.update(moe_intermediate_size=64, num_experts=8)
        group = 4
    else:
        cfg.update(intermediate_size=64, num_local_experts=6)
        group = 2
    cfg["num_experts_per_tok"] = 2
    experts = cfg.get("num_experts") or cfg["num_local_experts"]
    cfg["layout"] = dict(cfg["layout"], experts_held=8)
    cfg["repro"] = dict(cfg["repro"], overrides=dict(
        cfg["repro"]["overrides"], d_model=128, num_heads=4, num_kv_heads=2,
        head_dim=32, d_ff=64, num_experts=experts, num_experts_padded=8,
        experts_per_token=2, vocab_size=500, dtype=dtype, param_dtype=dtype))
    cfg["engine"] = dict(cfg["engine"], max_batch=4, chunk_len=16,
                         max_cache=256, num_pages=96)
    cfg["check"] = dict(cfg["check"], packed_tokens=1024, expert_group=group,
                        min_compared_tokens=20)
    if limit is not None:
        cfg["check"]["mean_logit_gap"] = limit
    mix = copy.deepcopy(c.traffic)
    mix.update(clients=4, block=8, blocks=4)
    if "system_prompts" in mix:
        mix["max_total"] = 250
        mix["system_prompts"].update(len_min=20, len_max=60)
        mix["user_turn"].update(median=20, min=4, max=60)
        mix["output"].update(median=12, min=4, max=24)
    else:
        mix["prompt"].update(median=20, min=8, max=40)
        mix["output"].update(median=30, min=8, max=60)
    return cells.Cell(c.name, c.chips, cfg, mix, c.end_to_end, ())
