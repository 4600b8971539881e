"""Sizes of a configuration as the benchmark reads them from its file in
``bench/configs/``: the published widths at the top level, the layout the
served weights take under ``"layout"``.  The weight maker, the reference
and the operation counts read only this, never the program's config."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    expert_ff: int
    experts: int              # routed experts of the published model
    experts_held: int         # expert slots in the served weight arrays
    top_k: int
    vocab: int
    vocab_held: int           # rows of the served embedding / head
    tied: bool
    qk_norm: bool
    rope_theta: float
    rms_eps: float
    dtype: str                # what the weights are served in

    @property
    def kv_bytes_per_token(self) -> int:
        """Cache bytes one token holds over all layers (K and V)."""
        return self.layers * 2 * self.kv_heads * self.head_dim * 2


def from_config(cfg: dict) -> ModelSpec:
    lay = cfg["layout"]
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    return ModelSpec(
        layers=cfg["num_hidden_layers"],
        d_model=d,
        heads=heads,
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or d // heads,
        expert_ff=cfg.get("moe_intermediate_size") or cfg["intermediate_size"],
        experts=cfg.get("num_experts") or cfg["num_local_experts"],
        experts_held=lay["experts_held"],
        top_k=cfg["num_experts_per_tok"],
        vocab=cfg["vocab_size"],
        vocab_held=-(-cfg["vocab_size"] // lay["vocab_multiple"])
        * lay["vocab_multiple"],
        tied=bool(cfg["tie_word_embeddings"]),
        qk_norm=bool(lay["qk_norm"]),
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        dtype=cfg["torch_dtype"],
    )
