"""Operations and bytes one served step needs, counted from the
configuration's shapes and the step's real rows: no padding, no dropped or
duplicated expert rows, attention over each row's real context only.
These are the numerators of ``mfu.*`` and ``decode_hbm_share.*``."""
from __future__ import annotations

import numpy as np

from bench.spec import ModelSpec

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _attn_params(s: ModelSpec) -> int:
    return (s.d_model * (s.heads + 2 * s.kv_heads) * s.head_dim
            + s.heads * s.head_dim * s.d_model)


def step_flops(s: ModelSpec, contexts, segs, emitted: int) -> float:
    """FLOPs of one step whose rows append ``segs[i]`` tokens after
    ``contexts[i]`` cached ones, emitting ``emitted`` tokens (each emitted
    token needs one LM-head row)."""
    c = np.asarray(contexts, np.float64)
    n = np.asarray(segs, np.float64)
    tokens = n.sum()
    per_token = (2 * _attn_params(s)
                 + 2 * s.d_model * s.experts                 # router
                 + s.top_k * 3 * 2 * s.d_model * s.expert_ff)
    # token j of a row (0-based) attends contexts + j + 1 keys, for QK^T
    # and for PV
    keys = (n * c + n * (n + 1) / 2).sum()
    attn = 2 * 2 * s.heads * s.head_dim * keys
    head = 2 * s.d_model * s.vocab * emitted
    return float(s.layers * (tokens * per_token + attn) + head)


def decode_bytes(s: ModelSpec, contexts, experts_touched: int) -> float:
    """Bytes a decode step must read from HBM: every weight outside the
    experts, the experts its routing touched (``experts_touched`` summed
    over layers), and the cached keys and values of each row's real
    context plus the token it appends."""
    w = BYTES[s.dtype]
    per_layer = (_attn_params(s) + s.d_model * s.experts + 2 * s.d_model
                 + (2 * s.head_dim if s.qk_norm else 0))
    rows = len(contexts)
    weights = (s.layers * per_layer + s.d_model            # final norm
               + s.d_model * s.vocab                      # LM head
               + rows * s.d_model)                        # embedding rows
    experts = experts_touched * 3 * s.d_model * s.expert_ff
    kv = (np.asarray(contexts, np.float64) + 1).sum() * s.kv_bytes_per_token
    return float(w * (weights + experts) + kv)
