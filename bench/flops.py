"""Operations and bytes that one engine step needs, from the model's
published shapes and the tokens the step carries.

Counted is the work the served model requires, whatever implements it:

* every linear layer (projections, MLP) once per real token, 2 FLOPs per
  multiply-add; the LM head once per emitted token;
* attention over the keys the Stem rule keeps (``stem_rule.py``): for a
  prompt token at position ``t`` in query block ``r``, the ``budget[r] - 1``
  earlier kept pages in full plus its own page up to ``t``; for a decode
  token, the decode budget's pages likewise; 4 FLOPs per (query head, key,
  head dim) for QK^T and PV;
* page scoring: one pooled dot product per (query head, visible page) and
  anti-diagonal group.

Padding, gather copies and recomputation are not counted.  Bytes are the
weights once per step in the published dtype plus the kept K/V pages of
every decode token (per KV head, at the budget of one query head).
"""
from __future__ import annotations

import numpy as np

from stem_rule import StemRule


def _dims(cfg):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"])


def linear_params(cfg) -> int:
    """Weights a token multiplies through, LM head excluded."""
    L, d, hq, hk, hd, ff, _ = _dims(cfg)
    return L * (d * hd * (hq + 2 * hk) + hq * hd * d + 3 * d * ff)


ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def weight_bytes(cfg) -> int:
    """The weights one step reads, in the published dtype: every linear
    layer and the LM head (the embedding rows it gathers are not counted)."""
    L, d, hq, hk, hd, ff, V = _dims(cfg)
    return (linear_params(cfg) + d * V) * ITEMSIZE[cfg["torch_dtype"]]


def decode_flops(cfg, rule: StemRule, pos: int) -> float:
    """One fed-back token at position ``pos`` (attending keys 0..pos)."""
    L, d, hq, hk, hd, ff, V = _dims(cfg)
    nv = pos // rule.page + 1
    kept = int(rule.decode_budget(nv))
    keys = (kept - 1) * rule.page + pos % rule.page + 1
    return (2.0 * linear_params(cfg) + 2.0 * d * V
            + L * hq * (4.0 * hd * keys + 2.0 * hd * nv))


def decode_bytes(cfg, rule: StemRule, pos: int) -> float:
    """K/V bytes a decode token reads at the budget (weights excluded)."""
    L, d, hq, hk, hd, ff, V = _dims(cfg)
    kept = int(rule.decode_budget(pos // rule.page + 1))
    return float(L * hk * kept * rule.page * hd * 2
                 * ITEMSIZE[cfg["torch_dtype"]])


def chunk_flops(cfg, rule: StemRule, prompt_len: int, start: int,
                width: int, completes: bool) -> float:
    """One prefill chunk of ``width`` tokens from ``start`` of a prompt of
    ``prompt_len`` tokens; only real prompt tokens count."""
    L, d, hq, hk, hd, ff, V = _dims(cfg)
    padded = -(-prompt_len // rule.page) * rule.page
    budgets = rule.prefill_budgets(padded)
    t = np.arange(start, min(start + width, prompt_len))
    if t.size == 0:
        return 0.0
    r = t // rule.page
    keys = (budgets[r].astype(np.int64) - 1) * rule.page + t % rule.page + 1
    rows = np.unique(r)
    score = float(np.sum((rows + 1) * rule.stride)) * 2.0 * hd * hq * L
    return (2.0 * linear_params(cfg) * t.size + 4.0 * L * hq * hd * keys.sum()
            + score + (2.0 * d * V if completes else 0.0))


def step_flops(cfg, rule: StemRule, step: dict) -> float:
    """``step``: {"decode": [positions], "chunks": [[prompt_len, start,
    width, completes], ...]}."""
    return (sum(decode_flops(cfg, rule, p) for p in step["decode"])
            + sum(chunk_flops(cfg, rule, *c) for c in step["chunks"]))


def step_bytes(cfg, rule: StemRule, step: dict) -> float:
    return weight_bytes(cfg) + sum(decode_bytes(cfg, rule, p)
                                   for p in step["decode"])
