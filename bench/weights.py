"""Seeded random weights of a Qwen-family dense model, made by the benchmark.

``canonical(cfg, key)`` draws every weight in the published layout and
dtype (Hugging Face ``Qwen2``/``Qwen3`` module names, ``torch_dtype``).  The
plain reference reads these.  ``program_params`` lays the same values out
the way the serving program stores them, in one jitted call on the device:

* matrices become the program's ``(d, heads, head_dim)`` einsum shapes;
* the program rotates adjacent pairs ``(2i, 2i+1)`` where the published
  model rotates ``(i, i + head_dim/2)``, so the query and key head
  dimensions (and their biases and norms) are permuted to match, exactly
  as a checkpoint converter does;
* RMSNorm weights are stored as ``w - 1`` (the program computes
  ``x * (1 + w)``): the benchmark draws each norm weight as ``1 + delta``
  and hands the program ``delta``;
* the vocabulary is padded with zero rows to the program's table size.

No value is rounded twice: both sides see the same bf16 draws.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MATRIX_STD = 0.02        # Qwen's initializer_range
NORM_DELTA_STD = 0.05    # spread of RMSNorm weights around 1


def jax_key(seed: int):
    """A JAX key from a seed of any size (PRNGKey keeps only 32 bits)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)[0]
    return jax.random.PRNGKey(int(word))


def shapes(cfg: dict) -> dict:
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    ff, V = cfg["intermediate_size"], cfg["vocab_size"]
    s = {"embed": (V, d), "final_norm": (d,),
         "attn_norm": (L, d), "mlp_norm": (L, d),
         "wq": (L, d, hq * hd), "wk": (L, d, hk * hd), "wv": (L, d, hk * hd),
         "wo": (L, hq * hd, d),
         "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d)}
    if cfg.get("attention_bias"):
        s.update(bq=(L, hq * hd), bk=(L, hk * hd), bv=(L, hk * hd))
    if cfg.get("qk_norm"):
        s.update(q_norm=(L, hd), k_norm=(L, hd))
    if not cfg["tie_word_embeddings"]:
        s["head"] = (d, V)
    return s


NORMS = ("final_norm", "attn_norm", "mlp_norm", "q_norm", "k_norm")


def canonical(cfg: dict, key) -> dict:
    """Every weight, drawn in the published dtype.  Norm entries hold the
    delta from 1."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(cfg).items())):
        std = NORM_DELTA_STD if name in NORMS else MATRIX_STD
        out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32) * std).astype(dtype)
    return out


def rope_perm(head_dim: int) -> np.ndarray:
    """Published head-dim index for each of the program's positions."""
    half = head_dim // 2
    return np.stack([np.arange(half), np.arange(half) + half], -1).reshape(-1)


def to_program(cfg: dict, w: dict, like) -> dict:
    """The program's parameter tree (structure and dtypes of ``like``, the
    program's abstract parameters) holding the canonical values ``w``."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    perm = rope_perm(hd)

    def heads(x, h, roped):          # (..., h * hd) -> (..., h, hd)
        x = x.reshape(x.shape[:-1] + (h, hd))
        return x[..., perm] if roped else x

    attn = {"wq": heads(w["wq"], hq, True), "wk": heads(w["wk"], hk, True),
            "wv": heads(w["wv"], hk, False),
            "wo": w["wo"].reshape(L, hq, hd, d)}
    if "bq" in w:
        attn.update(bq=heads(w["bq"], hq, True), bk=heads(w["bk"], hk, True),
                    bv=heads(w["bv"], hk, False))
    if "q_norm" in w:
        attn.update(q_norm=w["q_norm"][..., perm], k_norm=w["k_norm"][..., perm])
    sub = {"norm1": w["attn_norm"], "attn": attn, "norm2": w["mlp_norm"],
           "ffn": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                   "w_down": w["w_down"]}}
    vp = like["embed"].shape[0]
    tree = {"embed": jnp.pad(w["embed"], ((0, vp - w["embed"].shape[0]), (0, 0))),
            "final_norm": w["final_norm"], "segment0": {"sub0": sub}}
    if "head" in w:
        tree["head"] = jnp.pad(w["head"], ((0, 0), (0, vp - w["head"].shape[1])))
    if jax.tree.structure(tree) != jax.tree.structure(like):
        raise ValueError(f"program parameter layout changed: "
                         f"{jax.tree.structure(like)}")
    def cast(x, l):
        if x.shape != l.shape:
            raise ValueError(f"weight shape {x.shape} where the program "
                             f"holds {l.shape}")
        return x.astype(l.dtype)

    return jax.tree.map(cast, tree, like)


def program_params(cfg: dict, like, seed: int):
    """One jitted call on the device: seed -> the program's parameters."""
    return jax.jit(lambda k: to_program(cfg, canonical(cfg, k), like))(
        jax_key(seed))
