"""Plain reference of a served Qwen-family dense model under Stem attention.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``, written
from the published model (Hugging Face ``Qwen2``/``Qwen3``: RMSNorm, rotary
embedding with ``rotate_half``, optional QKV bias and per-head q/k norm,
SwiGLU) and from Stem's serving rule (``stem_rule.py``).  It imports nothing
of the program and takes none of its arrays: weights come from
``weights.canonical`` and the seed, stay in the dtype they are served in,
and are widened to float32 one layer at a time.

What a served request computes, position by position:

* prompt positions: the prompt is right-padded with token 0 to a page
  multiple.  Keys and values at and after the true length are zero (the
  page pool holds zeros there).  Each 128-row query block keeps its own
  page and page 0, plus the best-scoring earlier pages up to its prefill
  budget; the score of a page is the anti-diagonal routing score of the
  block's pooled queries against the page's pooled keys plus
  ``beta * max(0, max log ||v||)``.  Attention is exact and token-causal
  over the kept pages.
* generated positions (the served tokens fed back): each query keeps
  page 0 and its own page plus the best pages by its own score against
  each full page's mean key, up to the decode budget.

``logits`` returns the next-token logits at the last prompt position and
at every fed-back position.  With ``fp8=True`` both operands of every
linear layer and of the LM head, and the queries, keys and values, are
rounded to float8 e4m3 with one scale per tensor: the control, one
precision step below the bf16 the model is served in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from stem_rule import StemRule

HI = jax.lax.Precision.HIGHEST
DECODE_BUCKET = 512    # fed-back positions are padded to a multiple of this
NEG = -1e30
BIG = 1e30


def _fp8(x):
    """Round to float8 e4m3 with one scale for the tensor (its absolute
    maximum maps to e4m3's largest value, 448)."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, fp8):
    """x (..., k) @ w (k, n)."""
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, pos, theta):
    """x (s, h, d); rotate_half convention."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv            # (s, d/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _qkv(cfg, lw, h, pos, fp8):
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q, k, v = (_linear(h, lw[n], fp8) for n in ("wq", "wk", "wv"))
    if "bq" in lw:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    s = h.shape[0]
    q, k, v = (q.reshape(s, hq, hd), k.reshape(s, hk, hd), v.reshape(s, hk, hd))
    if "q_norm" in lw:
        q = _rms(q, lw["q_norm"], cfg["rms_norm_eps"])
        k = _rms(k, lw["k_norm"], cfg["rms_norm_eps"])
    theta = cfg["rope_theta"]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    if fp8:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    return q, k, v


def _mlp(cfg, lw, x, fp8):
    h = _rms(x, lw["mlp_norm"], cfg["rms_norm_eps"])
    g = jax.nn.silu(_linear(h, lw["w_gate"], fp8))
    return _linear(g * _linear(h, lw["w_up"], fp8), lw["w_down"], fp8)


def _summaries(k, v, page, stride):
    """Per page: anti-diagonal group means of K (n, hk, stride, d) and the
    max log ||v|| (n, hk)."""
    s, hk, d = k.shape
    n = s // page
    kg = k.reshape(n, page // stride, stride, hk, d).mean(1)      # n,u,hk,d
    vn = jnp.log(jnp.maximum(jnp.linalg.norm(v, axis=-1), 1e-20))
    return jnp.swapaxes(kg, 1, 2), vn.reshape(n, page, hk).max(1)


def _prefill_layer(cfg, rule, kmax, lw, x, pos, true_len, budgets, fp8):
    """One layer over the padded prompt.  Returns (x, zeroed k, v)."""
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    grp, page, stride = hq // hk, rule.page, rule.stride
    q, k, v = _qkv(cfg, lw, _rms(x, lw["attn_norm"], cfg["rms_norm_eps"]),
                   pos, fp8)
    live_tok = (pos < true_len)[:, None, None]
    k, v = jnp.where(live_tok, k, 0.0), jnp.where(live_tok, v, 0.0)
    n = x.shape[0] // page
    kg, vm = _summaries(k, v, page, stride)                 # (n,hk,u,d),(n,hk)
    qp = q.reshape(n, page // stride, stride, hq, hd).mean(1)    # n,u,hq,d
    pair = (stride - np.arange(stride)) % stride
    kgq = jnp.repeat(kg[:, :, pair], grp, axis=1)            # n,hq,u,d
    route = jnp.einsum("ruhd,jhud->hrj", qp, kgq, precision=HI) / (
        stride * np.sqrt(hd))
    score = route + rule.beta * jnp.maximum(
        jnp.repeat(vm, grp, axis=1).T, 0.0)[:, None, :]      # (hq, n, n)
    r = jnp.arange(n)[:, None]
    j = jnp.arange(n)[None, :]
    forced = ((j < rule.sink) | (j > r - rule.local)) & (j <= r)
    biased = jnp.where(forced, score + BIG, score)
    biased = jnp.where(j <= r, biased, NEG)
    vals, idx = jax.lax.top_k(biased, kmax)                  # (hq, n, kmax)
    live = (vals > NEG / 2) & (jnp.arange(kmax) < budgets[:, None])

    kb = k.reshape(n, page, hk, hd)
    vb = v.reshape(n, page, hk, hd)
    qb = q.reshape(n, page, hq, hd)
    head_kv = np.arange(hq) // grp

    def row(args):
        rr, qr, ir, lr = args              # qr (page,hq,d); ir, lr (hq,kmax)
        kk = kb[ir, :, head_kv[:, None]]    # (hq, kmax, page, d)
        vv = vb[ir, :, head_kv[:, None]]
        s = jnp.einsum("qhd,hkpd->hqkp", qr, kk, precision=HI) / np.sqrt(hd)
        qpos = rr * page + jnp.arange(page)
        kpos = ir[:, :, None] * page + jnp.arange(page)      # (hq,kmax,page)
        keep = (kpos[:, None] <= qpos[None, :, None, None]) & lr[:, None, :, None]
        s = jnp.where(keep, s, NEG)
        p = jax.nn.softmax(s.reshape(hq, page, -1), -1).reshape(s.shape)
        p = jnp.where(keep, p, 0.0)
        return jnp.einsum("hqkp,hkpd->qhd", p, vv, precision=HI)

    o = jax.lax.map(row, (jnp.arange(n), qb, jnp.swapaxes(idx, 0, 1),
                          jnp.swapaxes(live, 0, 1)), batch_size=8)
    x = x + _linear(o.reshape(x.shape[0], hq * hd), lw["wo"], fp8)
    return x + _mlp(cfg, lw, x, fp8), k, v


def _decode_layer(cfg, rule, lw, x, pos, n_dec, kp, vp, prompt_len, fp8):
    """One layer over the fed-back tokens at ``pos`` (prompt_len + i),
    attending over the prompt's keys ``kp``/``vp`` and their own."""
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    grp, page, stride = hq // hk, rule.page, rule.stride
    q, k, v = _qkv(cfg, lw, _rms(x, lw["attn_norm"], cfg["rms_norm_eps"]),
                   pos, fp8)
    db = x.shape[0]
    live_tok = (jnp.arange(db) < n_dec)[:, None, None]
    k, v = jnp.where(live_tok, k, 0.0), jnp.where(live_tok, v, 0.0)
    zeros = jnp.zeros((db,) + kp.shape[1:], kp.dtype)
    kc = jax.lax.dynamic_update_slice_in_dim(
        jnp.concatenate([kp, zeros]), k, prompt_len, 0)
    vc = jax.lax.dynamic_update_slice_in_dim(
        jnp.concatenate([vp, zeros]), v, prompt_len, 0)
    kg, vm = _summaries(kc, vc, page, stride)
    kmean = jnp.repeat(kg.mean(2), grp, axis=1)              # (n, hq, d)
    vmq = jnp.repeat(vm, grp, axis=1)                        # (n, hq)
    n = kc.shape[0] // page
    head_kv = np.arange(hq) // grp

    def rows(args):
        qi, pi = args                                         # (hq,d), ()
        rp = pi // page
        j = jnp.arange(n)
        valid = j <= rp
        forced = ((j < rule.sink) | (j > rp - rule.local)) & valid
        nv = rp + 1
        budget = jnp.maximum(jnp.maximum(rule.min_budget,
                                         jnp.minimum(nv, rule.sink + rule.local)),
                             jnp.floor(nv * rule.budget_frac).astype(jnp.int32))
        score = jnp.einsum("hd,jhd->hj", qi, kmean, precision=HI) / np.sqrt(hd)
        score = score + rule.beta * jnp.maximum(vmq.T, 0.0)
        biased = jnp.where(forced, score + BIG, score)
        biased = jnp.where(valid, biased, NEG)
        order = jnp.argsort(-biased, axis=-1)
        rank = jnp.argsort(order, axis=-1)
        keep_page = (rank < budget) & valid                  # (hq, n)
        s = jnp.einsum("hd,thd->ht", qi, kc[:, head_kv], precision=HI) / np.sqrt(hd)
        t = jnp.arange(kc.shape[0])
        keep = keep_page[:, t // page] & (t <= pi)
        s = jnp.where(keep, s, NEG)
        p = jnp.where(keep, jax.nn.softmax(s, -1), 0.0)
        return jnp.einsum("ht,thd->hd", p, vc[:, head_kv], precision=HI)

    o = jax.lax.map(rows, (q, pos), batch_size=64)
    x = x + _linear(o.reshape(db, hq * hd), lw["wo"], fp8)
    return x + _mlp(cfg, lw, x, fp8)


LAYER_KEYS = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "bq", "bk",
              "bv", "q_norm", "k_norm", "w_gate", "w_up", "w_down")


def _head(cfg, w, x, fp8):
    h = _rms(x, w["final_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
    table = w["head"] if "head" in w else w["embed"].T
    return _linear(h, table.astype(jnp.float32), fp8)


@functools.partial(jax.jit, static_argnames=("cfg_items", "rule", "kmax", "fp8"))
def _logits(w, prompt, true_len, budgets, dec, n_dec, *, cfg_items, rule,
            kmax, fp8):
    cfg = dict(cfg_items)
    layers = {k: w[k] for k in LAYER_KEYS if k in w}
    pos = jnp.arange(prompt.shape[0])
    dpos = true_len + jnp.arange(dec.shape[0])

    def layer(carry, lw):
        x, xd = carry
        lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
        x, k, v = _prefill_layer(cfg, rule, kmax, lw, x, pos, true_len,
                                 budgets, fp8)
        xd = _decode_layer(cfg, rule, lw, xd, dpos, n_dec, k, v, true_len, fp8)
        return (x, xd), None

    emb = w["embed"]
    (x, xd), _ = jax.lax.scan(
        layer, (emb[prompt].astype(jnp.float32),
                emb[dec].astype(jnp.float32)), layers)
    last = jax.lax.dynamic_index_in_dim(x, true_len - 1, 0)
    return _head(cfg, w, jnp.concatenate([last, xd]), fp8)


def _bucket(n, step):
    return max(step, -(-n // step) * step)


def logits(cfg: dict, rule: StemRule, w: dict, prompt, served, *,
           kmax: int, prompt_bucket: int, fp8: bool = False) -> np.ndarray:
    """(len(served), vocab) next-token logits: row 0 at the last prompt
    position, row i at the position of ``served[i - 1]`` fed back."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    P, n = len(prompt), len(served)
    padded = -(-P // rule.page) * rule.page
    lb = _bucket(padded, prompt_bucket)
    tok = np.zeros((lb,), np.int32)
    tok[:P] = prompt
    budgets = np.ones((lb // rule.page,), np.int32)
    b = rule.prefill_budgets(padded)
    budgets[:len(b)] = b
    db = _bucket(max(n - 1, 1), DECODE_BUCKET)
    dec = np.zeros((db,), np.int32)
    dec[:n - 1] = served[:n - 1]
    out = _logits(w, jnp.asarray(tok), jnp.int32(P), jnp.asarray(budgets),
                  jnp.asarray(dec), jnp.int32(n - 1),
                  cfg_items=tuple(sorted((k, v) for k, v in cfg.items()
                                         if isinstance(v, (int, float, str)))),
                  rule=rule, kmax=kmax,
                  fp8=fp8)
    return np.asarray(out[:n])


def gaps(ref: np.ndarray, tokens) -> np.ndarray:
    """At each position, how far the chosen token's reference logit lies
    below the reference's best."""
    tokens = np.asarray(tokens)
    return ref.max(-1) - ref[np.arange(len(tokens)), tokens]

