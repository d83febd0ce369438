"""The half of every plain reference that does not depend on the model:
Stem attention over the kept pages of one served request, the layout of
its inputs, the float8 rounding of the control and seeded keys.

A family's reference (``families/<family>.py``) computes its own queries,
keys and values, in float32 at ``Precision.HIGHEST``, and calls
``prefill_attention`` over the padded prompt and ``decode_attention`` over
the fed-back tokens.  What these do follows Stem's serving rule
(``stem_rule.py``), not the program:

* prompt positions: the prompt is right-padded with token 0 to a page
  multiple.  Keys and values at and after the true length are zero (the
  page pool holds zeros there).  Each query block of a page keeps its own
  page and page 0, plus the best-scoring earlier pages up to its prefill
  budget; the score of a page is the anti-diagonal routing score of the
  block's pooled queries against the page's pooled keys plus
  ``beta * max(0, max log ||v||)``.  Attention is exact and token-causal
  over the kept pages.
* generated positions (the served tokens fed back): each query keeps
  page 0 and its own page plus the best pages by its own score against
  each full page's mean key, up to the decode budget.

Attention logits are scaled by ``1 / sqrt(d)``, ``d`` the query and key
head size; a family with another softmax scale scales its queries.  Keys
and values may have different head sizes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from stem_rule import StemRule

HI = jax.lax.Precision.HIGHEST
DECODE_BUCKET = 512    # fed-back positions are padded to a multiple of this
NEG = -1e30
BIG = 1e30


def jax_key(seed: int):
    """A JAX key from a seed of any size (PRNGKey keeps only 32 bits)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)[0]
    return jax.random.PRNGKey(int(word))


def fp8(x):
    """Round to float8 e4m3 with one scale for the tensor (its absolute
    maximum maps to e4m3's largest value, 448)."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _summaries(k, v, page, stride):
    """Per page: anti-diagonal group means of K (n, hk, stride, d) and the
    max log ||v|| (n, hk)."""
    s, hk, d = k.shape
    n = s // page
    kg = k.reshape(n, page // stride, stride, hk, d).mean(1)      # n,u,hk,d
    vn = jnp.log(jnp.maximum(jnp.linalg.norm(v, axis=-1), 1e-20))
    return jnp.swapaxes(kg, 1, 2), vn.reshape(n, page, hk).max(1)


def prefill_attention(rule: StemRule, kmax: int, q, k, v, pos, true_len,
                      budgets):
    """Stem attention of the padded prompt's queries ``q`` (s, hq, d) over
    its keys ``k`` (s, hk, d) and values ``v`` (s, hk, dv), each query
    block keeping ``budgets[block]`` pages.  Returns (out (s, hq, dv), k,
    v), with the keys and values at and after ``true_len`` zeroed."""
    hq, hk, hd, dv = q.shape[1], k.shape[1], k.shape[2], v.shape[2]
    grp, page, stride = hq // hk, rule.page, rule.stride
    live_tok = (pos < true_len)[:, None, None]
    k, v = jnp.where(live_tok, k, 0.0), jnp.where(live_tok, v, 0.0)
    n = q.shape[0] // page
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
    vb = v.reshape(n, page, hk, dv)
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
    return o.reshape(q.shape[0], hq, dv), k, v


def decode_attention(rule: StemRule, q, k, v, pos, n_dec, kp, vp,
                     prompt_len):
    """Stem attention of the fed-back tokens' queries ``q`` (db, hq, d) at
    ``pos`` (prompt_len + i), over the prompt's keys and values ``kp``/``vp``
    (from ``prefill_attention``) and their own ``k``/``v``; the first
    ``n_dec`` rows are real.  Returns (db, hq, dv)."""
    hq, hk, hd = q.shape[1], k.shape[1], k.shape[2]
    grp, page = hq // hk, rule.page
    db = q.shape[0]
    live_tok = (jnp.arange(db) < n_dec)[:, None, None]
    k, v = jnp.where(live_tok, k, 0.0), jnp.where(live_tok, v, 0.0)
    kc = jax.lax.dynamic_update_slice_in_dim(
        jnp.concatenate([kp, jnp.zeros((db,) + kp.shape[1:], kp.dtype)]), k,
        prompt_len, 0)
    vc = jax.lax.dynamic_update_slice_in_dim(
        jnp.concatenate([vp, jnp.zeros((db,) + vp.shape[1:], vp.dtype)]), v,
        prompt_len, 0)
    kg, vm = _summaries(kc, vc, page, rule.stride)
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

    return jax.lax.map(rows, (q, pos), batch_size=64)


def _bucket(n, step):
    return max(step, -(-n // step) * step)


def inputs(rule: StemRule, prompt, served, prompt_bucket: int):
    """The arrays a family's jitted reference takes for one request:
    (tokens, true_len, budgets, fed-back tokens, their count).  The prompt
    is padded to a page multiple and then to a multiple of
    ``prompt_bucket`` (each padding block keeps one page); the fed-back
    tokens, ``served`` but the last, to a multiple of ``DECODE_BUCKET``."""
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
    return (jnp.asarray(tok), jnp.int32(P), jnp.asarray(budgets),
            jnp.asarray(dec), jnp.int32(n - 1))


def gaps(ref: np.ndarray, tokens) -> np.ndarray:
    """At each position, how far the chosen token's reference logit lies
    below the reference's best."""
    tokens = np.asarray(tokens)
    return ref.max(-1) - ref[np.arange(len(tokens)), tokens]
