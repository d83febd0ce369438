"""MLA latent pages and dropless experts in the paged Stem engine
(DeepSeek-V2-Lite's path), against plain references at the preset's
``reduced()`` size on seeded random weights, float32 throughout.

* The unified step's chunked prefill and cached decode over latent pages
  match a plain full-forward reference that expands the latent into
  per-head keys and values and keeps, per query-block row and per decode
  token, the pages Stem's rule keeps with one page set for all heads.
* Absorbed decode attention equals expanded attention over the same kept
  pages.
* Dropless experts match a per-token oracle on a chunk whose load exceeds
  the training path's capacity, with the top-k weights renormalised only
  under ``norm_topk_prob``.
* YaRN rotary matches the published formula.
"""
import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import chunked as chunked_lib
from repro.core import decode as decode_lib
from repro.core import policy as policy_lib
from repro.core.selection import DecodeSelection
from repro.models import common, mla, moe, registry, transformer

HI = jax.lax.Precision.HIGHEST
BS = 8               # page = Stem block
CHUNK = 16           # chunk lane width (two pages)
CFG = configs.reduced(configs.get_config("deepseek-v2-lite")).replace(
    dtype="float32")
POL = policy_lib.get_policy("stem").with_updates(
    block_size=BS, stride=4, sink_blocks=1, local_blocks=1,
    min_budget_blocks=2)
BUDGET = 0.5
# Every product is float32 on both sides; they differ only in summation
# order (absorbed against expanded attention, sorted expert groups against
# one expert per token), a few ulps of logits of order 0.1-1.
LOGIT_TOL = 2e-4


@pytest.fixture(scope="module")
def params():
    return registry.build(CFG).init_params(jax.random.PRNGKey(3))


# -- the plain reference --------------------------------------------------

def _rms(x, w):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6) * (1 + w)


def _silu(x):
    return x / (1 + np.exp(-x))


def _softmax(x):
    e = np.exp(x - x.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def _yarn_inv_freq(dim, theta, y):
    """DeepseekV2YarnRotaryEmbedding's inv_freq, transcribed."""
    def corr(rot):
        return dim * math.log(y.original_max_position / (rot * 2 * math.pi)) / (
            2 * math.log(theta))
    lo = max(math.floor(corr(y.beta_fast)), 0)
    hi = min(math.ceil(corr(y.beta_slow)), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    base = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    mask = 1.0 - np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
    return (1.0 / (y.factor * base)) * (1 - mask) + (1.0 / base) * mask


def _published_rope(x, pos, theta, y):
    """De-interleave, then rotate_half by cos/sin (mscale ratio 1).  The
    angles are float32 products, as the published cos/sin cache makes them
    (``torch.outer`` of float32 positions and inverse frequencies)."""
    d = x.shape[-1]
    ang = (np.asarray(pos, np.float32)[:, None]
           * _yarn_inv_freq(d, theta, y).astype(np.float32)[None])
    ang = ang.astype(np.float64)                              # (s, d/2)
    cos = np.concatenate([np.cos(ang)] * 2, -1)[:, None]
    sin = np.concatenate([np.sin(ang)] * 2, -1)[:, None]
    x = np.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    rot = np.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _stem_keep(scores, causal, forced, budget):
    """Keep the forced pages, then the best-scoring causal ones, up to
    ``budget`` pages; scores (n,) already reduced over heads."""
    order = np.argsort(-np.where(forced, np.inf, np.where(causal, scores,
                                                           -np.inf)),
                       kind="stable")
    keep = np.zeros_like(causal)
    keep[order[:budget]] = True
    return keep & causal


def _summaries(k, c, stride):
    """Per page of zero-padded keys k (n*BS, h, dq) and latents c: pooled
    keys (n, h, u, dq) and max log ||c_kv||."""
    n = len(k) // BS
    kg = k.reshape(n, BS // stride, stride, *k.shape[1:]).mean(1)
    vn = np.log(np.maximum(np.linalg.norm(c, axis=-1), 1e-20))
    return np.swapaxes(kg, 1, 2), vn.reshape(n, BS).max(1)


def _attend(q, k, v, ok, dq):
    """Exact attention of one query (h, dq) over keys where ``ok``."""
    s = np.where(ok[None], np.einsum("hd,thd->ht", q, k) / math.sqrt(dq),
                 -np.inf)
    return np.einsum("ht,thd->hd", _softmax(s), v)


def _ffn(f, h):
    if "router" not in f:
        return (_silu(h @ f["w_gate"]) * (h @ f["w_up"])) @ f["w_down"]
    mc = CFG.moe
    g = _softmax(h @ f["router"])
    y = (_silu(h @ f["shared"]["w_gate"]) * (h @ f["shared"]["w_up"])) \
        @ f["shared"]["w_down"]
    for t in range(len(h)):
        top = np.argsort(-g[t], kind="stable")[:mc.top_k]
        w = g[t, top]
        w = w / w.sum() if mc.norm_topk_prob else w * mc.routed_scaling_factor
        for e, we in zip(top, w):
            y[t] += we * (_silu(h[t] @ f["w_gate"][e]) * (h[t] @ f["w_up"][e])
                          ) @ f["w_down"][e]
    return y


def _reference_logits(params, prompt, fed):
    """Logits at the last prompt position and at each fed-back token.  The
    prompt runs right-padded with token 0 to a page multiple, its latents
    zero at and after its length; fed-back token i sits at position P + i."""
    m = CFG.mla
    P, n_dec = len(prompt), len(fed)
    padded = -(-P // BS) * BS
    n_pages = padded // BS
    budgets = POL.prefill_budgets(padded)
    stride, beta = POL.stride, POL.metric.beta
    gain = mla.logit_gain(CFG)
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    toks = np.zeros(padded, np.int64)
    toks[:P] = prompt
    streams = [(p["embed"][toks], np.arange(padded)),
               (p["embed"][np.asarray(fed)], P + np.arange(n_dec))]
    layers = [(p["segment0"], 0), *[(p["segment1"], i) for i in
                                    range(CFG.num_layers - 1)]]
    for seg, i in layers:
        lp = jax.tree.map(lambda a, i=i: a[i], seg)["sub0"]
        a = lp["attn"]
        proj = []
        for x, pos in streams:
            h = _rms(x, lp["norm1"])
            q = np.einsum("sd,dhk->shk", h, a["wq"])
            qr = _published_rope(q[..., m.nope_head_dim:], pos, CFG.rope_theta,
                                 CFG.rope_scaling)
            c = _rms(h @ a["w_dkv"], a["kv_norm"])
            kr = _published_rope((h @ a["w_kr"])[:, None], pos, CFG.rope_theta,
                                 CFG.rope_scaling)[:, 0]
            proj.append((np.concatenate([q[..., :m.nope_head_dim], qr], -1)
                         * gain, c, kr))
        (qp_, cp, krp), (qd, cd, krd) = proj
        cp[P:], krp[P:] = 0.0, 0.0                   # padding holds zeros

        def expand(c, kr):
            kn = np.einsum("tr,rhk->thk", c, a["w_uk"])
            return (np.concatenate([kn, np.broadcast_to(
                        kr[:, None], kn.shape[:2] + kr.shape[-1:])], -1),
                    np.einsum("tr,rhk->thk", c, a["w_uv"]))

        kp, vp = expand(cp, krp)
        dq = kp.shape[-1]
        kg, vm = _summaries(kp, cp, stride)
        out_p = np.zeros((padded, CFG.num_heads, m.v_head_dim))
        j = np.arange(n_pages)
        for r in range(n_pages):
            rows = np.arange(r * BS, (r + 1) * BS)
            qpool = qp_[rows].reshape(BS // stride, stride, CFG.num_heads,
                                      dq).mean(0)              # (u, h, dq)
            pair = (stride - np.arange(stride)) % stride
            route = np.einsum("uhd,jhud->hj", qpool, kg[:, :, pair]) / (
                stride * math.sqrt(dq))
            score = route.mean(0) + beta * np.maximum(vm, 0)
            causal = j <= r
            forced = ((j < POL.sink_blocks) | (j > r - POL.local_blocks)) & causal
            keep = _stem_keep(score, causal, forced, int(budgets[r]))
            for t in rows:
                ok = (np.arange(padded) <= t) & np.repeat(keep, BS)
                out_p[t] = _attend(qp_[t], kp, vp, ok, dq)
        # The cache a decode token sees: the prompt, then the fed-back
        # tokens from position P, zeros after the token itself.
        n_all = -(-(P + n_dec) // BS) * BS
        c_all = np.zeros((n_all, cp.shape[-1]))
        kr_all = np.zeros((n_all, krp.shape[-1]))
        c_all[:P], kr_all[:P] = cp[:P], krp[:P]
        c_all[P:P + n_dec], kr_all[P:P + n_dec] = cd, krd
        out_d = np.zeros((n_dec, CFG.num_heads, m.v_head_dim))
        for d_i in range(n_dec):
            t = P + d_i
            n_valid = t // BS + 1
            live = np.arange(n_valid * BS) <= t
            kk, vv = expand(c_all[:n_valid * BS] * live[:, None],
                            kr_all[:n_valid * BS] * live[:, None])
            kg, vm = _summaries(kk, c_all[:n_valid * BS] * live[:, None], stride)
            score = np.einsum("hd,jhd->hj", qd[d_i], kg.mean(2)).mean(0) / (
                math.sqrt(dq)) + beta * np.maximum(vm, 0)
            jj = np.arange(n_valid)
            forced = (jj < POL.sink_blocks) | (jj > n_valid - 1 - POL.local_blocks)
            budget = max(2, min(n_valid, 2), int(n_valid * BUDGET))
            keep = _stem_keep(score, np.ones(n_valid, bool), forced, budget)
            out_d[d_i] = _attend(qd[d_i], kk, vv, live & np.repeat(keep, BS), dq)
        new = []
        for (x, pos), o in zip(streams, (out_p, out_d)):
            x = x + np.einsum("thk,hkd->td", o, a["wo"])
            new.append((x + _ffn(lp["ffn"], _rms(x, lp["norm2"])), pos))
        streams = new
    last = np.concatenate([streams[0][0][P - 1:P], streams[1][0]])
    return _rms(last, p["final_norm"]) @ p["head"]


# -- the program ----------------------------------------------------------

def _served_logits(params, prompt, fed):
    """Chunk lane through the prompt, then the decode lane over ``fed``,
    through ``transformer.paged_mixed_step`` (the engine's step)."""
    maxp = -(-(len(prompt) + len(fed)) // BS) + CHUNK // BS
    pools = transformer.init_page_pools(CFG, 1 + maxp, POL)
    row = np.arange(1, 1 + maxp, dtype=np.int32)
    P = len(prompt)
    padded = -(-P // BS) * BS
    toks = np.zeros((-(-padded // CHUNK) * CHUNK,), np.int32)
    toks[:P] = prompt
    k_bound = chunked_lib.chunk_budget_bound(POL, maxp)
    step = jax.jit(lambda pools, tok, table, lens, chunk:
                   transformer.paged_mixed_step(
                       params, tok, pools, table, lens, CFG, stem_cfg=POL,
                       budget_frac=BUDGET, chunk=chunk, chunk_k_max=k_bound))
    idle = (jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, maxp), jnp.int32),
            jnp.zeros((1,), jnp.int32))
    out = []
    for t0 in range(0, padded, CHUNK):
        cd = {"tokens": jnp.asarray(toks[t0:t0 + CHUNK])[None],
              "page_table": jnp.asarray(row)[None],
              "start": jnp.asarray([t0], jnp.int32),
              "true_len": jnp.asarray([P], jnp.int32),
              "budgets": jnp.asarray(chunked_lib.chunk_budget_rows(
                  POL, padded, t0, CHUNK // BS))[None],
              "last": jnp.asarray([min(max(P - 1 - t0, 0), CHUNK - 1)],
                                  jnp.int32)}
        _, logits, pools, counts = step(pools, *idle, cd)
    out.append(np.asarray(logits)[0])
    for i, tok in enumerate(fed):
        logits, _, pools, counts = step(
            pools, jnp.asarray([[tok]], jnp.int32), jnp.asarray(row)[None],
            jnp.asarray([P + i], jnp.int32), None)
        out.append(np.asarray(logits)[0])
    return np.stack(out), np.asarray(counts)


@pytest.mark.parametrize("plen", [45, 70])
def test_chunked_prefill_and_decode_match_reference(params, plen):
    rng = np.random.default_rng(plen)
    prompt = rng.integers(0, CFG.vocab_size, plen)
    fed = rng.integers(0, CFG.vocab_size, 12)
    got, counts = _served_logits(params, prompt, fed)
    want = _reference_logits(params, prompt, fed)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=LOGIT_TOL * np.abs(want).max())
    # One decode token through 2 MoE layers: K experts hit in each, one
    # pair on the busiest expert.
    assert counts.tolist() == [2 * CFG.moe.top_k, 2]


def test_absorbed_decode_equals_expanded():
    """``attend_latent`` then ``W_UV`` against per-head expanded keys and
    values over the same kept pages (float32; summation order only)."""
    rng = np.random.default_rng(0)
    b, h, r, dr, dn, dv, kmax = 2, 4, 16, 8, 16, 16, 3
    pages = rng.normal(size=(b, kmax, BS, r + dr)).astype(np.float32)
    w_uk = rng.normal(size=(r, h, dn)).astype(np.float32) / 4
    w_uv = rng.normal(size=(r, h, dv)).astype(np.float32) / 4
    q_nope = rng.normal(size=(b, h, dn)).astype(np.float32)
    q_rope = rng.normal(size=(b, h, dr)).astype(np.float32)
    scale = (dn + dr) ** -0.5
    qt = np.concatenate([np.einsum("bhk,rhk->bhr", q_nope, w_uk), q_rope], -1)
    qt = qt * scale * math.sqrt(r + dr)
    idx = np.asarray([[[[0, 2, 1]]], [[[1, 0, 0]]]], np.int32)
    live = np.asarray([[[[True, True, False]]], [[[True, True, False]]]])
    lens = np.asarray([3 * BS, 2 * BS - 3], np.int32)
    sel = DecodeSelection(indices=jnp.asarray(idx), live=jnp.asarray(live),
                          budgets=None, n_valid=None)
    o = decode_lib.attend_latent(jnp.asarray(qt[:, :, None]),
                                 jnp.asarray(pages), sel, jnp.asarray(lens),
                                 BS, r)
    got = np.einsum("bhr,rhk->bhk", np.asarray(o)[:, :, 0], w_uv)
    for i in range(b):
        c, kr = pages[i, ..., :r], pages[i, ..., r:]          # (kmax, bs, .)
        k = np.concatenate([np.einsum("nkr,rhd->hnkd", c, w_uk),
                            np.broadcast_to(kr, (h,) + kr.shape)], -1)
        v = np.einsum("nkr,rhd->hnkd", c, w_uv)
        s = np.einsum("hd,hnkd->hnk", np.concatenate([q_nope[i], q_rope[i]], -1),
                      k) * scale
        pos = idx[i, 0, 0][:, None] * BS + np.arange(BS)
        ok = (pos < lens[i]) & live[i, 0, 0][:, None]
        s = np.where(ok, s, -np.inf)
        w = np.exp(s - s.max((1, 2), keepdims=True))
        w = w / w.sum((1, 2), keepdims=True)
        np.testing.assert_allclose(got[i], np.einsum("hnk,hnkd->hd", w, v),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("norm", [False, True])
def test_dropless_moe_matches_per_token_oracle(norm):
    """A 64-token chunk routed almost all to two experts: the training
    path's capacity (1.25 * s * K / E = 20 slots an expert) drops pairs,
    the serving path drops none."""
    mc = dataclasses.replace(CFG.moe, norm_topk_prob=norm,
                             routed_scaling_factor=1.0 if norm else 2.5)
    d = CFG.d_model
    p = moe.init(common.Initializer(jax.random.PRNGKey(1), jnp.float32), d,
                 mc, "silu")
    p = common.unzip(p)[0]
    bias = np.zeros((d, mc.num_experts), np.float32)
    bias[0, :2] = 40.0                                   # feature 0 -> e0, e1
    p["router"] = p["router"] + bias
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 64, d), jnp.float32)
    x = x.at[..., 0].set(1.0)
    y, load = moe.apply_dropless(p, x, mc, "silu")
    assert int(load.max()) == 64 > int(1.25 * 64 * mc.top_k / mc.num_experts)
    y_capped, _ = moe.apply(p, x, mc, "silu")
    want = np.zeros((64, d))
    xt = np.asarray(x[0], np.float64)
    pp = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    for t in range(64):
        g = np.asarray(jax.nn.softmax(xt[t] @ pp["router"]))
        top = np.argsort(-g, kind="stable")[:mc.top_k]
        w = g[top] / g[top].sum() if norm else g[top] * 2.5
        for e, we in zip(top, w):
            want[t] += we * (np.asarray(jax.nn.silu(xt[t] @ pp["w_gate"][e]))
                             * (xt[t] @ pp["w_up"][e])) @ pp["w_down"][e]
    sh = pp["shared"]
    want += (np.asarray(jax.nn.silu(xt @ sh["w_gate"])) * (xt @ sh["w_up"])) @ sh["w_down"]
    np.testing.assert_allclose(np.asarray(y[0]), want, rtol=1e-4, atol=1e-4)
    assert not np.allclose(np.asarray(y_capped[0]), want, rtol=1e-3, atol=1e-3)
    assert np.asarray(load).tolist()[:2] == [64, 64]


def test_yarn_matches_published_formula():
    y = configs.get_config("deepseek-v2-lite").rope_scaling
    dim, theta = 64, 1e4
    np.testing.assert_allclose(np.asarray(common.rope_freqs(dim, theta, y)),
                               _yarn_inv_freq(dim, theta, y), rtol=1e-6)
    # The published de-interleaved rotation and the program's adjacent-pair
    # one differ by one permutation of q and k alike: equal dot products.
    # Both sides' float32 inverse frequencies differ by an ulp in some
    # entries, which moves an angle by ~position * 1e-8 rad: positions stay
    # where that is below the tolerance.
    rng = np.random.default_rng(0)
    pos = np.asarray([0, 1, 100, 4095, 4096, 8191], np.float64)
    q = rng.normal(size=(len(pos), 1, dim))
    k = rng.normal(size=(len(pos), 1, dim))
    pub = np.einsum("shd,thd->st", _published_rope(q, pos, theta, y),
                    _published_rope(k, pos, theta, y))
    prog = np.einsum("hsd,htd->st",
                     np.asarray(common.apply_rope(jnp.asarray(q[:, 0][None], jnp.float32),
                                                  jnp.asarray(pos), theta, y)),
                     np.asarray(common.apply_rope(jnp.asarray(k[:, 0][None], jnp.float32),
                                                  jnp.asarray(pos), theta, y)))
    np.testing.assert_allclose(prog, pub, rtol=1e-4, atol=1e-3)
    assert common.rope_mscale(y) == 1.0
    # softmax scale (128 + 64)^-0.5 * (0.1 * 0.707 * ln 40 + 1)^2
    scale = 192 ** -0.5 * mla.logit_gain(configs.get_config("deepseek-v2-lite"))
    assert abs(scale - 0.11472) < 1e-4


def test_latent_pools_refuse_tensor_parallel_sharding():
    """A latent pool has one latent head per token, so there is no head
    axis to split over tp: ``shard_pools`` says so before placing any."""
    from repro.sharding import serving

    pools = transformer.init_page_pools(CFG, 5, POL)
    with pytest.raises(NotImplementedError, match="latent"):
        serving.shard_pools(pools, serving.ServingMesh(dp=1, tp=2, mesh=None))


def test_serve_cli_reaches_the_engine():
    """``launch/serve.py --arch deepseek-v2-lite`` serves through the
    chunked engine at the reduced size."""
    from repro.launch import serve

    out = serve.main(["--arch", "deepseek-v2-lite", "--reduced", "--policy",
                      "stem", "--requests", "2", "--decode-tokens", "3",
                      "--max-slots", "2"])
    assert out["mode"] == "engine" and out["prefill"] == "chunked"
    assert out["requests"] == 2 and not out["failed"]
    assert all(len(t) == 3 for t in out["tokens"].values())
    assert out["engine_stats"]["moe_experts_hit"] > 0
