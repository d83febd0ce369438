"""Serving driver: continuous batching over the paged Stem KV cache.

Models the paper's deployment story end-to-end: Stem-accelerated prefill
writes each request's K/V pages + block summaries into the shared page
pool, and decode streams tokens with OAM page selection per step.  Requests
carry *mixed prompt lengths* and *staggered arrivals*; the engine
(``runtime/engine.py``) admits them into slots as capacity frees up and
recycles slots on completion — no uniform-batch assumption anywhere.

Three modes:
  * default — the continuous-batching engine with **chunked prefill**: one
    fixed-shape unified step mixes prefill chunks (``--chunk-size``) and
    decode tokens per iteration under a ``--step-token-budget``, so long
    prompts never stall in-flight decodes and the engine compiles once;
  * ``--monolithic`` — the legacy one-shot admission prefill (per-length
    traces, head-of-line blocking) kept as the A/B baseline;
  * ``--fixed-batch`` — the legacy one-shot batch, but ragged: per-request
    prompt lengths are right-padded, per-sequence ``cache_lens`` flow
    through ``make_serve_step``, and every row decodes at its own length.

The sparsity policy is declarative: ``--policy <name>`` resolves any
registered ``SparsityPolicy`` (``stem``, ``streaming``, ``uniform-sam``,
``xattention``, …; see ``core/policy.py``) and rescales it to the serving
geometry; ``--stem`` keeps the legacy flag-built stem policy.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \\
      --requests 6 --min-prompt 48 --max-prompt 200 --decode-tokens 16 \\
      --max-slots 4 --stem
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \\
      --policy streaming --requests 6 --decode-tokens 16
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def build_trace(rng: np.random.RandomState, n_requests: int, min_prompt: int,
                max_prompt: int, decode_tokens: int, vocab: int,
                arrival_every: int, hp_every: int = 0,
                hp_ttft_slo_s: float = None, hp_tpot_slo_s: float = None):
    """Mixed-length, staggered-arrival request trace.  With ``hp_every``,
    every hp_every-th request is priority 1 and carries the given SLOs —
    the interactive class of the overload study."""
    from repro.runtime.engine import Request
    reqs = []
    for i in range(n_requests):
        plen = int(rng.randint(min_prompt, max_prompt + 1))
        hp = bool(hp_every) and (i % hp_every == hp_every - 1)
        reqs.append(Request(
            uid=i,
            prompt=rng.randint(0, vocab, size=(plen,)).astype(np.int32),
            max_new_tokens=decode_tokens,
            arrival_step=i * arrival_every,
            priority=1 if hp else 0,
            ttft_slo_s=hp_ttft_slo_s if hp else None,
            tpot_slo_s=hp_tpot_slo_s if hp else None,
        ))
    return reqs


def _latency_stats(finished):
    """Serving-latency summary: inter-token decode gaps (p50/p95/p99 —
    these surface head-of-line stalls and swapped-out time), TTFT, and
    TPOT, reported separately.  NaN entries (shed/aborted requests never
    emitted a token; single-token requests have no TPOT) are excluded."""
    lats = np.asarray([t for f in finished for t in f.token_latencies_s])
    ttfts = np.asarray([f.ttft_s for f in finished], np.float64)
    ttfts = ttfts[~np.isnan(ttfts)] if ttfts.size else ttfts
    tpots = np.asarray([f.tpot_s for f in finished], np.float64)
    tpots = tpots[~np.isnan(tpots)] if tpots.size else tpots
    out = {"p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0, "ttft_ms_mean": 0.0,
           "ttft_ms_p95": 0.0, "tpot_ms_mean": 0.0}
    if lats.size:
        out["p50_ms"] = float(np.percentile(lats, 50) * 1e3)
        out["p95_ms"] = float(np.percentile(lats, 95) * 1e3)
        out["p99_ms"] = float(np.percentile(lats, 99) * 1e3)
    if ttfts.size:
        out["ttft_ms_mean"] = float(np.mean(ttfts) * 1e3)
        out["ttft_ms_p95"] = float(np.percentile(ttfts, 95) * 1e3)
    if tpots.size:
        out["tpot_ms_mean"] = float(np.mean(tpots) * 1e3)
    return out


def serving_policy(name: str, block_size: int):
    """The named registered policy, rescaled to the serving geometry
    (registered defaults carry paper geometry: B=128 over 8k+ contexts).
    ignore_missing: content-free policies (streaming) have no
    stride/min_budget fields to rewrite."""
    from repro.core import policy as policy_lib
    return policy_lib.get_policy(name).with_updates(
        block_size=block_size, stride=4, sink_blocks=1, local_blocks=1,
        min_budget_blocks=2, ignore_missing=True)


def run_engine(args, cfg, bundle, params, stem_cfg, budget_frac):
    import jax.numpy as jnp  # noqa: F401  (keeps jax initialized up front)
    from repro.runtime.engine import EngineConfig, StemEngine

    mesh = None
    if args.mesh:
        try:
            dp, tp = (int(x) for x in args.mesh.split(","))
        except ValueError:
            raise SystemExit(f"--mesh wants 'dp,tp' (got {args.mesh!r})")
        mesh = (dp, tp)
    ecfg = EngineConfig.for_trace(
        max_slots=args.max_slots, max_prompt=args.max_prompt,
        max_new_tokens=args.decode_tokens, page_size=stem_cfg.block_size,
        budget_frac=budget_frac,
        chunk_size=args.chunk_size or None,
        step_token_budget=args.step_token_budget or None,
        monolithic_prefill=args.monolithic,
        prefix_cache=args.prefix_cache,
        prefix_evict=args.prefix_evict,
        scheduler=args.scheduler,
        max_waiting=args.max_waiting or None,
        executor=args.executor or None,
        mesh=mesh,
        admission_control=args.admission_control,
        async_depth=args.async_depth,
        sampler=args.sampler)
    chaos = None
    if args.chaos:
        from repro.runtime.chaos import ChaosConfig, ChaosInjector
        chaos = ChaosInjector(ChaosConfig(deny_alloc_steps=(2,),
                                          fail_steps=(4,),
                                          fail_restore_steps=(7,)))
    engine = StemEngine(bundle, params, stem_cfg, ecfg, chaos=chaos)
    rng = np.random.RandomState(args.seed + 1)
    trace = build_trace(rng, args.requests, args.min_prompt, args.max_prompt,
                        args.decode_tokens, cfg.vocab_size, args.arrival_every,
                        hp_every=args.hp_every,
                        hp_ttft_slo_s=args.hp_ttft_slo_ms * 1e-3,
                        hp_tpot_slo_s=args.hp_tpot_slo_ms * 1e-3)
    t0 = time.perf_counter()
    finished = engine.run(trace)
    wall = time.perf_counter() - t0
    ok = [f for f in finished if f.error is None]
    failed = [f for f in finished if f.error is not None]
    stats = _latency_stats(ok)
    total_tokens = sum(len(f.tokens) for f in finished)
    metrics = engine.metrics
    out = {
        "mode": "engine",
        "prefill": "monolithic" if args.monolithic else "chunked",
        "loop": "async" if ecfg.async_depth else "sync",
        "scheduler": ecfg.scheduler,
        "mesh": list(mesh) if mesh else None,
        "chunk_size": engine.chunk_size,
        "step_token_budget": engine.token_budget,
        "requests": len(finished),
        "failed": {f.uid: f.error for f in failed},
        "total_tokens": total_tokens,
        "wall_s": wall,
        "throughput_tok_s": total_tokens / max(wall, 1e-9),
        "engine_stats": dict(engine.stats),
        "engine_metrics": {
            "step_time_ema_s": metrics["step_time_ema_s"],
            "straggler_steps": metrics["straggler_steps"],
            "offload_peak_bytes": metrics["offload_peak_bytes"],
            "chaos": metrics["chaos"],
        },
        "tokens": {f.uid: f.tokens for f in finished},
        "engine": engine,
        **stats,
    }
    print(f"engine ({out['prefill']}, {out['loop']}, {ecfg.scheduler}): "
          f"{len(finished)} "
          f"reqs ({len(failed)} failed), {total_tokens} "
          f"tokens in {wall*1e3:.0f} ms -> {out['throughput_tok_s']:.1f} "
          f"tok/s; TTFT {out['ttft_ms_mean']:.1f} ms; TPOT "
          f"{out['tpot_ms_mean']:.2f} ms; inter-token p50 "
          f"{out['p50_ms']:.2f} / p95 {out['p95_ms']:.2f} ms; "
          f"traces {engine.stats['traces']}"
          f"+{engine.stats['prefill_traces']} prefill; "
          f"slots reused {engine.stats['slots_reused']}, "
          f"max concurrency {engine.stats['max_concurrency']}", flush=True)
    s = engine.stats
    if ecfg.async_depth:
        print(f"  async: depth {ecfg.async_depth}, blocking host syncs "
              f"{s['host_syncs']} over {s['id_fetches']} id fetches, "
              f"lookahead discards {s['lookahead_discards']}", flush=True)
    if s["pallas_fallbacks"]:
        print(f"  pallas: {s['pallas_fallbacks']} call site(s) fell back to "
              f"the XLA oracle", flush=True)
    if any(s[k] for k in ("preemptions", "shed", "aborts", "step_failures",
                          "restore_failures", "straggler_steps")):
        print(f"  resilience: preemptions {s['preemptions']} "
              f"(restores {s['restores']}), shed {s['shed']}, aborts "
              f"{s['aborts']}, step failures {s['step_failures']}, restore "
              f"failures {s['restore_failures']}; offload peak "
              f"{metrics['offload_peak_bytes']} B", flush=True)
    if args.prefix_cache:
        print(f"  prefix cache: hits {s['prefix_hits']}, pages shared "
              f"{s['prefix_pages_shared']}, cows {s['prefix_cows']}; "
              f"allocator shares {engine.allocator.shares}, cached pages "
              f"{engine.allocator.cached_pages}, total alloced "
              f"{engine.allocator.total_alloced}", flush=True)
    if metrics["straggler_steps"]:
        worst = max(metrics["straggler_steps"], key=lambda f: f[1])
        print(f"  stragglers: {len(metrics['straggler_steps'])} flagged "
              f"steps (EMA {metrics['step_time_ema_s']*1e3:.2f} ms; worst "
              f"step {worst[0]} at {worst[1]*1e3:.1f} ms vs EMA "
              f"{worst[2]*1e3:.2f} ms)", flush=True)
    return out


def run_fixed_batch(args, cfg, bundle, params, stem_cfg, budget_frac=1.0):
    """Legacy one-shot batch, ragged: pad per request, per-row cache_lens.
    With ``stem_cfg`` both prefill AND decode run policy-sparse (decode
    re-summarizes the contiguous cache per step — the differential
    reference arm for the paged engine)."""
    import jax
    import jax.numpy as jnp
    from repro.core import policy as policy_lib
    from repro.launch import steps as steps_lib
    from repro.models import transformer
    from repro.runtime import sampling as sampling_lib

    # Right-padded ragged prompts are only sound for global-attention
    # mixers: per-row masking hides padding K/V, and decode overwrites it.
    # Recurrent/SSM states absorb padding tokens irreversibly, and ring
    # caches treat padding slots as valid in-window keys.
    kinds = {k for _, ks in transformer.layer_program(cfg) for k in ks}
    unsafe = kinds - {"dense", "moe", "mla_dense", "mla_moe"}
    if unsafe:
        raise NotImplementedError(
            f"--fixed-batch ragged prompts unsupported for sub-layers "
            f"{sorted(unsafe)} ({cfg.name}): padding would contaminate "
            "recurrent/ring state")

    rng = np.random.RandomState(args.seed + 1)
    lens = rng.randint(args.min_prompt, args.max_prompt + 1,
                       size=(args.requests,)).astype(np.int32)
    max_prompt = int(lens.max())
    max_len = max_prompt + args.decode_tokens
    if stem_cfg is not None:
        # Sparse decode re-summarizes the contiguous cache, which needs the
        # cache length to be a whole number of blocks.
        bs = policy_lib.as_policy(stem_cfg).block_size
        max_len = -(-max_len // bs) * bs
    toks = np.zeros((args.requests, max_prompt), np.int32)
    for i, L in enumerate(lens):
        toks[i, :L] = rng.randint(0, cfg.vocab_size, size=(int(L),))

    # Same on-device sampling op as the engine (runtime/sampling.py) —
    # the sampled ids stay on device between steps and only the int32
    # ids are pulled to host, never the (b, vocab) logits.
    sampler = sampling_lib.get_sampler(getattr(args, "sampler", "greedy"))
    prefill = jax.jit(lambda p, b, lp: bundle.prefill(
        p, b, max_len=max_len, stem_cfg=stem_cfg, last_pos=lp))
    serve = jax.jit(
        steps_lib.make_serve_step(bundle, stem_cfg=stem_cfg,
                                  budget_frac=budget_frac),
        donate_argnums=(2,), static_argnames=())
    sample = jax.jit(lambda lg: sampler(lg)[:, None])

    t0 = time.perf_counter()
    batch = {"tokens": jnp.asarray(toks)}
    logits, caches = jax.block_until_ready(
        prefill(params, batch, jnp.asarray(lens - 1)))
    ttft = time.perf_counter() - t0
    toks_step = sample(logits)
    out_tokens = [np.asarray(toks_step)]
    t1 = time.perf_counter()
    cache_lens = jnp.asarray(lens)
    for i in range(args.decode_tokens - 1):
        logits, caches = serve(params, toks_step, caches,
                               cache_lens if i == 0 else None)
        toks_step = sample(logits)
        out_tokens.append(np.asarray(toks_step))
    jax.block_until_ready(toks_step)
    dt = time.perf_counter() - t1
    per_tok = dt / max(args.decode_tokens - 1, 1)
    gen = np.concatenate(out_tokens, axis=1)
    print(f"fixed-batch (ragged lens {lens.tolist()}): TTFT {ttft*1e3:.1f} ms, "
          f"decode {per_tok*1e3:.2f} ms/token ({args.requests} seqs)", flush=True)
    return {"mode": "fixed-batch", "ttft_s": ttft, "ms_per_token": per_tok * 1e3,
            "prompt_lens": lens.tolist(),
            "tokens": {i: gen[i].tolist() for i in range(args.requests)}}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--min-prompt", type=int, default=48)
    ap.add_argument("--max-prompt", type=int, default=200)
    ap.add_argument("--decode-tokens", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--arrival-every", type=int, default=2,
                    help="request i arrives at engine step i * this")
    ap.add_argument("--stem", action="store_true",
                    help="sparse decode budget (< 1.0); off = dense-equivalent")
    ap.add_argument("--policy", default=None,
                    help="named SparsityPolicy from the registry "
                         "(core/policy.py: stem, stem-sam, uniform-sam, "
                         "streaming, xattention, ...); default builds the "
                         "stem policy from StemConfig flags.  Implies the "
                         "sparse arm unless --budget-frac overrides it")
    ap.add_argument("--budget-frac", type=float, default=0.5)
    ap.add_argument("--block-size", type=int, default=0,
                    help="Stem block/page size; 0 = auto from max prompt")
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="prefill chunk width in tokens (multiple of the "
                         "page size); 0 = auto (2 pages)")
    ap.add_argument("--step-token-budget", type=int, default=0,
                    help="max tokens one engine step spends (decode tokens "
                         "first, then prefill chunks); 0 = auto "
                         "(max_slots + chunk)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="hash-keyed prefix-page sharing with copy-on-write: "
                         "admission maps matched whole prompt pages "
                         "read-only and prefills only the unmatched suffix "
                         "(chunked prefill only)")
    ap.add_argument("--monolithic", action="store_true",
                    help="legacy one-shot admission prefill (per-length "
                         "traces, head-of-line blocking) — the chunked A/B "
                         "baseline")
    ap.add_argument("--scheduler", choices=("slo", "fcfs"), default="slo",
                    help="token-budget scheduling order: 'slo' = priority + "
                         "SLO headroom (preemption-capable), 'fcfs' = "
                         "admission order (the PR 5 baseline)")
    ap.add_argument("--max-waiting", type=int, default=0,
                    help="waiting-queue bound; overflow sheds the lowest-"
                         "priority pending request (0 = unbounded)")
    ap.add_argument("--hp-every", type=int, default=0,
                    help="every Nth request is priority 1 with the --hp-* "
                         "SLOs (0 = uniform priority)")
    ap.add_argument("--hp-ttft-slo-ms", type=float, default=500.0,
                    help="TTFT SLO for the high-priority class")
    ap.add_argument("--hp-tpot-slo-ms", type=float, default=50.0,
                    help="TPOT SLO for the high-priority class")
    ap.add_argument("--mesh", default="",
                    help="'dp,tp' device mesh: dp-way data-parallel slot "
                         "groups x tp-way tensor-parallel KV-head sharding "
                         "of the page pools (needs dp*tp visible devices; "
                         "empty = single-device)")
    ap.add_argument("--executor", default="",
                    help="paged executor to force ('xla' | 'pallas'); empty "
                         "= policy default")
    ap.add_argument("--prefix-evict", choices=("lru", "hit-rate"),
                    default="lru",
                    help="prefix-cache eviction: 'lru' (default) or "
                         "'hit-rate' (evict fewest-shares-first, LRU ties)")
    ap.add_argument("--admission-control", action="store_true",
                    help="reject waiting requests whose TTFT SLO is "
                         "infeasible at the measured step time (explicit "
                         "error instead of a silent SLO miss)")
    ap.add_argument("--async-depth", type=int, default=0,
                    help="0 = synchronous engine loop (the differential "
                         "oracle); 1 = async pipeline: on-device sampling, "
                         "token-id-only transfers, one-step-lookahead "
                         "dispatch (bit-identical streams)")
    ap.add_argument("--sampler", default="greedy",
                    help="registered on-device sampler "
                         "(runtime/sampling.py); greedy = argmax")
    ap.add_argument("--chaos", action="store_true",
                    help="inject a fixed fault plan (alloc denial, step "
                         "failure, restore failure) — resilience demo; the "
                         "run must still complete every request")
    ap.add_argument("--fixed-batch", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro import backend, configs
    from repro.core.config import StemConfig
    from repro.models import registry
    import jax

    backend.setup_compile_cache()
    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg).replace(dtype="float32")
    if cfg.family == "encdec" or cfg.vlm_stub:
        raise NotImplementedError(
            f"serve drives token-only decoder prompts; {cfg.name} needs "
            "encoder frames / patch embeddings (use launch/eval paths)")
    bundle = registry.build(cfg)
    params = bundle.init_params(jax.random.PRNGKey(args.seed))

    bs = args.block_size or max(16, min(128, args.max_prompt // 8))
    bs = -(-bs // 8) * 8
    if args.policy:
        stem_cfg = serving_policy(args.policy, bs)
        sparse = True
    else:
        stem_cfg = StemConfig(block_size=bs, min_budget_blocks=2, sink_blocks=1,
                              local_blocks=1, stride=4)
        sparse = args.stem
    budget_frac = args.budget_frac if sparse else 1.0
    name = args.policy or "stem"
    print(f"serve: arch={cfg.name} page/block={bs} policy={name} "
          f"sparse={'on' if sparse else 'off'} budget_frac={budget_frac}",
          flush=True)

    if args.fixed_batch:
        return run_fixed_batch(args, cfg, bundle, params,
                               stem_cfg if sparse else None, budget_frac)
    return run_engine(args, cfg, bundle, params, stem_cfg, budget_frac)


if __name__ == "__main__":
    main()
