"""Smoke test of the serving main path on a TPU.

Drives ``repro.launch.serve.main`` -- the paged Stem engine with chunked
prefill -- at the full published width of qwen3-0.6b (28 layers, d_model
1024, 16 query / 8 KV heads, head_dim 128, bf16, random weights from a
seed), with 8 requests of 512-2048 prompt tokens and 32 decode tokens on
4 slots.  Block/page size 128.

  python chip_smoke.py              one chip: phases (a) xla executor,
                                    (b) pallas executor, (c) pallas with the
                                    async loop, then a kernel differential
  python chip_smoke.py --chips 4    four chips: the same trace on a
                                    --mesh 2,2 serving mesh against one
                                    device, streams bit-identical (fp32,
                                    depth cut to 4 layers)

Every check that fails exits non-zero.  The last line of standard output
is one JSON object, ``{"ok": true, "device": {...}}``, printed only when
every check passed.  Refuses to run anywhere but on a TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

SERVE = ["--arch", "qwen3-0.6b", "--policy", "stem", "--budget-frac", "0.5",
         "--requests", "8", "--min-prompt", "512", "--max-prompt", "2048",
         "--decode-tokens", "32", "--max-slots", "4", "--seed", "0"]
N_REQUESTS, DECODE_TOKENS = 8, 32
MAX_TRACES = 2            # the mixed and the decode-only step signatures
FOUR_CHIP_LAYERS = 4

# Fused-kernel differential at real widths, fp32 under "highest" matmul
# precision on both sides: the same bound as the CPU differential in
# tests/test_paged_kernel.py.
KERNEL_TOL = 1e-4

_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "compile_s",
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
}


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds JAX spends compiling (XLA/Mosaic compile or persistent-cache
    load), tracing and lowering, plus persistent-cache hits, summed from
    JAX's monitoring events."""

    def __init__(self, jax):
        self.totals = {k: 0.0 for k in _COMPILE_EVENTS.values()}
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        key = _COMPILE_EVENTS.get(event)
        if key:
            self.totals[key] += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return dict(self.totals, cache_hits=self.cache_hits)


def serve(clock, extra):
    """One ``serve.main`` run; returns (result, seconds spent per kind)."""
    from repro.launch import serve as serve_lib
    before = clock.snapshot()
    t0 = time.perf_counter()
    out = serve_lib.main(SERVE + extra)
    wall = time.perf_counter() - t0
    after = clock.snapshot()
    spent = {k: after[k] - before[k] for k in after}
    spent["wall_s"] = wall
    spent["run_s"] = wall - spent["compile_s"] - spent["trace_s"] \
        - spent["lower_s"]
    return out, spent


def check_run(name, out):
    check(not out["failed"], f"{name}: requests failed: {out['failed']}")
    toks = out["tokens"]
    check(len(toks) == N_REQUESTS,
          f"{name}: {len(toks)} of {N_REQUESTS} requests finished")
    short = {u: len(t) for u, t in toks.items() if len(t) != DECODE_TOKENS}
    check(not short, f"{name}: wrong token counts {short}")
    traces = out["engine_stats"]["traces"]
    check(traces <= MAX_TRACES,
          f"{name}: {traces} step traces > {MAX_TRACES} (retrace)")


def step_hlo(jax, engine):
    """Compiled HLO text of the engine's unified step.  The step's argument
    shapes are recorded from one more short request through the warm engine
    (which must add no trace), then the step is lowered and compiled from
    them; the persistent compile cache serves that compile."""
    import numpy as np

    from repro.runtime.engine import Request
    step = engine._unified
    seen = []

    def record(*args):
        if not seen:
            seen.append(jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), args))
        return step(*args)

    prompt = np.random.RandomState(7).randint(
        0, engine.cfg.vocab_size, size=(300,)).astype(np.int32)
    engine._unified = record
    try:
        done = engine.run([Request(uid=10_000, prompt=prompt,
                                   max_new_tokens=2)])
    finally:
        engine._unified = step
    check(done[-1].error is None and len(done[-1].tokens) == 2,
          "probe request through the warm engine failed")
    return step.lower(*seen[0]).compile().as_text()


def phase(jax, clock, name, extra, pallas):
    out, spent = serve(clock, extra)
    check_run(name, out)
    stats = out["engine_stats"]
    line = (f"phase {name}: requests {len(out['tokens'])}, tokens "
            f"{out['total_tokens']}, traces {stats['traces']}")
    if pallas:
        check(stats["pallas_fallbacks"] == 0,
              f"{name}: {stats['pallas_fallbacks']} pallas fallbacks")
        calls = step_hlo(jax, out["engine"]).count("tpu_custom_call")
        check(calls > 0, f"{name}: no tpu_custom_call in the compiled step")
        check(out["engine"].stats["traces"] <= MAX_TRACES,
              f"{name}: the warm engine retraced")
        line += (f", pallas_fallbacks {stats['pallas_fallbacks']}, "
                 f"tpu_custom_call in step HLO {calls}")
    print(f"{line}; compile_s {spent['compile_s']:.3f} (trace_s "
          f"{spent['trace_s']:.3f}, lower_s {spent['lower_s']:.3f}, "
          f"persistent-cache hits {spent['cache_hits']}), run_s "
          f"{spent['run_s']:.3f}, wall_s {spent['wall_s']:.3f}, "
          f"throughput {out['throughput_tok_s']:.1f} tok/s (engine.run "
          f"incl. compile)", flush=True)
    return out["tokens"]


def matching_tokens(a, b):
    """Leading tokens on which two streams agree, summed over requests."""
    n = 0
    for uid, ta in a.items():
        for x, y in zip(ta, b[uid]):
            if x != y:
                break
            n += 1
    return n


def kernel_differential(jax, seed=0):
    """fused_paged_decode / fused_paged_chunk against the XLA gather
    oracle at qwen3-0.6b attention widths, fp32, "highest" precision."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import chunked as chunked_lib
    from repro.kernels import paged_attn  # noqa: F401  (registers "pallas")
    from repro.launch.serve import serving_policy
    from repro.runtime import paged as paged_lib

    hq, hk, d, bs, maxp = 16, 8, 128, 128, 17
    pol = serving_policy("stem", bs)
    rng = np.random.default_rng(seed)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)

    def pool_with(rows):
        """rows: [(page ids, written tokens)] -> pool with those pages."""
        pool = paged_lib.init_pool(1 + len(rows) * maxp, hk, bs, d,
                                   pol.stride)
        for ids, n in rows:
            npages = -(-n // bs)
            pool = paged_lib.write_prefill_pages(
                pool, jnp.asarray(ids[:npages]), normal(hk, npages * bs, d),
                normal(hk, npages * bs, d), jnp.asarray(n, jnp.int32), pol)
        return pool

    table = (1 + np.arange(4 * maxp, dtype=np.int32)).reshape(4, maxp)
    errs = {}
    with jax.default_matmul_precision("highest"):
        lens = np.asarray([517, 1100, 1791, 2080], np.int32)
        pool = pool_with([(table[i], int(n)) for i, n in enumerate(lens)])
        q = normal(4, hq, 1, d)

        def decode(ex):
            fn = jax.jit(lambda q, pool, pt, lens: paged_lib.
                         paged_sparse_decode(q, pool, pt, lens, pol, 0.5,
                                             executor=ex))
            return np.asarray(fn(q, pool, jnp.asarray(table), lens))
        errs["decode"] = (decode("pallas"), decode("xla"))

        hist, nc = 9, 2
        start = np.full((2,), hist * bs, np.int32)
        true_len = start + np.asarray([nc * bs, 181], np.int32)
        pool = pool_with([(table[i], hist * bs) for i in range(2)])
        ct = jnp.asarray(table[:2])
        pool = paged_lib.write_chunk_pages(
            pool, ct, jnp.asarray(start), normal(2, hk, nc * bs, d),
            normal(2, hk, nc * bs, d), jnp.asarray(true_len), pol)
        budgets = np.stack([chunked_lib.chunk_budget_rows(
            pol, maxp * bs, int(s), nc) for s in start])
        qc = normal(2, hq, nc * bs, d)

        def chunk(ex):
            fn = jax.jit(lambda q, pool, pt, st, bud: chunked_lib.
                         chunked_prefill_attention(q, pool, pt, st, bud, pol,
                                                   executor=ex))
            return np.asarray(fn(qc, pool, ct, jnp.asarray(start),
                                 jnp.asarray(budgets)))
        errs["chunk"] = (chunk("pallas"), chunk("xla"))

    for lane, (got, want) in errs.items():
        err = float(np.max(np.abs(got - want)))
        print(f"kernel differential {lane}: fused vs xla max|err| {err:.3e} "
              f"(max|ref| {float(np.max(np.abs(want))):.3f}, tol "
              f"{KERNEL_TOL:g})", flush=True)
        check(np.isfinite(got).all() and err <= KERNEL_TOL,
              f"fused {lane} kernel differs from the xla oracle by {err}")


def one_chip(jax, clock):
    a = phase(jax, clock, "(a) xla sync", [], pallas=False)
    b = phase(jax, clock, "(b) pallas sync", ["--executor", "pallas"],
              pallas=True)
    c = phase(jax, clock, "(c) pallas async", ["--executor", "pallas",
                                               "--async-depth", "1"],
              pallas=True)
    check(c == b, "async pallas streams differ from sync pallas streams")
    total = N_REQUESTS * DECODE_TOKENS
    print(f"streams: (c) == (b) bit-identical; (a) vs (b) matching leading "
          f"tokens {matching_tokens(a, b)} of {total} (reported only: bf16 "
          f"greedy decoding may drift between executors)", flush=True)
    kernel_differential(jax)


@contextlib.contextmanager
def model_override(**changes):
    """Serve the published config with ``changes`` applied."""
    from repro import configs
    get_config = configs.get_config
    configs.get_config = lambda name: get_config(name).replace(**changes)
    try:
        yield
    finally:
        configs.get_config = get_config


def four_chips(jax, clock):
    """The serving mesh must reproduce one device's greedy streams.  That
    is a claim about the sharding logic, so it is checked in fp32.  In
    bf16 on TPU, tp > 1 gives each device a different attention program,
    whose roundings differ enough to flip greedy picks (dp alone does
    not).  Depth is cut to keep the fp32 compiles short."""
    devices = jax.devices()[:4]
    with model_override(dtype="float32", num_layers=FOUR_CHIP_LAYERS):
        single, s_spent = serve(clock, [])
        check_run("single device", single)
        mesh, m_spent = serve(clock, ["--mesh", "2,2"])
        check_run("mesh 2,2", mesh)
    engine = mesh["engine"]
    on = {d for leaf in jax.tree.leaves(engine.pools)
          for d in leaf.sharding.device_set}
    check(on == set(devices),
          f"mesh pools live on {sorted(d.id for d in on)}, not devices "
          f"{sorted(d.id for d in devices)}")
    check(list(engine.smesh.mesh.devices.flat) == devices,
          "the serving mesh is not built from jax.devices()[:4]")
    check(mesh["tokens"] == single["tokens"],
          "--mesh 2,2 streams differ from single-device streams")
    for name, spent in (("single device", s_spent), ("mesh 2,2", m_spent)):
        print(f"{name}: compile_s {spent['compile_s']:.3f}, run_s "
              f"{spent['run_s']:.3f}, wall_s {spent['wall_s']:.3f}",
              flush=True)
    print(f"mesh 2,2 over devices {sorted(d.id for d in on)}: streams "
          f"bit-identical to single device ({N_REQUESTS} requests, "
          f"{mesh['total_tokens']} tokens; fp32, {FOUR_CHIP_LAYERS} "
          f"layers)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(c) + kernel differential; 4: the "
                         "--mesh 2,2 vs single-device comparison only")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's default backend is "
              f"{platform!r}; not running", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import backend
    cache = backend.setup_compile_cache()
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device: {device['kind']} x {len(devices)} visible, using "
          f"{args.chips}; compile cache {cache}", flush=True)
    clock = CompileClock(jax)
    try:
        (four_chips if args.chips == 4 else one_chip)(jax, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
