"""Distributed training driver.

Composes the substrate: model registry + sharding rules + AdamW (fp32
master, bf16 grad compression) + seekable synthetic data + checkpoint
manager (atomic, keep-K, async) + straggler monitor + failure-injection
restart harness.

On a real fleet this is launched once per host with the same arguments;
jax.distributed.initialize() picks up the coordinator from the environment
(called only when JAX_COORDINATOR_ADDRESS is set, so single-host runs and
tests skip it).  Recommended production XLA flags (latency-hiding scheduler,
async collectives) are applied via ``--prod-flags``.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \\
      --steps 20 --batch 8 --seq 256 --checkpoint-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \\
      --steps 20 --restore --checkpoint-dir /tmp/ckpt   # resume
"""
from __future__ import annotations

import argparse
import os
import sys
import time

PROD_XLA_FLAGS = " ".join([
    # Overlap compute with collectives (latency-hiding scheduler).
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
])


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-scale smoke training)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--stem", action="store_true",
                    help="train with Stem sparse attention in the forward")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=10)
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="inject a failure at this step (fault-tolerance demo)")
    ap.add_argument("--prod-flags", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=1)
    args = ap.parse_args(argv)

    if args.prod_flags:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + PROD_XLA_FLAGS).strip()
    if os.environ.get("JAX_COORDINATOR_ADDRESS"):
        import jax
        jax.distributed.initialize()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import backend, configs, optim
    from repro.checkpoint import CheckpointManager
    from repro.core.config import StemConfig
    from repro.data import SyntheticLMData, make_global_batch
    from repro.launch import mesh as mesh_lib
    from repro.launch import steps as steps_lib
    from repro.models import registry
    from repro.runtime import FailureInjector, StragglerMonitor
    from repro.sharding import rules as rules_lib

    backend.setup_compile_cache()
    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg).replace(dtype="float32")
    bundle = registry.build(cfg)

    n_dev = len(jax.devices())
    mesh = mesh_lib.make_local_mesh() if n_dev < 256 else \
        mesh_lib.make_production_mesh(multi_pod=n_dev >= 512)

    stem_cfg = None
    if args.stem:
        stem_cfg = StemConfig(block_size=min(128, max(16, args.seq // 8)),
                              min_budget_blocks=2, sink_blocks=1, local_blocks=1,
                              stride=4)

    opt_cfg = optim.AdamWConfig(peak_lr=args.lr, warmup_steps=max(2, args.steps // 10),
                                decay_steps=max(args.steps, 10))
    abstract_values, axes_tree = bundle.abstract_params()
    param_sh = rules_lib.param_shardings(cfg, mesh, abstract_values, axes_tree)
    state_sh = steps_lib.opt_state_shardings(cfg, mesh, param_sh, abstract_values)

    train_step = steps_lib.make_train_step(
        bundle, opt_cfg, stem_cfg=stem_cfg, remat=True,
        microbatches=args.microbatches, grad_shardings=state_sh.master)

    data = SyntheticLMData(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed,
        kind={"vlm": "vlm", "encdec": "encdec"}.get(cfg.family, "lm"),
        d_model=cfg.d_model,
        frames=cfg.encdec.encoder_frames if cfg.encdec else 0)
    batch0 = data.batch_at(0)
    batch_sh = rules_lib.batch_sharding(
        cfg, mesh, {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch0.items()})

    mgr = CheckpointManager(args.checkpoint_dir) if args.checkpoint_dir else None
    start_step = 0
    with mesh:
        if args.restore and mgr and mgr.latest_step() is not None:
            abstract_state = steps_lib.abstract_opt_state(abstract_values, opt_cfg)
            state, meta = mgr.restore(abstract_state, shardings=state_sh)
            state = optim.OptState(*state)
            start_step = int(meta["step"])
            print(f"restored checkpoint at step {start_step}", flush=True)
        else:
            params = jax.jit(bundle.init_params, out_shardings=param_sh)(
                jax.random.PRNGKey(args.seed))
            state = jax.jit(lambda p: optim.init_state(p, opt_cfg), out_shardings=state_sh)(params)

        jit_step = jax.jit(train_step, in_shardings=(state_sh, batch_sh),
                           donate_argnums=(0,))

        injector = FailureInjector((args.fail_at,) if args.fail_at >= 0 else ())
        monitor = StragglerMonitor(on_straggler=lambda s, dt, ema: print(
            f"[straggler] step {s}: {dt:.3f}s vs ema {ema:.3f}s", flush=True))

        losses = []
        for step in range(start_step, args.steps):
            injector.maybe_fail(step)
            monitor.start()
            gbatch = make_global_batch(data.batch_at(step), mesh, batch_sh)
            state, metrics = jit_step(state, gbatch)
            loss = float(metrics["loss"])
            monitor.stop(step)
            losses.append(loss)
            if step % args.log_every == 0:
                print(f"step {step}: loss={loss:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e}", flush=True)
            if mgr and (step + 1) % args.checkpoint_every == 0:
                mgr.save(step + 1, state, extra={"loss": loss}, blocking=False)
        if mgr:
            mgr.save(args.steps, state, extra={"final": True}, blocking=True)
    return {"final_loss": losses[-1] if losses else None, "losses": losses,
            "stragglers": monitor.flagged}


if __name__ == "__main__":
    main()
