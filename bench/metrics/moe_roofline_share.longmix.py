"""Roofline share of the MoE layers' expert FFN (``stem.moe``), %: the
least time a step's expert work can take, the larger of its FLOPs over the
bf16 peak and its bytes over the HBM peak, over the phase's device time
per traced step.  The phase's time is ``stem.moe`` plus the unscoped
``ragged-dot-*`` kernels, as ``moe_ms_per_step`` reads it: the TPU
compiler's grouped-matmul kernels for the routed experts lose the op's
named scope.

FLOPs: the router, the ``num_experts_per_tok`` routed and the shared
experts for every real token of the window's steps (``moe_flops`` of the
family), per step.  Bytes: every routed expert the step's real tokens hit
(the engine's ``moe_experts_hit`` over its steps), read once per layer,
plus the routers and shared experts, per step.  Both are lower bounds of
what any implementation moves, so the share cannot pass 100%.  None where
the run has no ``stem.moe`` phase or no MoE counter."""


RAGGED = "ragged-dot"


def read(rec):
    ph, stats, fam = rec.get("phases"), rec["stats"], rec["family"]
    calls, steps = stats.get("step_calls", 0), rec["steps"]
    if (not ph or not ph["steps"] or not ph["phase_s"].get("stem.moe")
            or not calls or not steps or "moe_experts_hit" not in stats):
        return None
    model, peak = rec["model"], rec["peak"]
    flops = sum(fam.moe_flops(model, s) for s in steps) / len(steps)
    nbytes = (stats["moe_experts_hit"] / calls * fam.expert_bytes(model)
              + fam.moe_fixed_bytes(model))
    least_s = max(flops / peak["bf16_flops_per_s"],
                  nbytes / peak["hbm_bytes_per_s"])
    moe_s = ph["phase_s"]["stem.moe"] + sum(
        s for name, s in ph["unscoped_ops"] if name.startswith(RAGGED))
    return 100.0 * least_s * ph["steps"] / moe_s
