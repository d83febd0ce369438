"""Device self time of the MoE layers' expert FFN per traced step, ms: the
``stem.moe`` phase (router, dispatch, routed and shared experts, combine;
inside ``stem.mlp``, so ``dense_ms_per_step`` counts it too) plus the
grouped-matmul kernels the TPU compiler makes of its ``ragged_dot``s.
Those kernels are named ``ragged-dot-*`` and lose the op's named scope, so
the phase split counts them as unscoped; they are the routed experts'
matmuls.  None where the trace has no such phase."""

RAGGED = "ragged-dot"


def read(rec):
    ph = rec.get("phases")
    if not ph or not ph["steps"] or "stem.moe" not in ph["phase_s"]:
        return None
    moe_s = ph["phase_s"]["stem.moe"] + sum(
        s for name, s in ph["unscoped_ops"] if name.startswith(RAGGED))
    return moe_s / ph["steps"] * 1e3
