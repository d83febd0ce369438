"""HBM roofline share of absorbed MLA decode (``stem.mla_decode``: the kept
latent pages' gather, attention in the latent and ``W_UV``), %: the kept
latent bytes the window's decode tokens read per step (``latent_decode_bytes``
of the family: each kept page once per slot and layer, 576 values a token)
over the HBM peak, over the phase's device time per traced step.  A lower
bound of what any implementation reads, so the share cannot pass 100%.
None where the run has no such phase or no decode token."""


def read(rec):
    ph, fam, steps = rec.get("phases"), rec["family"], rec["steps"]
    if (not ph or not ph["steps"] or not ph["phase_s"].get("stem.mla_decode")
            or not any(s["decode"] for s in steps)):
        return None
    nbytes = sum(fam.latent_decode_bytes(rec["model"], rec["rule"], s)
                 for s in steps) / len(steps)
    least_s = nbytes / rec["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s * ph["steps"] / ph["phase_s"]["stem.mla_decode"]
