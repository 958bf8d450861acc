"""The reduce kernels' share of their HBM roofline, in %: the least time the
reduce's bytes need at peak HBM bandwidth (chipbench.counts.reduce_min_bytes)
over the kernels' device time per step. Memory-bound by construction: the
selection is a few compares per element."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["kernel_s"] <= 0 or not rec.get("traced_steps"):
        return None
    per_step = tr["kernel_s"] / rec["traced_steps"]
    least = rec["reduce_bytes_per_step"] / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / per_step
