"""Model FLOP utilization of the whole step, in %: the model FLOPs of the
traced steps (chipbench.counts.train_flops_per_token, no recomputation) over
the traced window, over the chips' peak bf16 FLOP/s."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not rec.get("traced_steps"):
        return None
    flops = rec["flops_per_step"] * rec["traced_steps"]
    return 100.0 * flops / tr["window_s"] / (rec["peaks"]["bf16_flops"] * rec["chips"])
