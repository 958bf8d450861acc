"""The mixture-of-experts blocks' share of the chip's peak, in %: their model
FLOPs a step (``chipbench.reference.moonlight.moe_flops_per_token``: router,
the routed experts a token is expected to send to those held here, shared
experts; recomputation not counted) over their device time a step
(``moe_ms``), over the peak bf16 FLOP/s. Compute-bound by its count: the
dispatch's sort and gathers add bytes, not FLOPs."""

from chipbench.metrics import moe_ms
from chipbench.reference.moonlight import moe_flops_per_token


def read(rec):
    ms = moe_ms.read(rec)
    if ms is None:
        return None
    flops = moe_flops_per_token(rec["model"]) * rec["tokens_per_step"]
    return 100.0 * flops / (ms * 1e-3) / rec["peaks"]["bf16_flops"]
