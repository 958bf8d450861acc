"""The latent-attention blocks' share of the chip's peak, in %: their model
FLOPs a step (``chipbench.reference.moonlight.mla_flops_per_token``:
projections, score and value products at the mix's sequence; recomputation
not counted) over their device time a step (``mla_ms``), over the peak bf16
FLOP/s."""

from chipbench.metrics import mla_ms
from chipbench.reference.moonlight import mla_flops_per_token


def read(rec):
    ms = mla_ms.read(rec)
    if ms is None:
        return None
    flops = mla_flops_per_token(rec["model"], rec["mix"]["seq"]) * rec["tokens_per_step"]
    return 100.0 * flops / (ms * 1e-3) / rec["peaks"]["bf16_flops"]
