"""Device milliseconds per step in the mixture-of-experts blocks: the self
time of the ops whose ``op_name`` scope is ``fwd_bwd`` and whose block is
``moe`` (router, dispatch, held and shared experts, combine; forward,
recomputation and backward), from the trace (``chipbench.scopes``). No such
op, as in a program without the block's scope, no reading."""


def read(rec):
    spent = rec.get("scopes", {}).get("stage_s", {}).get("fwd_bwd/moe")
    if not spent or not rec.get("traced_steps"):
        return None
    return spent / rec["traced_steps"] * 1e3
