"""Device milliseconds per step in forward and backward (the model's blocks
``embed``, ``attn``, ``mlp``, ``loss``): the self time of the ops whose
``op_name`` scope is ``fwd_bwd`` (``chipbench.scopes``), from the trace,
averaged over the chips. No such op, no reading."""


def read(rec):
    spent = rec.get("scopes", {}).get("scope_s", {}).get("fwd_bwd")
    if not spent or not rec.get("traced_steps"):
        return None
    return spent / rec["traced_steps"] * 1e3
