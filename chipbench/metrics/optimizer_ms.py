"""Device milliseconds per step in the optimizer (global norm, clip,
schedule, update, step counter): the self time of the ops whose
``op_name`` scope is ``optimizer`` (``chipbench.scopes``), from the trace,
averaged over the chips. No such op, no reading."""


def read(rec):
    spent = rec.get("scopes", {}).get("scope_s", {}).get("optimizer")
    if not spent or not rec.get("traced_steps"):
        return None
    return spent / rec["traced_steps"] * 1e3
