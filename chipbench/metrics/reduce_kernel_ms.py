"""Device milliseconds per step in the Mosaic (Pallas) kernels of the reduce,
from the trace; averaged over the chips. No kernel event, no reading."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["kernel_s"] <= 0 or not rec.get("traced_steps"):
        return None
    return tr["kernel_s"] / rec["traced_steps"] * 1e3
