"""Device milliseconds per step in the exchange between chips: the union of
the collective ops' intervals (all-reduce, all-gather, reduce-scatter,
all-to-all, collective-permute, known by opcode from the compiled step; an
async one from its start to its done), from the trace, averaged over the
chips. No collective, no reading."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr.get("collective_s") or not rec.get("traced_steps"):
        return None
    return tr["collective_s"] / rec["traced_steps"] * 1e3
