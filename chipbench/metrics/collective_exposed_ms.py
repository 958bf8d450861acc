"""Device milliseconds per step in which a collective is under way and no
other op runs on the chip: the part of ``collective_ms``'s union that
compute does not hide, averaged over the chips. No collective, no reading."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr.get("collective_s") or not rec.get("traced_steps"):
        return None
    return tr["collective_exposed_s"] / rec["traced_steps"] * 1e3
