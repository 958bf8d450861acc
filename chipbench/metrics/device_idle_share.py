"""Share of the traced window, in %, in which no operation ran on the device:
100 (1 - union of device op intervals / window), averaged over the chips."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
