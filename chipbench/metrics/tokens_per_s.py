"""Training tokens of every step completed in the window, over the window's
length (first dispatch to last completion), per chip."""


def read(rec):
    if not rec.get("window_s") or not rec.get("steps"):
        return None
    return rec["steps"] * rec["tokens_per_step"] / rec["window_s"] / rec["chips"]
