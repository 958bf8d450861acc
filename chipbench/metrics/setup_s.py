"""Set-up seconds: process start to the first timed step (imports, device
init, weights, compile or cache load, warm-up steps), less the time the
correctness readings alone took."""


def read(rec):
    return rec.get("setup_s")
