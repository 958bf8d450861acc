"""Host milliseconds per step inside the program's ``TrainLoop.step`` (jit
dispatch), from the harness's own span around the call in the traced run."""


def read(rec):
    spans = rec.get("dispatch_s")
    if not spans:
        return None
    return sum(spans) / len(spans) * 1e3
