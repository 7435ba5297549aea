"""Device time per answer in the client pipeline (``pipeline``: order-key
counts, the capacity recurrence scan and the rank search)."""


def read(reduced):
    if reduced is None or not reduced.answers:
        return None
    t = reduced.module_seconds(lambda name: name == "jit_pipeline")
    return 1e3 * t / reduced.answers if t > 0 else None
