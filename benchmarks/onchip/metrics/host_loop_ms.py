"""Mean host time per step outside the blocked step: batch upload,
metrics pull and bucket choice (the benchmark's host spans)."""


def read(rec):
    if not rec.steps:
        return None
    return 1e3 * sum(s["total_s"] - s["step_s"] for s in rec.steps) \
        / len(rec.steps)
