"""Share of the traced window in which no operation runs on a chip,
averaged over the cell's chips (device trace)."""


def read(rec):
    t = rec.trace
    if t is None or t["window_s"] <= 0:
        return None
    busy = list(t["busy_s"].values())
    if not busy or max(busy) <= 0:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / t["window_s"])
