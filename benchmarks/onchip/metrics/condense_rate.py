"""Mean over the window's steps of the share of tokens condensed, as
the step itself counts it (``condense_rate``)."""


def read(rec):
    vals = [s["condense_rate"] for s in rec.steps
            if s["condense_rate"] is not None]
    if not vals:
        return None
    return 100.0 * sum(vals) / len(vals)
