"""Model FLOP/s utilisation of the whole step: model FLOPs per token
(``flops.py``) times the non-padding tokens trained per second in the
traced window, over the chips' published bf16 peak (``peaks.py``)."""
import peaks


def read(rec):
    if rec.trace is None or not rec.steps or rec.window_s <= 0:
        return None
    tokens = sum(s["tokens"] for s in rec.steps)
    peak = peaks.peak(rec.device_kind)["bf16_flops"]
    return (100.0 * rec.flops_per_token * tokens / rec.window_s
            / (rec.chips * peak))
