"""Device ms a step of the expert FFN (scope ``expert_ffn``), forward and
backward, mean over the chips, in the traced window (class
``expert_ffn`` of ``scopes.py``)."""
import scopes


def read(rec):
    return scopes.class_ms(rec, "expert_ffn")
