"""Device ms a step of clipping and AdamW (scope ``optimizer``), forward
and backward, mean over the chips, in the traced window (class
``optimizer`` of ``scopes.py``)."""
import scopes


def read(rec):
    return scopes.class_ms(rec, "optimizer")
