"""Device ms a step of attention (scope ``attention``), forward and
backward, mean over the chips, in the traced window (class
``attention`` of ``scopes.py``)."""
import scopes


def read(rec):
    return scopes.class_ms(rec, "attention")
