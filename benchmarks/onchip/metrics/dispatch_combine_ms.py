"""Device ms a step of the expert exchange's packing, dispatch, combine
and unpacking (scopes ``dispatch_pack``, ``dispatch``, ``combine``,
``combine_unpack``), forward and backward, mean over the chips, in the
traced window (class ``dispatch_combine`` of ``scopes.py``)."""
import scopes


def read(rec):
    return scopes.class_ms(rec, "dispatch_combine")
