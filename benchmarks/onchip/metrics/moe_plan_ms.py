"""Device ms a step of the router, the exchange plan's build and token
condensation (scopes ``router``, ``plan_build``, ``condense``),
forward and backward, mean over the chips, in the traced window (class
``moe_plan`` of ``scopes.py``)."""
import scopes


def read(rec):
    return scopes.class_ms(rec, "moe_plan")
