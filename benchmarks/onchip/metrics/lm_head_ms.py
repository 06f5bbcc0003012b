"""Device ms a step of the embedding, the LM head and the loss (scopes
``embed``, ``lm_head``), forward and backward, mean over the chips, in
the traced window (class ``lm_head`` of ``scopes.py``)."""
import scopes


def read(rec):
    return scopes.class_ms(rec, "lm_head")
