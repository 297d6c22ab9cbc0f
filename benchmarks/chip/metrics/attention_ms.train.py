"""attention_ms.train: device self time in the program's ``attention``
scope (the attention kernel: forward, recompute and backward) per traced
training step (``yardstick.scopes``)."""
from yardstick import scopes


def read(out, cell):
    return scopes.scope_ms(out, cell, "attention", "attention_ms.train")
