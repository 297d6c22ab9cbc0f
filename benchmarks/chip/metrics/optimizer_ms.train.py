"""optimizer_ms.train: device self time in the program's ``optimizer``
scope (gradient clipping and the AdamW update) per traced training step
(``yardstick.scopes``)."""
from yardstick import scopes


def read(out, cell):
    return scopes.scope_ms(out, cell, "optimizer", "optimizer_ms.train")
