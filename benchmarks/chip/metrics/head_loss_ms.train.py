"""head_loss_ms.train: device self time in the program's ``head_loss``
scope (logits over the vocabulary and the cross-entropy, forward and
backward) per traced training step (``yardstick.scopes``)."""
from yardstick import scopes


def read(out, cell):
    return scopes.scope_ms(out, cell, "head_loss", "head_loss_ms.train")
