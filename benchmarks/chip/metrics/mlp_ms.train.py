"""mlp_ms.train: device self time in the program's ``mlp`` scope (the
SwiGLU MLP, forward, recompute and backward) per traced training step
(``yardstick.scopes``)."""
from yardstick import scopes


def read(out, cell):
    return scopes.scope_ms(out, cell, "mlp", "mlp_ms.train")
