"""experts_ms.train: device self time in the program's ``experts`` scope
(the held experts' grouped matmuls; forward, recompute and backward) per traced training step
(``yardstick.scopes``)."""
from yardstick import mla_moe


def read(out, cell):
    return mla_moe.scope_ms(out, cell, "experts", "experts_ms.train")
