"""dispatch_ms.train: device self time in the program's ``dispatch`` scope
(the MoE dispatch: token copies sorted to their experts and gathered, and the weighted scatter-add back to the tokens; forward, recompute and backward) per traced training step
(``yardstick.scopes``)."""
from yardstick import mla_moe


def read(out, cell):
    return mla_moe.scope_ms(out, cell, "dispatch", "dispatch_ms.train")
