"""router_ms.train: device self time in the program's ``router`` scope
(the MoE routers: scores, selection, gates and the balance loss; forward, recompute and backward) per traced training step
(``yardstick.scopes``)."""
from yardstick import mla_moe


def read(out, cell):
    return mla_moe.scope_ms(out, cell, "router", "router_ms.train")
