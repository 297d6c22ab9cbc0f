"""train_mfu: the whole training step's share of the chips' bf16 peak.

Model FLOPs per token (``yardstick.flops``: 6 x matmul parameters plus the
causal attention products; recomputation not counted) times the window's
train_tokens_per_s, over the chips' published peak (``yardstick.peaks``).
"""
from yardstick import flops, peaks


def read(out, cell):
    tps = out.end_to_end.get("train_tokens_per_s")
    if not tps:
        return None
    fpt = flops.train_flops_per_token(cell.config["model"],
                                      cell.traffic["seq"])
    peak = peaks.peaks(out.device["kind"])["bf16_flops_per_s"] * cell.chips
    return 100.0 * tps * fpt / peak
