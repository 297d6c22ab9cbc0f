"""train_mfu.mla_moe: the whole training step's share of the chips' bf16
peak, for a model of latent attention and routed experts.

Model FLOPs per token (``yardstick.mla_moe.train_flops_per_token``: the
matmul parameters every token passes through, one routed expert for each
copy the program computed here in the window, and the causal attention
products at latent attention's qk and v widths; recomputation not counted)
times the window's train_tokens_per_s, over the chips' published peak.
"""
from yardstick import mla_moe, peaks


def read(out, cell):
    tps = out.end_to_end.get("train_tokens_per_s")
    rows = out.counters.get("rows_here_window")
    if not tps or not rows:
        return None
    per_token = sum(rows) / len(rows) / out.counters["tokens_per_step"]
    fpt = mla_moe.train_flops_per_token(cell.config["model"],
                                        cell.traffic["seq"], per_token)
    peak = peaks.peaks(out.device["kind"])["bf16_flops_per_s"] * cell.chips
    return 100.0 * tps * fpt / peak
