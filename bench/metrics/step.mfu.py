"""Model FLOP utilization of the traced window: model FLOPs of the tokens
trained in it (``bench.flops``, recompute not counted) over its seconds
times the chip's bf16 peak (``bench.peaks``), in percent."""

from bench.flops import train_flops_per_token
from bench.peaks import peaks


def read(ctx):
    flops = ctx.tokens * train_flops_per_token(
        ctx.cell.model, ctx.cell.traffic["seq_len"])
    peak = peaks(ctx.device_kind)["bf16_flops_per_s"] * ctx.cell.entry["chips"]
    return 100.0 * flops / (ctx.window["seconds"] * peak)
