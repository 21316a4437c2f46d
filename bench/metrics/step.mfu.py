"""Whole-round model FLOP utilisation: the training FLOPs the admitted tokens
require (6·N + 6·L·S·d a token, causal) over the traced window, divided by the
window and the chip's bf16 peak."""

import counts

UNIT = "%"


def read(ctx):
    if ctx.trace is None:
        return None
    per_token = counts.train_flops_per_token(ctx.cell.config, int(ctx.cell.flags()["--seq-len"]))
    flops = per_token * ctx.window_tokens
    return 100.0 * flops / (ctx.trace.window_s * ctx.peaks["bf16_flops_per_s"])
