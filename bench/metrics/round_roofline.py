"""The round program's share of its roofline: the least time the chip could take
for the round's required work, max(FLOPs / peak FLOP/s, HBM bytes / peak
bandwidth), over the program's device time per round. Both counts come from
shapes (``counts.py``); which of the two bounds it is noted on stderr."""

import counts
from pathlib import Path
from harness import load_module

UNIT = "%"
_device_ms = load_module(Path(__file__).with_name("round.device_ms.py"))


def read(ctx):
    found = _device_ms.round_program(ctx)
    if found is None:
        return None
    _, per_run = found
    flags, cfg = ctx.cell.flags(), ctx.cell.config
    t_flops = counts.round_flops(cfg, flags) / ctx.peaks["bf16_flops_per_s"]
    t_bytes = counts.round_bytes(cfg, flags, cfg["padded_vocab"]) / ctx.peaks["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "hbm_bytes"
    ctx.trace.notes.append(
        f"round.roofline bound by {bound}: flops {t_flops!r} s, bytes {t_bytes!r} s a round")
    return 100.0 * max(t_flops, t_bytes) / per_run
