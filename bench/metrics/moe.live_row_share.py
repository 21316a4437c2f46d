"""The share of the expert-share layers' routed rows that carry work: the
window's mean of the counter ``moe_local_slots`` (slots a step and layer routed
to a held expert) over that of ``moe_routed_rows`` (the rows the layer's
routed part was sized for, a step and layer), in %. None where the rows lack
either counter (a program whose routed part is sized by every slot)."""

import scope_ms

UNIT = "%"


def read(ctx):
    if ctx.trace is None:
        return None
    live = scope_ms.window_mean(ctx, "moe_local_slots")
    rows = scope_ms.window_mean(ctx, "moe_routed_rows")
    if live is None or not rows:
        return None
    return 100.0 * live / rows
