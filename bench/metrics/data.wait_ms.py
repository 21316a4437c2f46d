"""Device-idle time per window round while the host plans the round, builds
its batches or copies them to the device: the idle time inside the program's
``obs.plan``, ``obs.data`` and ``obs.h2d`` spans (``spans.py``)."""

import spans

UNIT = "ms"


def read(ctx):
    return spans.idle_ms(ctx, "data")
