"""Device-idle time per window round under none of the program's spans: the
host's time between them (``spans.py``)."""

import spans

UNIT = "ms"


def read(ctx):
    return spans.idle_ms(ctx, "untraced")
