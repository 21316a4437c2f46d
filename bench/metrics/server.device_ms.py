"""Self time per round of the round program's ops in the server phase: the
weighted mean, the outer update and their metrics, under the program's
``server`` scope (read from its scope table; ``spans.py``)."""

import spans

UNIT = "ms"


def read(ctx):
    parts = spans.round_parts_ms(ctx)
    return None if parts is None else parts.get("server", 0.0)
