"""Self time per round of the round program's inner AdamW update: its ops under
the ``client`` scope in ``opt`` (the program's ``jax.named_scope``s, read from
its scope table; ``spans.py``)."""

import spans

UNIT = "ms"


def read(ctx):
    parts = spans.round_parts_ms(ctx)
    return None if parts is None else parts.get("opt", 0.0)
