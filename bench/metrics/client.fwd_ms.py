"""Self time per round of the round program's forward pass: its ops under the
``client`` scope and in ``fwd``, not transposed (the program's
``jax.named_scope``s, read from its scope table; ``spans.py``)."""

import spans

UNIT = "ms"


def read(ctx):
    parts = spans.round_parts_ms(ctx)
    return None if parts is None else parts.get("fwd", 0.0)
