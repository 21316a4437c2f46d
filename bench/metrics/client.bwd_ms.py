"""Self time per round of the round program's backward pass: its ops under the
``client`` scope in the transposed ``fwd`` (``transpose(jvp(fwd))``; the
program's ``jax.named_scope``s, read from its scope table; ``spans.py``)."""

import spans

UNIT = "ms"


def read(ctx):
    parts = spans.round_parts_ms(ctx)
    return None if parts is None else parts.get("bwd", 0.0)
