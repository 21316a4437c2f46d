"""Self time per round of the round program's attention core: its ops under
the ``client`` scope with an ``attn`` segment, forward and backward together
(``attn_parts.py``)."""

import attn_parts

UNIT = "ms"


def read(ctx):
    parts = attn_parts.parts_ms(ctx)
    return None if parts is None else sum(parts.values())
