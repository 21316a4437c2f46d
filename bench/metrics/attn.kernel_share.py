"""Share of the attention core's self time (``client.attn_ms``) spent in the
Pallas kernels' custom calls: about 100% where every layer takes the kernels,
0 where the configuration falls back to the jnp paths (``attn_parts.py``)."""

import attn_parts

UNIT = "%"


def read(ctx):
    parts = attn_parts.parts_ms(ctx)
    if parts is None:
        return None
    kernel = sum(v for (_, is_kernel), v in parts.items() if is_kernel)
    return 100.0 * kernel / sum(parts.values())
