"""Device-idle time per window round inside the sync loop's other spans:
``obs.launch``, ``obs.sync``, ``obs.eval``, ``obs.control``, ``obs.ckpt`` and
``obs.log`` (``spans.py``). With ``data.wait_ms`` and ``idle.untraced_ms`` it
sums to the device's idle time."""

import spans

UNIT = "ms"


def read(ctx):
    return spans.idle_ms(ctx, "loop")
