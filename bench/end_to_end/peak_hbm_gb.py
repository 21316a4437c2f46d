"""The device allocator's peak after the window, in 1e9 bytes: on the TPU v5e
``peak_bytes_reserved``, which holds the round's temporaries."""

UNIT = "GB"


def read(ctx):
    return ctx.memory_peak_bytes / 1e9
