"""Training tokens of every client delta admitted into the global model over
the window's whole rounds, divided by the window's wall time (host clock)."""

UNIT = "tokens/s"


def read(ctx):
    return ctx.window_tokens / ctx.window_s
