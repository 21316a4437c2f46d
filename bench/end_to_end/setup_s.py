"""From the process's start to the window's: imports, device start-up, weights,
compilation or compile-cache reads, and the set-up rounds (host clock)."""

UNIT = "s"


def read(ctx):
    return ctx.setup_s
