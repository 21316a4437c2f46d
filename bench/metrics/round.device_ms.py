"""Device time per round of the sync round program (client and server phase):
the union of its operations' intervals, per run. The program is the XLA module
with the most device time in the window; it runs once per round, and a count
that differs from the window's rounds is noted on stderr."""

UNIT = "ms"


def round_program(ctx):
    """(module name, device seconds per run) of the round program, or None."""
    if ctx.trace is None or ctx.cell.flags().get("--aggregation") != "sync":
        return None
    name = ctx.trace.busiest_module()
    if name is None:
        return None
    seconds, runs = ctx.trace.modules[name]
    if runs != ctx.trace.rounds:
        ctx.trace.notes.append(
            f"round program {name} ran {runs} times in {ctx.trace.rounds} traced rounds")
    return name, seconds / runs


def read(ctx):
    found = round_program(ctx)
    if found is None:
        return None
    name, per_run = found
    ctx.trace.notes.append(f"round program: {name}, {per_run * 1e3!r} ms a round")
    return 1e3 * per_run
