"""Device time per run of the eval program, ``jit_eval_ce``: the union of its
operations' intervals, per run, as ``round.device_ms`` reads the round program."""

import spans

UNIT = "ms"


def read(ctx):
    if ctx.trace is None:
        return None
    found = [v for k, v in ctx.trace.modules.items() if spans.module_base(k) == spans.EVAL_PROGRAM]
    if not found:
        return None
    seconds, runs = map(sum, zip(*found))
    return 1e3 * seconds / runs
