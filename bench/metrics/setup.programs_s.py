"""Seconds the set-up rounds spent building device programs: the sum of the
rows' ``compile_s`` (the trainer's ``CompileCounter``: each executable's
compile or persistent-cache read) over the set-up rounds."""

import spans

UNIT = "s"


def read(ctx):
    rows = spans.rows(ctx)
    if rows is None:
        return None
    setup = rows[: ctx.cell.setup_rounds]
    if not setup or any("compile_s" not in r for r in setup):
        return None
    return sum(float(r["compile_s"]) for r in setup)
