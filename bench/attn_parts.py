"""The round program's attention core, for ``metrics/client.attn_ms.py`` and
``metrics/attn.kernel_share.py``.

A program that scopes its scaled-dot-product core ``attn`` (a
``jax.named_scope`` in ``models/attention.py``) names every op of it, forward
and backward, in the round program's scope table (``spans.scopes``): an op
under ``client`` with an ``attn`` segment, whatever transformations wrap the
segments (``vmap(transpose(jvp(fwd)))``). Of those ops, a Pallas kernel's
custom call is the one whose op_name ends in ``pallas_call``. Where no op has
the segment (an older program), the readers find nothing and return None.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, Optional, Tuple

import spans

KERNEL = "pallas_call"


def _segments(op_name: str) -> Tuple[set, list]:
    """The scope names of an op_name, each unwrapped from its transformations,
    and the last segment; of names XLA merged with ``;``, the first is read."""
    names, segs = set(), op_name.split(";", 1)[0].split("/")
    for seg in segs:
        m = spans._WRAPPED.fullmatch(seg)
        while m:
            seg = m.group(2)
            m = spans._WRAPPED.fullmatch(seg)
        names.add(seg)
    return names, segs[-1]


def part(op_name: str) -> Optional[Tuple[Optional[str], bool]]:
    """``(bucket, is_kernel)`` of an op of the client phase's attention core
    (``spans.bucket``: ``fwd`` or ``bwd``), or None for any other op."""
    names, last = _segments(op_name)
    if "client" not in names or "attn" not in names:
        return None
    return spans.bucket(op_name), last == KERNEL


def parts_ms(ctx) -> Optional[Dict[Tuple[Optional[str], bool], float]]:
    """Self time per round program run, in ms, of the attention core's ops by
    :func:`part`; None outside a sync cell, where the trace or the scope
    table lacks the program, or where no op is scoped ``attn``."""
    if ctx.cell.flags().get("--aggregation") != "sync":
        return None
    if not hasattr(ctx, "attn_parts"):
        summary = spans.of(ctx)
        table = spans.scopes(ctx).get(spans.ROUND_PROGRAM) if summary is not None else None
        out = None
        if table is not None and spans.ROUND_PROGRAM in summary.modules:
            runs, ops = summary.modules[spans.ROUND_PROGRAM]
            acc: Dict[Tuple[Optional[str], bool], float] = defaultdict(float)
            for name, seconds in ops.items():
                key = part(table[name]) if name in table else None
                if key is not None:
                    acc[key] += 1e3 * seconds / runs
            out = dict(acc) or None
        if out is not None:
            ctx.trace.notes.append("attention core, ms a run: " + ", ".join(
                f"{b}{' kernel' if k else ''}={v!r}" for (b, k), v in sorted(
                    out.items(), key=lambda kv: (str(kv[0][0]), kv[0][1]))))
        ctx.attn_parts = out
    return ctx.attn_parts
