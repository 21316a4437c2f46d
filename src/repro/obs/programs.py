"""Named device programs, and the scope of each of their operations.

A device trace names each XLA module run (``jit_fed_round(<id>)``) and each
operation by its HLO instruction (``%fusion.12 = ...``), but on the TPU an
operation event carries no ``op_name``: nothing in the trace says which
``jax.named_scope`` an operation came from. This registry is how a reader of
the trace learns it.

``register(jitted)`` wraps a jitted function; the wrapper records the
abstract arguments (shape, dtype, sharding) of its first call and otherwise
calls straight through. ``op_scopes()``, called only when asked (after a
measured window, never inside one), lowers and compiles each recorded program
again from those arguments and reads every instruction's ``op_name`` out of
the compiled HLO. With JAX's persistent compilation cache on, that compile is
a read of the cache and gives back the very executable that ran.

The module imports without JAX; JAX is imported by the calls that need it.
"""
from __future__ import annotations

import re
import threading
from typing import Callable, Dict, Optional

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,)]+)")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)

_lock = threading.Lock()
_programs: Dict[str, "Program"] = {}


class Program:
    """A jitted function that remembers the abstract arguments of its first call."""

    def __init__(self, fn: Callable, name: str):
        self.fn = fn
        self.name = name
        self.args: Optional[tuple] = None

    def __call__(self, *args):
        if self.args is None:
            self.args = _abstract(args)
        return self.fn(*args)


def _abstract(args: tuple) -> tuple:
    import jax

    def leaf(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding,
                                        weak_type=x.aval.weak_type)
        return x  # host values are kept: they lower as they are

    return jax.tree_util.tree_map(leaf, args)


def register(jitted: Callable, name: Optional[str] = None) -> Program:
    """Wrap ``jitted`` (a ``jax.jit`` of a named function) as a :class:`Program`.

    The program is kept under its function's name; registering another program
    of that name replaces it, so the registry holds the newest build of each."""
    prog = Program(jitted, name or jitted.__name__)
    with _lock:
        _programs[prog.name] = prog
    return prog


def scope_table(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` of every instruction of an HLO module's
    text; names lose their ``%``. A fusion the compiler made without an
    ``op_name`` takes that of the last instruction of its fused computation
    that has one (the nearest to its root); an instruction with neither reads
    ``""``."""
    comps: Dict[str, list] = {}  # computation -> [(name, op_name, called computation)]
    body: list = []
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            body = comps.setdefault(head.group(1), [])
            continue
        inst = _INSTRUCTION.match(line)
        if inst:
            op, calls = _OP_NAME.search(line), _CALLS.search(line)
            body.append((inst.group(1), op.group(1) if op else "",
                         calls.group(1) if calls else None))

    def inherited(comp: str, seen: frozenset) -> str:
        for _, op, calls in reversed(comps.get(comp, ())):
            if not op and calls and calls not in seen:
                op = inherited(calls, seen | {calls})
            if op:
                return op
        return ""

    return {name: op or (inherited(calls, frozenset({calls})) if calls else "")
            for insts in comps.values() for name, op, calls in insts}


def op_scopes() -> Dict[str, Dict[str, str]]:
    """``{module name: {instruction name: op_name}}`` for every registered
    program that has run, by compiling it again (see the module docstring).
    The module name is the compiled module's own (``jit_fed_round``), which a
    trace shows with the run's id appended (``jit_fed_round(<id>)``)."""
    with _lock:
        progs = list(_programs.values())
    out: Dict[str, Dict[str, str]] = {}
    for prog in progs:
        if prog.args is None:
            continue
        text = prog.fn.lower(*prog.args).compile().as_text()
        found = _MODULE.search(text)
        out[found.group(1) if found else "jit_" + prog.name] = scope_table(text)
    return out
