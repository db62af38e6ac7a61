"""What each executed HLO instruction of a compiled program does.

A device trace names each op by its HLO instruction (``fusion.132``,
``ell_gimv.3``, ``all-to-all.1``); a fusion's name does not say that it
gathers or scatters.  ``op_kinds`` reads the optimized HLO text of the
compiled step (``jitted.lower(...).compile().as_text()``) and maps every
instruction name to the opcodes it runs: its own, and for a fusion those of
its fused computations as well.
"""
from __future__ import annotations

import re

_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][a-z0-9\-]*)\((.*)$")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_HEAD = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")


def module_name(text: str) -> str | None:
    """The program's module name (``jit_step``), as a trace's ``XLA Modules``
    line names it."""
    m = _MODULE.match(text)
    return m.group(1) if m else None


def op_kinds(text: str) -> dict[str, frozenset[str]]:
    """instruction name -> the opcodes it runs."""
    own: dict[str, list[tuple[str, str, str | None]]] = {}
    comp = None
    for line in text.splitlines():
        if line and not line[0].isspace():
            head = _HEAD.match(line)
            comp = head.group(1) if head else None
            if comp is not None:
                own.setdefault(comp, [])
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        called = _CALLS.search(m.group(3)) if m.group(2) == "fusion" else None
        own[comp].append((m.group(1), m.group(2), called.group(1) if called else None))

    memo: dict[str, frozenset[str]] = {}

    def inside(c: str) -> frozenset[str]:
        if c not in memo:
            memo[c] = frozenset()  # guards a (malformed) cycle
            ops = set()
            for _, op, called in own.get(c, ()):
                ops.add(op)
                if called:
                    ops |= inside(called)
            memo[c] = frozenset(ops)
        return memo[c]

    out = {}
    for instrs in own.values():
        for name, op, called in instrs:
            out[name] = frozenset({op} | (inside(called) if called else set()))
    return out
