"""Which operator and kernel each instruction of a compiled program runs.

The executor evaluates every plan node under a ``jax.named_scope`` of its
operator (``scan``, ``semi_join``, ``freq_join`` with ``pregroup`` inside
it, ``final_agg``), and ``kernels/ops.py`` opens one per kernel
(``freq_join``, ``semi_join``, ``segment_sum``, ``group_by_sum``,
``weighted_percentile``, ``sort`` and ``search`` inside the sort-based
freq-join, ``scatter`` and ``gather`` inside the dense one).  JAX writes
the scopes into each HLO instruction's ``op_name`` metadata between
transform names (``jit(...)``, ``vmap()``), control-flow markers
(``while``, ``body``) and, last, the primitive's own name; XLA keeps the
metadata through optimisation.

``scope_table`` turns the optimised HLO text of a compiled program
(``Compiled.as_text()``, whose instruction names are the ones a device
trace prints) into ``{instruction: scope path}``, the path being the
program's own scopes in nesting order, e.g.
``{"while.134": "freq_join/freq_join/search"}``.  An instruction without a
scope of its own takes the common scope of what it fuses, else the scope
of the instruction that calls its computation (a ``while`` body's
instructions take the ``while``'s).  Instructions left with no scope are
left out of the table.
"""

from __future__ import annotations

import re

# every jax.named_scope this package opens inside a compiled program
SCOPES = frozenset({
    "scan", "semi_join", "freq_join", "pregroup", "final_agg",
    "segment_sum", "group_by_sum", "weighted_percentile", "sort", "search",
    "scatter", "gather",
})

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^\s(]+) \(")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply)=%([^\s,)}]+)")


def scope_path(op_name: str) -> str:
    """The package's scopes in an ``op_name``, outermost first: the last
    component (the primitive) and every name outside ``SCOPES`` dropped."""
    parts = op_name.split("/")
    if "(" not in parts[-1]:
        parts = parts[:-1]
    return "/".join(p for p in parts if p in SCOPES)


def _common(paths) -> str:
    out = []
    for parts in zip(*(p.split("/") for p in paths)):
        if any(x != parts[0] for x in parts):
            break
        out.append(parts[0])
    return "/".join(out)


def scope_table(hlo_text: str) -> dict[str, str]:
    """``{instruction name: scope path}`` of an optimised HLO module's text,
    for every instruction that resolves to a scope (module docstring)."""
    comps: dict[str, list[tuple[str, str, list[str]]]] = {}
    entry = None
    current = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            current = comps.setdefault(m.group(1), [])
            if line.startswith("ENTRY"):
                entry = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None or current is None:
            continue
        op = _OP_NAME.search(line)
        current.append((m.group(1), scope_path(op.group(1)) if op else "",
                        _CALLS.findall(line)))
    if entry is None:
        return {}

    inner: dict[str, set[str]] = {}

    def scopes_inside(comp: str) -> set[str]:
        # the scopes of a computation's instructions and of those it calls
        if comp not in inner:
            found = set()
            for _, own, callees in comps.get(comp, ()):
                if own:
                    found.add(own)
                for c in callees:
                    found |= scopes_inside(c)
            inner[comp] = found
        return inner[comp]

    table: dict[str, str] = {}
    seen: set[str] = set()
    stack = [(entry, "")]
    while stack:
        comp, inherited = stack.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for name, own, callees in comps.get(comp, ()):
            scope = own or _common(
                set().union(*(scopes_inside(c) for c in callees))) \
                or inherited
            if scope:
                table[name] = scope
            stack.extend((c, scope) for c in callees)
    return table
