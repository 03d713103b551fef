"""Device time by model part: the vocabulary of parts, and the table that
says which part each operation of a compiled program belongs to.

Every jitted program of the models, the loss, the optimizer and the
serving engine opens ``jax.named_scope(<part>)`` where a part's work
happens (:data:`PARTS`).  A scope is trace-time metadata: it lands in the
``op_name`` of every operation traced under it
(``jit(step)/transpose(jvp(attention))/dot_general``), survives XLA's
passes on the fusion that operation ends up the root of, and changes no
instruction of the program.  :func:`program_parts` reads a compiled
program's optimized text back into ``{operation: (part, phase)}``; a
device trace names the same operations, so a reader sums a traced run's
device time by part (``benchmarks/lib/parts.py``).

Pure host code, like the rest of ``paddle_tpu.obs``: the text comes from
the caller (``jitted.lower(...).compile().as_text()``), never jax.
"""

from __future__ import annotations

import re
from typing import Dict, Tuple

__all__ = ["PARTS", "PHASES", "UNSCOPED", "parts_on_path", "part_of",
           "operation_key", "program_name", "program_parts"]

# what a scope may be called.  ``exit_gate`` is the looped model's
# (models/ouro.py: the norm and the gate that decide, after every pass,
# which tokens leave); every other name is shared by the families.
PARTS = ("embed", "norm", "attention", "kv_append", "mlp", "router",
         "experts", "shared_expert", "mixer", "head", "loss", "optimizer",
         "sampling", "exit_gate")
PHASES = ("forward", "backward", "recomputed")
UNSCOPED = "unscoped"

# jax wraps the OUTERMOST scope of a transformed function in the
# transform's name: ``transpose(jvp(mlp))``; ``jit(...)`` is a function's
# name, never a scope
_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap|checkpoint|remat|"
                      r"custom_jvp|custom_vjp)\((.*)\)$")


def parts_on_path(op_name: str) -> list:
    """The components of an ``op_name``'s ``/``-separated path that are
    names of :data:`PARTS` once the transforms' wrappers are peeled,
    outermost first."""
    found = []
    for comp in op_name.split("/"):
        m = _WRAPPER.match(comp)
        while m is not None:
            comp = m.group(1)
            m = _WRAPPER.match(comp)
        if comp in PARTS:
            found.append(comp)
    return found


def part_of(op_name: str) -> Tuple[str, str]:
    """``(part, phase)`` of an operation from the ``op_name`` jax gave
    it.  The part is the INNERMOST name of :data:`PARTS` on the path
    (:data:`UNSCOPED` where none is); the phase is ``recomputed`` under
    ``rematted_computation``, else ``backward`` under a ``transpose(``,
    else ``forward``."""
    named = parts_on_path(op_name)
    if "rematted_computation" in op_name:
        phase = "recomputed"
    elif "transpose(" in op_name:
        phase = "backward"
    else:
        phase = "forward"
    return (named[-1] if named else UNSCOPED), phase


def _closing(text: str, start: int) -> int:
    """Index of the ``)`` that closes the ``(`` at ``text[start]``."""
    depth = 0
    for i in range(start, len(text)):
        ch = text[i]
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return i
    raise ValueError(f"unbalanced parentheses in {text[:80]!r}")


def operation_key(line: str) -> str:
    """What an operation is known by on both sides: ``%name = type``,
    the HLO line up to its opcode.  A device trace names an event by the
    instruction's whole line, but not letter for letter as the compiled
    object prints it (a trace prints every operand's shape, no
    ``/*index=5*/`` marks, ``async-done`` where the text says
    ``slice-done``: looked at on a v5e trace, PERF.md section 3); the
    name and the result's type with its layout are the same on both
    sides, and differ between two compiles of one function at two
    shapes wherever the shape shows."""
    head, sep, rest = line.strip().partition(" = ")
    if not sep:
        return head
    if head.startswith("ROOT "):
        head = head[5:]
    end = _closing(rest, 0) + 1 if rest.startswith("(") \
        else (rest.find(" ") if " " in rest else len(rest))
    return f"{head} = {rest[:end]}"


# instructions that are no work on the device: a trace never names them
_NOT_RUN = ("parameter(", "constant(", "get-tuple-element(", "bitcast(",
            "tuple(")
_HEADER = re.compile(r"^(ENTRY )?%(\S+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([^\s,)]+)")


def program_name(hlo_text: str) -> str:
    """``jit_decode`` from ``HloModule jit_decode, ...``."""
    m = re.match(r"HloModule ([^\s,]+)", hlo_text)
    if m is None:
        raise ValueError("not an HLO module's text")
    return m.group(1)


def _operands(line: str, key: str) -> list:
    """Names of the instructions an instruction's line takes."""
    rest = line.split(key, 1)[1]
    start = rest.find("(")
    return re.findall(r"%([^\s,()]+)", rest[start:_closing(rest, start)])


def program_parts(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """``{operation_key: (part, phase)}`` for every instruction of a
    compiled program's optimized text that a device trace can name: the
    instructions of the entry computation and of every computation that
    is no fusion's body (a ``while``'s body and condition, a
    ``conditional``'s branches), but those that are no work on the
    device (parameters, constants, tuples and their elements).

    A fusion counts whole under its own ``op_name``, which XLA takes
    from the fusion's root, and where the fusion carries none under its
    body's ROOT's.  An instruction whose ``op_name`` names no part is
    data on its way somewhere (a stacked weight sliced out by a scan, a
    weight re-laid or fetched ahead of its matmul, a copy XLA put
    between two fusions): it counts under the part and phase of the
    instructions that take its result where they all agree, through
    tuples' elements and bitcasts, and is :data:`UNSCOPED` where they
    do not or nothing in its computation takes it."""
    comps, current = {}, None
    for line in hlo_text.splitlines():
        m = _HEADER.match(line)
        if m is not None:
            current = comps.setdefault(m.group(2), [])
            continue
        if current is not None and " = " in line and line.startswith(" "):
            current.append(line)
    fused, roots = set(), {}
    for name, lines in comps.items():
        for line in lines:
            if " fusion(" in line:
                m = _CALLS.search(line)
                if m is not None:
                    fused.add(m.group(1))
            if line.lstrip().startswith("ROOT "):
                m = _OP_NAME.search(line)
                roots[name] = m.group(1) if m else ""
    table = {}
    for name, lines in comps.items():
        if name in fused:
            continue
        own, users, keys = {}, {}, {}
        for line in lines:
            key = operation_key(line)
            ins = key.split(" = ")[0].lstrip("%")
            keys[ins] = key, line
            m = _OP_NAME.search(line)
            op_name = m.group(1) if m else ""
            if not op_name:
                m = _CALLS.search(line)
                if m is not None:
                    op_name = roots.get(m.group(1), "")
            own[ins] = part_of(op_name)
            for operand in _operands(line, key):
                users.setdefault(operand, []).append(ins)
        settled = {}

        def settle(ins):
            """The part of ``ins``: its own, or its takers' where it has
            none and they agree (None: they do not)."""
            if ins not in settled:
                settled[ins] = None         # a cycle cannot be: a guard
                if own[ins][0] != UNSCOPED:
                    settled[ins] = own[ins]
                else:
                    takers = {settle(u) for u in users.get(ins, ())}
                    if len(takers) == 1:
                        settled[ins], = takers
            return settled[ins]

        for ins, (key, line) in keys.items():
            if not line.split(key, 1)[1].lstrip().startswith(_NOT_RUN):
                table[key] = settle(ins) or own[ins]
    return table
