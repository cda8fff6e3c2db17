"""Byte counts read from compiled HLO instruction text.

A TPU profiler trace names each device operation by its full HLO
instruction, e.g.::

    %_call.3 = f32[80,784,1158]{2,1,0:T(8,128)} custom-call(
        f32[80,784,1158]{...} %pad.5, f32[80,784,1158]{...} %pad.5, ...),
        custom_call_target="tpu_custom_call", ...

so the shapes a kernel reads and writes come with its time. The counts are
logical bytes (the product of the dimensions times the item size): tile
padding of the layout is not counted, so a share of the HBM rate computed
from them is a lower bound of what the memory system did.
"""

from __future__ import annotations

import re

ITEM_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s4": 0.5, "u4": 0.5,
}
SHAPE_RE = re.compile(r"\b(" + "|".join(sorted(ITEM_BYTES, key=len, reverse=True))
                      + r")\[([0-9,]*)\]")
OPCODE_RE = re.compile(r"=\s*(?:\([^=]*?\)|\S+)\s+([a-z][a-z0-9\-]*)\(")
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather", "reduce-scatter",
               "all-to-all", "collective-broadcast")
CONTAINERS = ("while", "conditional", "call")


def shape_bytes(text: str) -> int:
    """Logical bytes of every array shape written in ``text``."""
    total = 0.0
    for dtype, dims in SHAPE_RE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * ITEM_BYTES[dtype]
    return int(total)


def instruction_name(text: str) -> str:
    """``%_call.3 = ...`` -> ``_call.3``; other names are returned as given."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def opcode(text: str) -> str:
    """The HLO opcode of an instruction's text ('' where it has none)."""
    m = OPCODE_RE.search(text)
    return m.group(1) if m else ""


def is_kernel(text: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in text


def kind(text: str) -> str:
    """'kernel' (a Pallas/Mosaic call), 'collective', 'container' (an op
    whose span holds other ops: a loop, a branch, a call) or 'glue'."""
    if is_kernel(text):
        return "kernel"
    op = opcode(text)
    if any(op.startswith(c) for c in COLLECTIVES):
        return "collective"
    if op in CONTAINERS:
        return "container"
    return "glue"


def _operand_list(text: str, op: str) -> str:
    """The text between ``op(`` and its matching ``)``."""
    start = text.index(f" {op}(") + len(op) + 2
    depth = 1
    for i in range(start, len(text)):
        c = text[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
            if depth == 0:
                return text[start:i]
    raise ValueError(f"unbalanced operand list in {text[:200]!r}")


def _split_top(s: str) -> list[str]:
    parts, depth, cur = [], 0, []
    for c in s:
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        if c == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(c)
    if cur:
        parts.append("".join(cur))
    return [p.strip() for p in parts if p.strip()]


def distinct_buffer_bytes(text: str) -> int:
    """Bytes of an instruction's distinct operand arrays plus its results.

    An operand passed more than once (the three-slab halo passes one array
    as three operands) is counted once, so the count is what the call must
    at least move, never more."""
    op = opcode(text)
    lhs = text.split(" = ", 1)[1]
    result = lhs[: lhs.index(f" {op}(")]
    seen: dict[str, int] = {}
    for operand in _split_top(_operand_list(text, op)):
        name = operand.split()[-1]
        seen.setdefault(name, shape_bytes(operand))
    return shape_bytes(result) + sum(seen.values())


def result_bytes(text: str) -> int:
    """Bytes a collective moves: its result. The async ``-start`` form
    returns a tuple of (operand, result, contexts); the largest array of it
    is the result of a permute."""
    op = opcode(text)
    lhs = text.split(" = ", 1)[1]
    result = lhs[: lhs.index(f" {op}(")]
    if op.endswith("-start"):
        sizes = [shape_bytes(f"{d}[{s}]") for d, s in SHAPE_RE.findall(result) if s]
        return max(sizes, default=0)
    return shape_bytes(result)
