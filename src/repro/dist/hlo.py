"""Collective wire bytes read off compiled HLO.

:func:`parse_collective_bytes` is the *measured* side of the halo wire
models (:func:`repro.dist.measured_collective_permute_bytes`,
``benchmarks/fig10_scaling.py``, ``benchmarks/fig13_multifield.py``): it
sums the per-chip bytes every collective in a post-SPMD HLO module moves.
"""

from __future__ import annotations

import re

_SHAPE_RE = re.compile(r"\b(f64|f32|bf16|f16|s32|u32|s16|u16|s8|u8|pred|s64|u64)\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))  # [n_groups, group_size]<=[total]
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def parse_collective_bytes(hlo_text: str) -> dict:
    """Per-chip wire bytes per collective kind, from post-SPMD HLO.

    Result types appear on the LHS of each instruction; ring cost model:
      all-reduce(B, g):        2B(g-1)/g
      all-gather(out B):        B(g-1)/g
      reduce-scatter(out B,g):  B(g-1)       (input = B*g)
      all-to-all(B, g):         B(g-1)/g
      collective-permute(B):    B
    """
    totals: dict[str, float] = {}
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "= " not in line:
            continue
        m = re.search(
            r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
            r"(?:-start|-done)?\(", line)
        if not m:
            continue
        if "-done(" in line:
            continue  # paired with -start; count once
        kind = m.group(1)
        lhs = line.split(m.group(0))[0]
        res_shapes = _SHAPE_RE.findall(lhs)
        if "-start(" in line:
            # Async form: the LHS tuple holds the aliased input AND the
            # output plus u32[] scalar contexts — summing double-counts.
            # The OUTPUT is what the cost model wants: the largest tensor
            # entry for permute/all-reduce/all-to-all (in==out) and
            # all-gather (out is bigger); the smallest for reduce-scatter
            # (out is 1/g of the input). Scalar contexts are dropped.
            tensors = [_shape_bytes(d, s) for d, s in res_shapes if s]
            pick = min if kind == "reduce-scatter" else max
            res_bytes = pick(tensors) if tensors else 0
        else:
            res_bytes = sum(_shape_bytes(d, s) for d, s in res_shapes)
        g = _group_size(line)
        if kind == "collective-permute":
            # Point-to-point: moves its result bytes; no replica_groups
            # (HLO encodes source_target_pairs instead, so g is meaningless).
            moved = float(res_bytes) if "source_target_pairs" in line else 0.0
        elif g <= 1:
            moved = 0.0
        elif kind == "all-reduce":
            moved = 2.0 * res_bytes * (g - 1) / g
        elif kind == "all-gather":
            moved = res_bytes * (g - 1) / g
        elif kind == "reduce-scatter":
            moved = float(res_bytes) * (g - 1)
        else:  # all-to-all
            moved = res_bytes * (g - 1) / g
        totals[kind] = totals.get(kind, 0.0) + moved
        counts[kind] = counts.get(kind, 0) + 1
    totals["total"] = sum(v for k, v in totals.items() if k != "total")
    return {"bytes": totals, "counts": counts}
