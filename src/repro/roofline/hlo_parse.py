"""Parse collective traffic out of post-SPMD HLO text.

cost_analysis() does not expose collective bytes, so we sum operand/result
sizes of every collective instruction in ``compiled.as_text()``.

Wire-byte model per chip (ring algorithms, documented in EXPERIMENTS.md):
  all-reduce          2 × tensor size   (reduce-scatter + all-gather phases)
  all-gather          1 × result size   (each chip receives S - S/k ≈ S)
  reduce-scatter      1 × operand size
  all-to-all          1 × result size
  collective-permute  1 × result size
Async "-start" forms are counted once; "-done" ops are skipped.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(pred|[su](?:8|16|32|64)|bf16|f16|f32|f64|c64|c128)\[([0-9,]*)\]")

_COLL_RE = re.compile(
    r"=\s*(?P<result>\([^)]*\)|[^\s(]+)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<start>-start)?\((?P<args>[^)]*)\)"
)


def _bytes_of(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def top_collectives(hlo_text: str, k: int = 12):
    """The k largest collective instructions (wire bytes, op, result type) —
    the §Perf diagnosis tool: WHAT is being moved, not just how much."""
    found = []
    for m in _COLL_RE.finditer(hlo_text):
        op = m.group("op")
        result_b = _bytes_of(m.group("result"))
        args_b = _bytes_of(m.group("args"))
        wire = 2 * result_b if op == "all-reduce" else (
            args_b if op == "reduce-scatter" else result_b)
        found.append((wire, op, m.group("result")[:70]))
    found.sort(reverse=True)
    return found[:k]


def compiled_cost(compiled) -> Dict[str, float]:
    """FLOPs / bytes-accessed of a ``jax.jit(...).lower(...).compile()``
    object, via XLA's own cost_analysis — the real-cost feed for the
    kernel bandwidth model (roofline/kernel_model.py compares its analytic
    bytes against this).

    cost_analysis() returns one dict, read into
    ``{"flops": float, "bytes_accessed": float}``. On XLA:CPU
    ``bytes accessed`` counts every post-fusion dataflow edge (fusion-
    internal tiles included), so treat it as an UPPER bound on HBM traffic,
    not a measurement — the analytic model should come out at or below it.
    """
    ca = compiled.cost_analysis()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }


def collective_bytes(hlo_text: str) -> Tuple[int, Dict[str, int], Dict[str, int]]:
    """Returns (total_wire_bytes, wire_bytes_by_op, op_counts)."""
    by_op: Dict[str, int] = defaultdict(int)
    counts: Dict[str, int] = defaultdict(int)
    for m in _COLL_RE.finditer(hlo_text):
        op = m.group("op")
        result_b = _bytes_of(m.group("result"))
        args_b = _bytes_of(m.group("args"))
        if op == "all-reduce":
            wire = 2 * result_b
        elif op == "reduce-scatter":
            wire = args_b
        else:  # all-gather, all-to-all, collective-permute
            wire = result_b
        by_op[op] += wire
        counts[op] += 1
    return sum(by_op.values()), dict(by_op), dict(counts)
