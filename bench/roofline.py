"""Operations and bytes of the system's kernels, from their shapes.

A kernel call moves at least every operand once in and every result once
out, so the bytes below are a lower bound on its memory traffic, and the
least time the chip could take is ``max(bytes / peak HBM bandwidth,
flops / peak compute)``.  A kernel's roofline share is that least time over
its measured time: it cannot pass 100% unless the bytes are counted too
high or the time leaves work out.

The join kernels (``hash_join_*``) do equality compares on the vector unit,
for which the chip publishes no peak, and no matrix work: their bound is
memory.  The closure kernels are boolean matrix products on the matrix
unit (``closure_step``) and a matrix-vector product (``closure_descendants``).

:func:`hlo_bytes` reads the same lower bound off a call's HLO text as the
profiler records it (result and operand shapes), so the trace reduction
needs no knowledge of the plan.
"""
from __future__ import annotations

import re
from typing import Dict

ITEM_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
              "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
              "f64": 8}
_SHAPE = re.compile(r"\b(%s)\[([0-9,]*)\]" % "|".join(ITEM_BYTES))

I32 = 4
F32 = 4


def hash_join_match(m: int, nv: int, n: int) -> Dict[str, int]:
    """Candidate matrix of ``m`` binding rows of ``nv`` variables against
    ``n`` KB rows: int32 bindings and validity, four int32 KB rows, an int8
    ``[m, n]`` result."""
    return {"bytes": I32 * (m * nv + m + 4 * n) + m * n, "flops": 0}


def hash_join_count(m: int, nv: int, n: int) -> Dict[str, int]:
    """Per-binding-row match counts: the match operands in, ``[m, 1]`` out."""
    return {"bytes": I32 * (m * nv + m + 4 * n + m), "flops": 0}


def hash_join_emit(m: int, nv: int, n: int, out_cap: int) -> Dict[str, int]:
    """Source pairs of the first ``out_cap`` matches: the match operands and
    the ``[m, 1]`` offsets in, two ``[1, out_cap + 1]`` int32 rows out."""
    return {"bytes": I32 * (m * nv + m + 4 * n + m + 2 * (out_cap + 1)),
            "flops": 0}


def hash_join_probe(m: int, nv: int, k: int, out_cap: int) -> Dict[str, int]:
    """Probe re-check and compaction: bindings, validity and four gathered
    ``[m, k]`` candidate blocks in; ``[1, out_cap + 1]`` sources and
    ``[m, 1]`` counts out."""
    return {"bytes": I32 * (m * nv + m + 4 * m * k + out_cap + 1 + m),
            "flops": 0}


def closure_step(n: int) -> Dict[str, int]:
    """One boolean squaring of an ``[n, n]`` float32 reach matrix."""
    return {"bytes": F32 * 3 * n * n, "flops": 2 * n ** 3}


def closure_descendants(n: int, out_cap: int) -> Dict[str, int]:
    """Fused last squaring against the root's column, compacted: the
    ``[n, n]`` matrix and ``[1, n]`` column in, ``out_cap + 2`` int32 out."""
    return {"bytes": F32 * (n * n + n) + I32 * (out_cap + 2),
            "flops": 2 * n * n}


KB_JOIN_KERNELS = ("hash_join_match", "hash_join_count", "hash_join_emit",
                   "hash_join_probe")

KERNELS = {f.__name__: f for f in (hash_join_match, hash_join_count,
                                   hash_join_emit, hash_join_probe,
                                   closure_step, closure_descendants)}


def hlo_bytes(text: str) -> int:
    """Bytes of every array shape written in one instruction's HLO text
    (its results and its operands)."""
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += ITEM_BYTES[dtype] * n
    return total


def least_seconds(cost: Dict[str, int], peaks: Dict[str, float]) -> float:
    return max(cost["bytes"] / peaks["hbm_bytes_per_s"],
               cost["flops"] / peaks["bf16_flops_per_s"])
