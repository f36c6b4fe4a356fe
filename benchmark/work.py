"""What a kernel has to do, computed from shapes: the operations and bytes
the ALGORITHM needs for one call, not what an implementation happens to
move. A kernel's roofline share is the least time the chip could take for
this work (the larger of operations over peak FLOP/s and bytes over peak
bytes/s, peaks from ``peaks.json``) over the time the trace shows.

One *objective pass* is one evaluation of a GLM's value and gradient:
margins ``m = X w`` and the contraction ``g = X^T r``.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json; "
            "add its published peaks with their source"
        )
    return table[device_kind]


def sparse_pass(rows: int, columns: int, nonzeros: int) -> tuple[float, float]:
    """(operations, bytes) of one objective pass over a sparse matrix.

    Each of the two directions reads every nonzero once as a 4-byte value
    and a 4-byte index (CSR for the margins, CSC for the contraction: the
    least any layout stores), reads one vector and writes the other. Two
    operations a nonzero a direction."""
    flops = 2.0 * 2.0 * nonzeros
    bytes_ = 2.0 * nonzeros * (4 + 4) + 2.0 * (rows + columns) * 4
    return flops, bytes_


def dense_pass(rows: int, columns: int, itemsize: int) -> tuple[float, float]:
    """(operations, bytes) of one objective pass over a dense matrix held
    in ``itemsize``-byte elements: the matrix is read once (a fused kernel
    computes margins and contraction from one resident tile), labels,
    offsets and weights are read, the coefficient vector is read and the
    gradient written."""
    flops = 2.0 * 2.0 * rows * columns
    bytes_ = float(rows) * columns * itemsize + 3.0 * rows * 4 + 2.0 * columns * 4
    return flops, bytes_


def least_seconds(flops: float, bytes_: float, device_kind: str,
                  dtype: str = "bfloat16") -> tuple[float, str]:
    """The least time one chip could take, and which peak bounds it."""
    p = peaks(device_kind)
    t_flops = flops / p["flops_per_s"][dtype]
    t_bytes = bytes_ / p["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops > t_bytes else (t_bytes, "memory")
