"""Operations and bytes a kernel call needs, and the least time a chip takes for them.

The count is of the work the exact float32 contract requires, whatever
kernel computes it:

  score + top-K of Q query rows against a [T, D] float32 table, K kept:
    flops = 2 Q T D                      (the [Q, D] x [D, T] product)
    bytes = 4 T D + 4 Q D + 8 Q K        (read the table and the queries once,
                                          write K scores and K int32 ids a row)

Q counts real query rows, not the padding a bucketed batch adds. A kernel
that reads fewer table bytes while staying exact changes this count, and
comes with a benchmark change that restates it.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def topk_work(q: int, t: int, d: int, k: int) -> Tuple[float, float]:
    """(flops, bytes) of one exact float32 score + top-K call."""
    return 2.0 * q * t * d, 4.0 * t * d + 4.0 * q * d + 8.0 * q * k


def least_seconds(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The larger of the compute bound and the memory bound."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def peaks_for(device_kind: str, path: Path = PEAKS) -> Dict[str, float]:
    """Published peaks of one chip of this kind; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]
