"""The work a kernel launch needs, and the least time the chip could take.

A launch of ``B`` active queries over a node of ``N`` rows of width ``d``,
with ``W`` auth-mask words and ``P`` predicate words per row, needs

- operations: ``2 * B * N * d`` (the query-row products);
- bytes: every node row read once, ``N * (4d + 4W + 4P)``, every query row
  read once, ``B * (4d + 4W + 4P + 4)`` with its bound, and the answers
  written, ``B * k * 8``.

That counts each node row once whatever the tiling, so the share reads the
same work whatever implements it.  The least time is the larger of the
operations over the peak rate and the bytes over the memory bandwidth of
the device, from ``peaks.json``.
"""
from __future__ import annotations

import json
import os
from typing import Dict

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; add them with their source")
    return table[device_kind]


def flops(span) -> float:
    return 2.0 * span.rows * span.n * span.dim


def bytes_moved(span) -> float:
    row = 4 * span.dim + 4 * span.w + 4 * span.p
    return span.n * row + span.rows * (row + 4) + span.rows * span.k * 8


def least_seconds(span, peak: Dict[str, float]) -> float:
    return max(flops(span) / peak["flops_per_s"],
               bytes_moved(span) / peak["hbm_bytes_per_s"])
