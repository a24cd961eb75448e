"""Algorithmic work of a served dispatch: operations and bytes from the
algorithm's own sizes, never from the staged tables.

Inputs are each graph's true n, the components k its tier serves, the
signal rows requested (not the quantized or padded rows), the filters
in a bank and the family.  So packing, padding and the kernel's
implementation can change the measured time but never these counts.

Per signal row and factor the walk costs 6 flops for a G factor (the
paper's count for an extended Givens transform) and 2 for a T factor (a
shear's multiply-add; a scaling counts the same).  A tier request runs
two legs (analysis, synthesis) and one diagonal scale of n; a bank
request runs one analysis and, per filter, a scale and a synthesis.
Bytes: each request's signal read once and its answers written once (4
bytes an entry), and per graph in the dispatch its factor entries read
once per leg (G: i, j, c, s, sigma; T: kind, i, j, a; 4 bytes each) and
its gains read once (n per filter).
"""
from __future__ import annotations

from typing import Iterable, NamedTuple

FLOPS_PER_FACTOR = {"sym": 6, "general": 2}
BYTES_PER_FACTOR = {"sym": 20, "general": 16}
BYTES_PER_ENTRY = 4


class Request(NamedTuple):
    graph: int          # fleet position (groups the table reads)
    n: int              # true graph size
    k: int              # components the tier serves
    rows: int           # signal rows requested
    filters: int        # 0 for a tier request, F for a bank request


def dispatch_work(family: str, requests: Iterable[Request]) -> tuple:
    """(flops, bytes) the algorithm needs for one coalesced dispatch."""
    per = FLOPS_PER_FACTOR[family]
    flops = bytes_ = 0
    graphs = {}
    for r in requests:
        outs = max(r.filters, 1)
        walk = per * r.k
        flops += r.rows * (walk + outs * (r.n + walk))
        bytes_ += BYTES_PER_ENTRY * r.rows * r.n * (1 + outs)
        graphs[r.graph] = (r.n, r.k, outs)
    for n, k, outs in graphs.values():
        bytes_ += 2 * BYTES_PER_FACTOR[family] * k + BYTES_PER_ENTRY * n * outs
    return flops, bytes_


def least_seconds(flops: float, bytes_: float, peaks: dict) -> tuple:
    """(seconds, bound): the larger of flops over the bf16 peak and
    bytes over the HBM bandwidth, and which of the two it is."""
    t_ops = flops / float(peaks["bf16_flops_per_s"])
    t_mem = bytes_ / float(peaks["hbm_bytes_per_s"])
    return (t_ops, "flops") if t_ops >= t_mem else (t_mem, "bytes")
