"""Required work of one paged decode-attention call (one layer).

For each live slot b with ``context[b]`` cached rows (positions 0..pos):
the K and V rows it must read in the pool's format, q read and the output
written; FLOPs 4 H hd context (q k^T and p v). Pages past a slot's
position, and slots that are not decoding, are not required work.
"""
from __future__ import annotations

from bench.work import Work


def work(context: list[int], n_heads: int, n_kv: int, hd: int,
         kv_bytes: int = 4, act_bytes: int = 2) -> Work:
    rows = float(sum(context))
    live = len(context)
    return Work(flops=4.0 * n_heads * hd * rows,
                bytes=2.0 * rows * n_kv * hd * kv_bytes
                + 2.0 * live * n_heads * hd * act_bytes)
