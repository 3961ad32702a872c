"""Required work of one VQ dequant-matmul call: y (M, r) = x (M, c) @ W^T
for a GPTVQ-packed W (r=out, c=in).

FLOPs: 2 M r c. Bytes: the packed codes (``code_bits`` per ``d`` weights),
the int8 codebooks (k entries of d values per group) with their float32
scales, x read and y written in the activation type.
"""
from __future__ import annotations

from bench.spec import VQFormat
from bench.work import Work


def work(M: int, r: int, c: int, fmt: VQFormat,
         act_bytes: int = 2) -> Work:
    cg, rg = fmt.plan(r, c)
    n_codebooks = (c // cg) * (r // rg)
    codes = r * (c // fmt.d) * fmt.code_bits / 8
    codebooks = n_codebooks * (fmt.k * fmt.d * fmt.codebook_bits / 8 + 4)
    return Work(flops=2.0 * M * r * c,
                bytes=codes + codebooks + act_bytes * M * (c + r))
