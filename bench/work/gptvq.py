"""FLOPs GPTVQ requires for one target matrix W (r=out, c=in), counted from
shapes (for a quantization-job cell; see PERF.md's open questions).

* Hessian: H = X^T X over the calibration tokens, 2 N c^2 (a tap shared
  by several targets is paid once; ``hessian`` is per tap).
* Inverse Cholesky factor of H: about c^3 (factor, invert, factor).
* EM codebook init: per iteration, every d-vector's Hessian-weighted
  distance to k centroids (3 d k) and the weighted centroid update (2 d):
  em_iters r c (3k + 2).
* Column sweep: the error feedback into the columns to the right, r c^2,
  and each d-vector's assignment, 3 r c k.
* Codebook update: per gradient step on tr((W-Q) H (W-Q)^T), the
  gradient 2 r c^2 and its scatter onto the codebooks, r c.
"""
from __future__ import annotations


def target_flops(r: int, c: int, *, k: int, em_iters: int,
                 update_iters: int) -> dict:
    return {"inverse": float(c) ** 3,
            "em_init": float(em_iters) * r * c * (3 * k + 2),
            "column_sweep": float(r) * c * c + 3.0 * r * c * k,
            "codebook_update": float(update_iters) * (2.0 * r * c * c
                                                      + r * c)}


def hessian_flops(n_tokens: int, c: int) -> float:
    return 2.0 * n_tokens * c * c
