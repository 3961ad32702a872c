"""Jit'd public wrappers around the Pallas kernels.

``use_pallas`` selects the execution path:
  * True  — pl.pallas_call, compiled for the TPU. ``interpret`` defaults
            to False: a CPU caller (the tests) must ask for interpret mode
            explicitly, so no TPU caller lands in it by omission.
  * False — the pure-XLA fallback (used by the multi-pod dry-run: Pallas TPU
            lowering is unavailable on the host-CPU dry-run platform).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.vq_linear import VQLinear
from repro.kernels import ref
from repro.kernels.vq_assign import vq_assign
from repro.kernels.vq_dequant_matmul import vq_dequant_matmul


def vql_matmul(x: jax.Array, vql, *, use_pallas: bool = True,
               interpret: bool = False, tile_m: int = 128, tile_n: int = 128,
               tile_k: int = 256) -> jax.Array:
    """y = x @ W^T for a VQLinear, fused on TPU.

    Blockwise-normalized layouts (scale_block != 0) are folded here: the
    scale plane is pre-expanded by core/vq_linear.prepare_fused and applied
    inside the kernel tile — no layout is rejected anymore. Accepts an
    already-prepped FusedVQLinear directly (serving path: fold once at
    engine load instead of per call)."""
    from repro.core import vq_linear as vql_mod

    if isinstance(vql, VQLinear):
        vql = vql_mod.prepare_fused(vql)
        assert isinstance(vql, vql_mod.FusedVQLinear), \
            "rows not packed on word boundaries — no fused layout"
    if use_pallas:
        return vq_dequant_matmul(
            x, vql.words, vql.codebooks_f, vql.scales,
            d=vql.d, k_c=vql.k,
            container_bits=packing.container_bits(vql.code_bits),
            rows_per_band=vql.rows_per_band, group_cols=vql.group_cols,
            scale_block=vql.scale_block, tile_m=tile_m,
            tile_n=min(tile_n, vql.r), tile_k=min(tile_k, vql.c),
            interpret=interpret)
    return ref.vq_dequant_matmul_ref(
        x, vql.words, vql.codebooks_f, vql.scales, d=vql.d,
        code_bits=vql.code_bits, rows_per_band=vql.rows_per_band,
        group_cols=vql.group_cols, scale_block=vql.scale_block)


def paged_attention(q, k_pool, v_pool, page_table, pos, *,
                    k_scale=None, v_scale=None,
                    k_codebook=None, v_codebook=None,
                    use_pallas: bool = True, interpret: bool = False):
    """Fused paged-attention decode: one query token per slot attends over
    its page-table-mapped KV blocks (kpos <= pos masking) without
    materializing the logical per-slot view. q (B, H, hd) -> (B, H, hd).

    ``k_scale``/``v_scale`` mark a quantized pool (int8/int4 code pages +
    per-row per-kv-head f32 scales): the Pallas path DMAs code pages and
    their scale tiles and dequantizes in VMEM; the XLA path dequantizes
    the gathered pages in the oracle. ``k_codebook``/``v_codebook`` mark
    a VQ pool (packed 4-bit index pages + frozen per-kv-head codebooks):
    the Pallas path keeps the codebook tile resident in VMEM and does
    the table lookup there. All paths share kernels/kv_quant.py."""
    if use_pallas:
        from repro.kernels.paged_attention import paged_attention_tpu
        return paged_attention_tpu(q, k_pool, v_pool, page_table, pos,
                                   k_scale=k_scale, v_scale=v_scale,
                                   k_codebook=k_codebook,
                                   v_codebook=v_codebook,
                                   interpret=interpret)
    return ref.paged_attention_ref(q, k_pool, v_pool, page_table, pos,
                                   k_scale=k_scale, v_scale=v_scale,
                                   k_codebook=k_codebook,
                                   v_codebook=v_codebook)


def assign(x, hw, codebook, *, use_pallas: bool = True,
           interpret: bool = False, tile_n: int = 1024):
    if use_pallas:
        n = x.shape[0]
        t = min(tile_n, n)
        while n % t != 0:
            t -= 1
        return vq_assign(x, hw, codebook, tile_n=t, interpret=interpret)
    return ref.vq_assign_ref(x, hw, codebook)
