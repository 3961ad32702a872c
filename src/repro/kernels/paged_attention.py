"""Fused paged-attention decode Pallas TPU kernel.

One query token per slot (S == 1 decode) attends over that slot's paged
KV blocks *in place*: the per-slot page table rides in as a scalar-prefetch
operand, so the k/v BlockSpec index maps resolve ``page_table[slot, page]``
to a physical block row of the shared pool and the DMA engine streams
exactly the pages the slot owns — the (B, n_pages*page_size, KV, hd)
logical view the XLA gather path materializes per layer never exists.

Grid (B, n_pages): one program per (slot, logical page), with the page
dimension innermost so the online-softmax running max/sum/acc live in VMEM
scratch across pages (same structure as kernels/flash_attention.py). A
program DMAs the page's whole ``(page_size, KV, cols)`` tile — the pool's
full trailing dims, which is what the TPU tiling accepts for any KV — and
flattens it to ``(page_size*KV, cols)`` rows. All H query heads score
against all of those rows in one MXU matmul; a head mask (row head ==
column kv head) keeps each query head on its own kv head's rows, so GQA
needs no materialized head expansion and no per-head slicing.

Packed formats are decoded without interleaving lanes in VMEM. Instead the
head dim is split into P "pieces" by ``dim % P`` outside the kernel: P = 2
for int4 (low/high nibble = even/odd dims), P = 4 for vq2 (nibble n, vector
element e -> dim 4j + 2n + e), P = 1 otherwise. Each piece of the code tile
decodes elementwise, the scores are the sum of per-piece matmuls, and the
output comes back piece-major and is re-interleaved outside the kernel.

Quantized pools (KVQuantSpec bits 8/4, kernels/kv_quant.py): the pools hold
int8 code pages (int4 packed two codes per byte along the head dim) plus
per-row per-kv-head f32 scales. The scale plane is viewed as one
``(1, page_size*KV)`` row per block — a free reshape of the pool — whose
index map reads the SAME scalar-prefetched page table as k/v, so a program
DMAs its page's codes and the matching scale row together. A per-row scale
factors out of the dot product, so the kernel multiplies the K scales into
the scores and the V scales into the softmax weights; the decoded values
are the same ``kv_quant`` expressions (sign-extended nibbles, codebook
entries) the oracle and the gather path use.

VQ pools (KVQuantSpec mode "vq2"): pages hold packed 4-bit codebook
indices over d=2 vectors along the head dim. The frozen (KV, 16, 2)
codebooks ride in as a page-invariant ``(32, KV, 1)`` tile (one column of
kv-head values per codebook entry), so the lookup is a 16-way select per
piece — a table lookup with no gather and no one-hot tensor.

Masking is the serving invariant ``kpos <= pos[slot]`` over *logical*
positions: stale rows in recycled blocks, the tail of the slot's last page,
the reserved scratch block 0 (where inactive slots' page-table entries
point), and table rows past the slot's depth are all strictly above
``pos`` and never contribute. Stale *scales* ride the same masked rows:
they decode stale codes to finite garbage whose scores die at the mask,
exactly like stale fp16 keys. An idle slot (pos == 0, table all-scratch)
attends exactly one scratch row — defined output, discarded by the engine.

``kernels/ref.py:paged_attention_ref`` is the pure-XLA oracle (same
``kv_quant`` decode on the gathered view);
``tests/kernels/test_paged_attention.py`` is the differential harness and
``tests/kernels/test_tpu_compile.py`` compiles it for a v5e chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import kv_quant

NEG_INF = -1e30


def _pieces(kv_bits) -> int:
    """Head-dim pieces a stored byte column decodes into (see module doc)."""
    if kv_bits == kv_quant.VQ_BITS:
        return 2 * kv_quant.VQ_D
    return 2 if kv_bits == 4 else 1


def _decode_pieces(codes, cb_ref, kv_bits):
    """(page_size, KV, cols) stored tile -> list of P (page_size, KV, cols)
    f32 pieces, piece i holding head dims ``i::P`` (scales not applied)."""
    if kv_bits == kv_quant.PASSTHROUGH_BITS or kv_bits == 8:
        return [codes.astype(jnp.float32)]
    c32 = codes.astype(jnp.int32)
    if kv_bits == 4:
        # kv_quant.unpack_int4: sign-extended low / high nibble
        lo = jnp.left_shift(c32, 28) >> 28
        hi = c32 >> 4
        return [lo.astype(jnp.float32), hi.astype(jnp.float32)]
    # vq2 (kv_quant.unpack_vq2 + vq_dequant_rows): unsigned nibbles index
    # the kv head's codebook; entry (c, e) is a (KV, 1) column
    byte = c32 & 0xFF
    out = []
    for nib in (byte & 0x0F, (byte >> 4) & 0x0F):
        for e in range(kv_quant.VQ_D):
            val = jnp.zeros(codes.shape, jnp.float32)
            for c in range(kv_quant.VQ_K):
                val = jnp.where(nib == c, cb_ref[c * kv_quant.VQ_D + e][None],
                                val)
            out.append(val)
    return out


def _kernel(table_ref, pos_ref, q_ref, rowkv_ref, colinfo_ref, k_ref, v_ref,
            *rest, scale, page_size, n_pages, kv_bits):
    quantized = kv_bits != kv_quant.PASSTHROUGH_BITS
    vq = kv_bits == kv_quant.VQ_BITS
    rest = list(rest)
    ks_ref = vs_ref = kcb_ref = vcb_ref = None
    if quantized:
        ks_ref, vs_ref = rest.pop(0), rest.pop(0)
    if vq:
        kcb_ref, vcb_ref = rest.pop(0), rest.pop(0)
    o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    pg = pl.program_id(1)
    n_pieces = q_ref.shape[1]

    @pl.when(pg == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def rows(tile):   # (page_size, KV, cols) -> (page_size*KV, cols)
        return tile.reshape(-1, tile.shape[-1])

    kp = [rows(t) for t in _decode_pieces(k_ref[0], kcb_ref, kv_bits)]
    vp = [rows(t) for t in _decode_pieces(v_ref[0], vcb_ref, kv_bits)]

    s = None
    for i in range(n_pieces):
        si = jax.lax.dot_general(
            q_ref[0, i].astype(jnp.float32), kp[i],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        s = si if s is None else s + si
    if quantized:
        s = s * ks_ref[0]                    # per-row K scales
    s = s * scale
    # column j of the flattened page is (row j // KV, kv head j % KV); the
    # head mask pins each query head to its kv head's rows, and the logical
    # position mask is the single serving mask: scratch block 0, recycled-
    # block staleness (codes AND scales) and the last-page tail all have
    # kpos > pos and die here
    kpos = pg * page_size + colinfo_ref[0:1, :]
    valid = (rowkv_ref[...] == colinfo_ref[1:2, :]) & (kpos <= pos_ref[b])
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if quantized:
        p = p * vs_ref[0]                    # per-row V scales
    for i in range(n_pieces):
        acc_scr[i] = acc_scr[i] * alpha + jax.lax.dot_general(
            p, vp[i], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(pg == n_pages - 1)
    def _done():
        inv = 1.0 / jnp.maximum(l_scr[...], 1e-30)
        for i in range(n_pieces):
            o_ref[0, i] = (acc_scr[i] * inv).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_tpu(q, k_pool, v_pool, page_table, pos, *,
                        k_scale=None, v_scale=None,
                        k_codebook=None, v_codebook=None,
                        interpret: bool = False):
    """Fused paged decode attention.

    q          : (B, H, hd)  — the decode token's query per slot
    k_pool/v_pool : (num_blocks, page_size, KV, hd) shared block pools;
                 with ``k_scale``/``v_scale`` given they are int8 code
                 pools instead (last axis hd for int8, hd//2 for packed
                 int4) and are dequantized in VMEM
    page_table : (B, n_pages) int32 physical block per logical page
                 (0 = reserved scratch block)
    pos        : (B,) int32 per-slot position of the decode token; the
                 kernel attends logical positions kpos <= pos[b]
    k_scale/v_scale : optional (num_blocks, page_size, KV) f32 per-row
                 per-kv-head scales of a quantized pool
    k_codebook/v_codebook : optional (KV, 16, 2) f32 frozen codebooks of
                 a VQ pool; pools then hold packed 4-bit index pages
                 (last axis hd//4) looked up in VMEM
    returns    : (B, H, hd) in q.dtype
    """
    B, H, hd = q.shape
    num_blocks, page_size, KV, cols = k_pool.shape
    n_pages = page_table.shape[-1]
    G = H // KV
    scale = 1.0 / (hd ** 0.5)
    if k_codebook is not None:
        kv_bits = kv_quant.VQ_BITS
    elif k_scale is not None:
        kv_bits = kv_quant.infer_bits(cols, hd)
    else:
        kv_bits = kv_quant.PASSTHROUGH_BITS
    P = _pieces(kv_bits)
    w = hd // P
    R = page_size * KV

    # piece-major query: qp[b, i, h, j] = q[b, h, j*P + i]
    qp = q.reshape(B, H, w, P).transpose(0, 3, 1, 2)
    row_kv = (jnp.arange(H, dtype=jnp.int32) // G)[:, None]
    col = jnp.arange(R, dtype=jnp.int32)
    col_info = jnp.stack([col // KV, col % KV])      # (2, R): row, kv head

    def fixed(b, pg, table, pos):
        return 0, 0

    def page(b, pg, table, pos):
        # the in-kernel gather: logical page pg of slot b lives in physical
        # block table[b, pg] — resolved here, in the index map, so only the
        # slot's own pages are ever DMA'd
        return table[b, pg], 0, 0, 0

    def scale_page(b, pg, table, pos):
        # scale rows resolve through the SAME scalar-prefetched table, so
        # a quantized page and its scales always travel together
        return table[b, pg], 0, 0

    in_specs = [
        pl.BlockSpec((1, P, H, w), lambda b, pg, table, pos: (b, 0, 0, 0)),
        pl.BlockSpec((H, 1), fixed),
        pl.BlockSpec((2, R), fixed),
        pl.BlockSpec((1, page_size, KV, cols), page),
        pl.BlockSpec((1, page_size, KV, cols), page),
    ]
    operands = [qp, row_kv, col_info, k_pool, v_pool]
    if kv_bits != kv_quant.PASSTHROUGH_BITS:
        in_specs += [pl.BlockSpec((1, 1, R), scale_page)] * 2
        operands += [s.astype(jnp.float32).reshape(num_blocks, 1, R)
                     for s in (k_scale, v_scale)]
    if kv_bits == kv_quant.VQ_BITS:
        # (KV, 16, 2) -> (32, KV, 1): entry (c, e) is one (KV, 1) column;
        # page-invariant, so it stays resident while the page dim streams
        n_ent = kv_quant.VQ_K * kv_quant.VQ_D
        in_specs += [pl.BlockSpec(
            (n_ent, KV, 1), lambda b, pg, table, pos: (0, 0, 0))] * 2
        operands += [cb.astype(jnp.float32).reshape(KV, n_ent).T[..., None]
                     for cb in (k_codebook, v_codebook)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, P, H, w),
                               lambda b, pg, table, pos: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((P, H, w), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, page_size=page_size,
                          n_pages=n_pages, kv_bits=kv_bits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, P, H, w), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32), *operands)
    return out.transpose(0, 2, 3, 1).reshape(B, H, hd)
