"""Fused paged-attention decode Pallas TPU kernel.

One query token per slot (S == 1 decode) attends over that slot's paged
KV blocks *in place*: the per-slot page table rides in as a scalar-prefetch
operand and the kernel gathers the slot's pages from the pool, which stays
in HBM, with its own DMAs — the (B, n_pages*page_size, KV, hd) logical view
the XLA gather path materializes per layer never exists.

Grid (B, n_blocks): one program per (slot, block of ``pages_per_block``
consecutive logical pages), with the block dimension innermost so the
online-softmax running max/sum/acc live in VMEM scratch across blocks (same
structure as kernels/flash_attention.py). ``pages_per_block`` is derived
from shapes alone (``pages_per_block()``): as many pages as keep both
buffers of the K and V blocks and their f32 decode under a fixed VMEM
budget, rounded down to a power of two — 16 pages of an f32 qwen3-1.7b
pool, fewer for packed formats, whose decode takes more VMEM per page.

Only live pages are read. A slot's live pages are those with
``page <= pos // page_size``. A block with no live page issues no DMA and
does no work. A live block copies its live pages, each page's whole
``(page_size, KV, cols)`` tile (the pool's full trailing dims, which is
what the TPU tiling accepts for any KV), from ``pool[table[slot, page]]``
into one of two VMEM buffers; the copies of the next live block in grid
order — the slot's next block, or the next slot's first — are started
before the current block's are awaited, so the next pages stream while
this block computes. The block's tile is flattened to
``(pages_per_block*page_size*KV, cols)`` rows. All H query heads score
against all of those rows in one MXU matmul; a head mask (row head ==
column kv head) keeps each query head on its own kv head's rows, so GQA
needs no materialized head expansion and no per-head slicing.

Packed formats are decoded without interleaving lanes in VMEM. Instead the
head dim is split into P "pieces" by ``dim % P`` outside the kernel: P = 2
for int4 (low/high nibble = even/odd dims), P = 4 for vq2 (nibble n, vector
element e -> dim 4j + 2n + e), P = 1 otherwise. Each piece of the code tile
decodes elementwise, the scores are the sum of per-piece matmuls, and the
output comes back piece-major and is re-interleaved outside the kernel.

Narrow pages. The chip's DMA copies whole 128-lane tiles, so a page whose
rows are narrower (int4 and vq2 at hd 128: 64 and 32 byte columns) cannot
be copied as its own ``(page_size, KV, cols)`` tile. Such a pool is viewed
as ``(num_blocks, R/L, L*cols)`` with ``R = page_size*KV`` and L lane
groups (``lg``): view row r holds the L consecutive (row, kv head) page
rows ``r*L + g`` side by side, one per lane group g. In VMEM each decoded
view row is expanded into L rows, row g keeping lane group g and zeros
elsewhere, and the query is repeated once per lane group; so score column
(page, g, r) scores page row ``r*L + g``, the head and position masks
apply as above, and lane group g of the output sums over the page rows it
carries. The L groups are added up outside the kernel. Scale rows are
stored in the same (g, r) order, and the vq2 codebook tile gives each
(view row, lane) its kv head's entry. L = 1, the pool's own tile,
wherever a row fills the lanes (f32, bf16 and int8 pools at hd 128).

Quantized pools (KVQuantSpec bits 8/4, kernels/kv_quant.py): the pools hold
int8 code pages (int4 packed two codes per byte along the head dim) plus
per-row per-kv-head f32 scales. The scale plane is viewed as one
``(1, page_size*KV)`` row per block — a free reshape of the pool — and a
live page's scale row is copied with its codes, through the same table
entry, to its place in the block's ``(1, rows)`` scale row. A per-row scale
factors out of the dot product, so the kernel multiplies the K scales into
the scores and the V scales into the softmax weights; the decoded values
are the same ``kv_quant`` expressions (sign-extended nibbles, codebook
entries) the oracle and the gather path use.

VQ pools (KVQuantSpec mode "vq2"): pages hold packed 4-bit codebook
indices over d=2 vectors along the head dim. The frozen (KV, 16, 2)
codebooks ride in as a block-invariant ``(32, KV, 1)`` tile (one column of
kv-head values per codebook entry), so the lookup is a 16-way select per
piece — a table lookup with no gather and no one-hot tensor.

Masking is the serving invariant ``kpos <= pos[slot]`` over *logical*
positions: stale rows in recycled blocks, the tail of the slot's last page,
the reserved scratch block 0 (where inactive slots' page-table entries
point), and the buffer's pages past the slot's last live page (not copied:
they hold what an earlier block left, or the zeros the V buffers start
from) are all strictly above ``pos`` and never contribute. Stale *scales*
ride the same masked rows: they decode stale codes to finite garbage whose
scores die at the mask, exactly like stale fp16 keys. An idle slot
(pos == 0, table all-scratch) attends exactly one scratch row — defined
output, discarded by the engine.

``kernels/ref.py:paged_attention_ref`` is the pure-XLA oracle (same
``kv_quant`` decode on the gathered view);
``tests/kernels/test_paged_attention.py`` is the differential harness and
``tests/kernels/test_tpu_compile.py`` compiles it for a v5e chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import kv_quant

NEG_INF = -1e30

# VMEM one block of pages may take: both buffers of its K and V tiles as
# they lie in VMEM, their f32 decode, and the block's scores. Half of the
# 16 MiB a v5e kernel may scope, leaving the rest to the compiler.
BLOCK_VMEM_BYTES = 8 << 20


def _pieces(kv_bits) -> int:
    """Head-dim pieces a stored byte column decodes into (see module doc)."""
    if kv_bits == kv_quant.VQ_BITS:
        return 2 * kv_quant.VQ_D
    return 2 if kv_bits == 4 else 1


def _page_view(page_size: int, n_kv: int, cols: int):
    """(lane groups, shape of a page as the kernel copies it): the pool's
    own tile where a row fills the 128 lanes, else ``R/lg`` rows of ``lg``
    page rows side by side (see module doc)."""
    rows = page_size * n_kv
    lg = math.gcd(rows, max(1, 128 // cols))
    if lg == 1:
        return 1, (page_size, n_kv, cols)
    return lg, (rows // lg, lg * cols)


def _tiled(rows: int, cols: int, itemsize: int) -> int:
    """Bytes of a (rows, cols) array in VMEM's (32 // itemsize, 128) tiles."""
    sub = 32 // itemsize
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * itemsize


def pages_per_block(n_heads: int, head_dim: int, page_size: int,
                    n_kv: int, n_pages: int, pool_dtype, kv_bits) -> int:
    """Logical pages the kernel covers per grid step, from shapes alone:
    the largest power of two whose block fits ``BLOCK_VMEM_BYTES``, at most
    ``n_pages``. ``kv_bits`` is 16 for a passthrough pool, else 8, 4 or
    ``kv_quant.VQ_BITS``."""
    itemsize = jnp.dtype(pool_dtype).itemsize
    quantized = kv_bits != kv_quant.PASSTHROUGH_BITS
    cols = kv_quant.storage_cols(head_dim, kv_bits) if quantized \
        else head_dim
    rows = page_size * n_kv
    lg, view = _page_view(page_size, n_kv, cols)
    stored = math.prod(view[:-2]) * _tiled(*view[-2:], itemsize)
    if quantized:
        stored += _tiled(1, rows, 4)                 # the page's scale row
    n_pieces = _pieces(kv_bits)
    decoded = n_pieces * _tiled(rows, lg * head_dim // n_pieces, 4)
    scores = 2 * _tiled(n_heads, rows, 4)            # s and p
    per_page = 2 * (2 * stored + decoded) + scores   # K and V
    fit = max(1, min(n_pages, BLOCK_VMEM_BYTES // per_page))
    return 1 << (fit.bit_length() - 1)


def _decode_pieces(codes, cb_ref, kv_bits):
    """Stored tile -> list of P f32 pieces of the same shape, piece i
    holding head dims ``i::P`` (scales not applied). ``cb_ref`` holds, per
    codebook entry, the entry's value for the tile's trailing two dims."""
    if kv_bits == kv_quant.PASSTHROUGH_BITS or kv_bits == 8:
        return [codes.astype(jnp.float32)]
    c32 = codes.astype(jnp.int32)
    if kv_bits == 4:
        # kv_quant.unpack_int4: sign-extended low / high nibble
        lo = jnp.left_shift(c32, 28) >> 28
        hi = c32 >> 4
        return [lo.astype(jnp.float32), hi.astype(jnp.float32)]
    # vq2 (kv_quant.unpack_vq2 + vq_dequant_rows): unsigned nibbles index
    # the kv head's codebook
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


def _kernel(table_ref, pos_ref, q_ref, rowkv_ref, colinfo_ref, *rest,
            scale, page_size, n_pages, ppb, lg, kv_bits):
    quantized = kv_bits != kv_quant.PASSTHROUGH_BITS
    vq = kv_bits == kv_quant.VQ_BITS
    rest = list(rest)
    group_ref = rest.pop(0) if lg > 1 else None
    k_hbm, v_hbm = rest.pop(0), rest.pop(0)
    ks_hbm = vs_hbm = kcb_ref = vcb_ref = ks_buf = vs_buf = None
    if quantized:
        ks_hbm, vs_hbm = rest.pop(0), rest.pop(0)
    if vq:
        kcb_ref, vcb_ref = rest.pop(0), rest.pop(0)
    o_ref, m_scr, l_scr, acc_scr, k_buf, v_buf = rest[:6]
    rest = rest[6:]
    if quantized:
        ks_buf, vs_buf = rest.pop(0), rest.pop(0)
    sems, cur_ref = rest
    b = pl.program_id(0)
    blk = pl.program_id(1)
    n_slots = pl.num_programs(0)
    n_pieces = q_ref.shape[1]
    R = colinfo_ref.shape[1] // ppb          # (row, kv head) pairs a page

    def last_page(slot):
        return jnp.minimum(pos_ref[slot] // page_size, n_pages - 1)

    def page_copies(slot, block, buf, i):
        """The DMAs of page i of (slot, block) into buffer ``buf``."""
        phys = table_ref[slot * n_pages + block * ppb + i]
        copies = [pltpu.make_async_copy(k_hbm.at[phys], k_buf.at[buf, i],
                                        sems.at[buf]),
                  pltpu.make_async_copy(v_hbm.at[phys], v_buf.at[buf, i],
                                        sems.at[buf])]
        if quantized:
            row = pl.ds(i * R, R)
            copies += [pltpu.make_async_copy(ks_hbm.at[phys],
                                             ks_buf.at[buf, :, row],
                                             sems.at[buf]),
                       pltpu.make_async_copy(vs_hbm.at[phys],
                                             vs_buf.at[buf, :, row],
                                             sems.at[buf])]
        return copies

    def for_live_pages(slot, block, buf, act):
        # only the block's live pages travel; the rest of the buffer keeps
        # what it held and dies at the position mask
        n_live = jnp.clip(last_page(slot) - block * ppb + 1, 0, ppb)

        def page(i, carry):
            for c in page_copies(slot, block, buf, i):
                act(c)
            return carry

        jax.lax.fori_loop(0, n_live, page, 0)

    @pl.when(blk * ppb <= last_page(b))
    def _live_block():
        @pl.when((b == 0) & (blk == 0))
        def _first():
            # a row past pos weighs 0 in p @ v, which stays 0 only if the
            # value is finite: the V side starts from zeros, not from
            # whatever VMEM held
            v_buf[...] = jnp.zeros_like(v_buf)
            if quantized:
                vs_buf[...] = jnp.zeros_like(vs_buf)
            cur_ref[0] = 0
            for_live_pages(b, blk, 0, lambda c: c.start())

        buf = cur_ref[0]
        more = (blk + 1) * ppb <= last_page(b)
        nxt_b = jnp.where(more, b, b + 1)
        nxt_blk = jnp.where(more, blk + 1, 0)

        @pl.when(nxt_b < n_slots)
        def _prefetch():
            for_live_pages(nxt_b, nxt_blk, 1 - buf, lambda c: c.start())
            cur_ref[0] = 1 - buf

        for_live_pages(b, blk, buf, lambda c: c.wait())

        @pl.when(blk == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        def pieces(tile, cb_ref):
            """(ppb, *page view) -> P (ppb*R, lanes) f32 row blocks, one row
            per (page, lane group, view row)."""
            if lg == 1:       # (ppb, page_size, KV, cols)
                flat = tile.reshape(-1, *tile.shape[2:])
                return [t.reshape(-1, t.shape[-1])
                        for t in _decode_pieces(flat, cb_ref, kv_bits)]
            # (ppb, R/lg, lg*cols): a view row holds lg page rows side by
            # side; row g of the expansion keeps lane group g, zeros the rest
            mine = group_ref[...][None] != 0
            return [jnp.where(mine, t[:, None], 0.0).reshape(-1, t.shape[-1])
                    for t in _decode_pieces(tile, cb_ref, kv_bits)]

        kp = pieces(k_buf[buf], kcb_ref)
        vp = pieces(v_buf[buf], vcb_ref)

        s = None
        for i in range(n_pieces):
            si = jax.lax.dot_general(
                q_ref[0, i].astype(jnp.float32), kp[i],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            s = si if s is None else s + si
        if quantized:
            s = s * ks_buf[buf]                  # per-row K scales
        s = s * scale
        # column j of the block's scores is the page row colinfo[0, j] of
        # kv head colinfo[1, j]; the head mask pins each query head to its
        # kv head's rows, and the logical position mask is the single
        # serving mask: scratch block 0, recycled-block staleness (codes AND
        # scales), the last-page tail and the buffer's uncopied pages all
        # have kpos > pos and die here
        kpos = blk * (ppb * page_size) + colinfo_ref[0:1, :]
        valid = (rowkv_ref[...] == colinfo_ref[1:2, :]) & (kpos <= pos_ref[b])
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            p = p * vs_buf[buf]                  # per-row V scales
        for i in range(n_pieces):
            acc_scr[i] = acc_scr[i] * alpha + jax.lax.dot_general(
                p, vp[i], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        m_scr[...] = m_new

        @pl.when(jnp.logical_not(more))
        def _done():
            inv = 1.0 / jnp.maximum(l_scr[...], 1e-30)
            for i in range(n_pieces):
                o_ref[0, i] = (acc_scr[i] * inv).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_tpu(q, k_pool, v_pool, page_table, pos, *,
                        k_scale=None, v_scale=None,
                        k_codebook=None, v_codebook=None,
                        interpret: bool | pltpu.InterpretParams = False):
    """Fused paged decode attention.

    q          : (B, H, hd)  — the decode token's query per slot
    k_pool/v_pool : (num_blocks, page_size, KV, hd) shared block pools;
                 with ``k_scale``/``v_scale`` given they are int8 code
                 pools instead (last axis hd for int8, hd//2 for packed
                 int4) and are dequantized in VMEM
    page_table : (B, n_pages) int32 physical block per logical page
                 (0 = reserved scratch block)
    pos        : (B,) int32 per-slot position of the decode token; the
                 kernel attends logical positions kpos <= pos[b] and reads
                 only the pages that hold them
    k_scale/v_scale : optional (num_blocks, page_size, KV) f32 per-row
                 per-kv-head scales of a quantized pool
    k_codebook/v_codebook : optional (KV, 16, 2) f32 frozen codebooks of
                 a VQ pool; pools then hold packed 4-bit index pages
                 (last axis hd//4) looked up in VMEM
    interpret  : False on the TPU; True runs Pallas's HLO interpreter on
                 the CPU, a ``pltpu.InterpretParams`` the TPU interpreter
                 (DMAs, semaphores and VMEM simulated; uninitialized VMEM
                 reads as NaN)
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
    lg, view = _page_view(page_size, KV, cols)    # lg > 1: w == cols
    ppb = pages_per_block(H, hd, page_size, KV, n_pages, k_pool.dtype,
                          kv_bits)
    n_blocks = -(-n_pages // ppb)
    N = ppb * R

    # piece-major query, repeated once per lane group:
    # qp[b, i, h, g*w + j] = q[b, h, j*P + i]
    qp = jnp.tile(q.reshape(B, H, w, P).transpose(0, 3, 1, 2), lg)
    row_kv = (jnp.arange(H, dtype=jnp.int32) // G)[:, None]
    # score column (page, lane group g, view row r) is page row u = r*lg + g
    col = jnp.arange(N, dtype=jnp.int32)
    page, g, r = col // R, col % R // (R // lg), col % (R // lg)
    u = r * lg + g
    col_info = jnp.stack([page * page_size + u // KV, u % KV])  # row, head

    def fixed(*shape):
        return pl.BlockSpec(shape, lambda b, blk, table, pos: (0,) * len(
            shape))

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [
        pl.BlockSpec((1, P, H, lg * w),
                     lambda b, blk, table, pos: (b, 0, 0, 0)),
        fixed(H, 1),
        fixed(2, N),
    ]
    operands = [qp, row_kv, col_info]
    if lg > 1:
        lane = jnp.arange(lg * cols, dtype=jnp.int32) // cols
        in_specs.append(fixed(lg, 1, lg * cols))
        operands.append((lane == jnp.arange(lg)[:, None]).astype(
            jnp.int32)[:, None])
    in_specs += [hbm, hbm]
    operands += [k_pool.reshape(num_blocks, *view),
                 v_pool.reshape(num_blocks, *view)]
    scratch = [
        pltpu.VMEM((H, 1), jnp.float32),
        pltpu.VMEM((H, 1), jnp.float32),
        pltpu.VMEM((P, H, lg * w), jnp.float32),
        pltpu.VMEM((2, ppb, *view), k_pool.dtype),
        pltpu.VMEM((2, ppb, *view), v_pool.dtype),
    ]
    if kv_bits != kv_quant.PASSTHROUGH_BITS:
        # one (1, R) row per block, in the score columns' (g, r) order
        in_specs += [hbm, hbm]
        operands += [s.astype(jnp.float32).reshape(num_blocks, R // lg, lg)
                     .transpose(0, 2, 1).reshape(num_blocks, 1, R)
                     for s in (k_scale, v_scale)]
        scratch += [pltpu.VMEM((2, 1, N), jnp.float32)] * 2
    if kv_bits == kv_quant.VQ_BITS:
        # per codebook entry (c, e), its value for the view's trailing two
        # dims: a (KV, 1) column of kv heads, or with lane groups the kv
        # head of each (row, lane); block-invariant, so it stays resident
        n_ent = kv_quant.VQ_K * kv_quant.VQ_D
        if lg == 1:
            tiles = [cb.astype(jnp.float32).reshape(KV, n_ent).T[..., None]
                     for cb in (k_codebook, v_codebook)]
        else:
            head = (jnp.arange(R) % KV).reshape(R // lg, lg)
            tiles = [jnp.repeat(cb.astype(jnp.float32).reshape(KV, n_ent)
                                .T[:, head], cols, axis=-1)
                     for cb in (k_codebook, v_codebook)]
        in_specs += [fixed(*tiles[0].shape)] * 2
        operands += tiles
    scratch += [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, P, H, lg * w),
                               lambda b, blk, table, pos: (b, 0, 0, 0)),
        scratch_shapes=scratch,
    )
    # lane groups are summed outside the kernel, in f32
    out_dtype = q.dtype if lg == 1 else jnp.float32
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, page_size=page_size,
                          n_pages=n_pages, ppb=ppb, lg=lg, kv_bits=kv_bits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, P, H, lg * w), out_dtype),
        # a block prefetches the next one in grid order: sequential grid
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32).reshape(-1), pos.astype(jnp.int32),
      *operands)
    # lane group g holds the sum over the page rows it carries
    out = out.reshape(B, P, H, lg, w).sum(3).astype(q.dtype)
    return out.transpose(0, 2, 3, 1).reshape(B, H, hd)
