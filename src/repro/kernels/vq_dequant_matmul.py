"""Fused VQ-decode + matmul Pallas TPU kernel — the serving hot-spot.

TPU adaptation of the paper's ARM-TBL decode kernel (DESIGN.md §3): the
bit-packed index matrix is the HBM payload (2-4.5 bits/weight); codebooks
live in VMEM; decode happens on-chip and the reconstructed tile feeds the
MXU directly, so the dense weight matrix never round-trips through HBM.

Layout contract (matches core/vq_linear.VQLinear):
  x          (M, K)                      activations
  words      (N, K/d * bits / 32)        packed uint32 codes, row-major
  codebooks  (n_cg, n_bands, k_c, d)     fp32 (int8 codebook * scale folded)
  scales     (N, K/Ns) fp32, optional    blockwise normalization plane
with N = n_bands * rows_per_band, K = n_cg * group_cols.

Piece decomposition (no lane interleaving in VMEM). Word w of a row holds
``lanes`` codes, code l covering columns ``(w*lanes + l)*d + e``. With
P = lanes*d, column k = w*P + p belongs to "piece" p = l*d + e, so
``x @ W.T = sum_p x[:, p::P] @ W_p.T`` where W_p (N, K/P) is aligned with
the word grid: W_p[n, w] = codebook[group(w), band(n), code_l[n, w], e].
The wrapper hands x over piece-major; in the kernel each piece is a shift
and mask of the word tile, a k_c-way select against per-entry value
planes, and one MXU matmul.

Value planes. Entry (c, e) of every (band, group) codebook in the tile is
broadcast to a (tile_n, words) plane by two 0/1 expansion matmuls
(rows -> bands, words -> groups) at HIGHEST precision, which reproduce the
f32 entries exactly. The planes are built once per grid step into VMEM
scratch and read by every piece. A blockwise scale plane is expanded the
same way (per piece when a scale block is narrower than one word).

Tiling follows the TPU rule for the last two block dims (a multiple of
(8, 128), or the whole array dim): tile_n is a band-aligned divisor of N
that is a multiple of 128 with a multiple-of-8 band count (or all of N),
and tile_k is a group-aligned divisor of K whose words per row, and scale
columns, are multiples of 128 (or all of K). M is padded to a multiple of
8 and sliced back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _band_mask(shape, width, axis, col_scale=1, col_offset=0):
    """0/1 f32 expansion matrix: entry (i, j) is 1 where the index along
    the other axis, times ``col_scale`` plus ``col_offset``, falls in band
    ``[k*width, (k+1)*width)`` of the index k along ``axis``."""
    band = jax.lax.broadcasted_iota(jnp.int32, shape, axis) * width
    pos = (jax.lax.broadcasted_iota(jnp.int32, shape, 1 - axis) * col_scale
           + col_offset)
    return ((pos >= band) & (pos < band + width)).astype(jnp.float32)


def _kernel(x_ref, w_ref, c_ref, *rest, d, k_c, container_bits,
            rows_per_band, words_per_group, scale_block):
    if scale_block:
        s_ref, o_ref, v_scr = rest
    else:
        o_ref, v_scr = rest
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    tn, wk = w_ref.shape
    bands_t, gk = c_ref.shape[-2:]
    lanes = 32 // container_bits
    P = lanes * d

    # value planes: v_scr[c*d + e][n, w] = codebook[group(w), band(n), c, e]
    e_rows = _band_mask((tn, bands_t), rows_per_band, axis=1)
    e_cols = _band_mask((gk, wk), words_per_group, axis=0)
    unroll = k_c <= 16   # large codebooks loop instead of unrolling

    def value_plane(j, carry):
        by_band = jnp.dot(c_ref[0, j], e_cols, precision=_HI,
                          preferred_element_type=jnp.float32)
        v_scr[j] = jnp.dot(e_rows, by_band, precision=_HI,
                           preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, k_c * d, value_plane, 0, unroll=unroll)

    def scale_plane(p):
        # s[n, (w*P + p) // Ns] expanded onto the (tn, wk) word grid
        e_s = _band_mask((s_ref.shape[1], wk), scale_block, axis=0,
                         col_scale=P, col_offset=p)
        return jnp.dot(s_ref[...], e_s, precision=_HI,
                       preferred_element_type=jnp.float32)

    shared_scale = None
    if scale_block and scale_block % P == 0:
        shared_scale = scale_plane(0)

    words = w_ref[...]
    mask = 2 ** container_bits - 1
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for l in range(lanes):
        code = jax.lax.shift_right_logical(words, l * container_bits) & mask
        for e in range(d):
            p = l * d + e
            w_p = jax.lax.fori_loop(
                0, k_c,
                lambda c, w_p: jnp.where(code == c, v_scr[c * d + e], w_p),
                jnp.zeros((tn, wk), jnp.float32), unroll=unroll)
            if scale_block:
                w_p = w_p * (shared_scale if shared_scale is not None
                             else scale_plane(p))
            acc += jax.lax.dot_general(
                x_ref[p], w_p, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
    o_ref[...] += acc


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pick(legal: list[int], want: int) -> int:
    """Largest legal tile <= want, else the smallest legal one."""
    below = [t for t in legal if t <= want]
    return max(below) if below else min(legal)


def _snap_tile_n(N: int, rows_per_band: int, tile_n: int) -> int:
    """Band-aligned divisor of N the TPU tiling accepts for the words,
    scale and output blocks (lanes: %128 or all of N) and the codebook
    block (bands: %8 or all of them)."""
    n_bands = N // rows_per_band
    legal = [bt * rows_per_band for bt in range(1, n_bands + 1)
             if n_bands % bt == 0
             and ((bt * rows_per_band) % 128 == 0 and bt % 8 == 0
                  or bt == n_bands)]
    return _pick(legal, tile_n)


def _snap_tile_k(K: int, group_cols: int, pieces: int, scale_block: int,
                 tile_k: int) -> int:
    """Group-aligned divisor of K whose words per row (tile_k / pieces)
    and scale columns (tile_k / scale_block) are multiples of 128, or all
    of K (a whole packed row is always a legal block)."""
    n_cg = K // group_cols
    legal = []
    for gk in range(1, n_cg + 1):
        tk = gk * group_cols
        if n_cg % gk:
            continue
        ok = tk % pieces == 0 and (tk // pieces) % 128 == 0
        if scale_block:
            ok = ok and tk % scale_block == 0 and (tk // scale_block) % 128 == 0
        if ok or tk == K:
            legal.append(tk)
    return _pick(legal, tile_k)


@functools.partial(
    jax.jit,
    static_argnames=("d", "k_c", "container_bits",
                     "rows_per_band", "group_cols", "scale_block", "tile_m",
                     "tile_n", "tile_k", "interpret"),
)
def vq_dequant_matmul(
    x: jax.Array,
    words: jax.Array,
    codebooks: jax.Array,
    scales: jax.Array | None = None,
    *,
    d: int,
    k_c: int,
    container_bits: int,
    rows_per_band: int,
    group_cols: int,
    scale_block: int = 0,
    tile_m: int = 128,
    tile_n: int = 128,
    tile_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """y = x @ dequant(words, codebooks).T ; returns (M, N) fp32.

    ``scales`` (required iff scale_block != 0) is the pre-expanded blockwise
    normalization plane (N, K // scale_block). ``tile_*`` are upper
    bounds; the tiles actually used are snapped to legal TPU blocks."""
    M, K = x.shape
    N = words.shape[0]
    assert (scales is not None) == bool(scale_block)
    lanes = 32 // container_bits
    P = lanes * d
    assert group_cols % P == 0, (
        f"column groups ({group_cols}) must hold whole words ({P} cols)")
    n_bands = N // rows_per_band

    tile_n = _snap_tile_n(N, rows_per_band, tile_n)
    tile_k = _snap_tile_k(K, group_cols, P, scale_block, tile_k)
    tile_m = min(tile_m, _round_up(M, 8))
    Mp = _round_up(M, tile_m)
    wk, gk = tile_k // P, tile_k // group_cols
    bands_t = tile_n // rows_per_band
    n_kt = K // tile_k
    grid = (Mp // tile_m, N // tile_n, n_kt)

    # piece-major activations: xp[p, m, w] = x[m, w*P + p]
    xp = jnp.pad(x.astype(jnp.float32), ((0, Mp - M), (0, 0)))
    xp = xp.reshape(Mp, K // P, P).transpose(2, 0, 1)
    # codebooks per k-tile, entry-major: cb[t, c*d + e, band, g]
    cb = codebooks.astype(jnp.float32).reshape(
        n_kt, gk, n_bands, k_c, d).transpose(0, 3, 4, 2, 1).reshape(
        n_kt, k_c * d, n_bands, gk)

    in_specs = [
        pl.BlockSpec((P, tile_m, wk), lambda i, j, kk: (0, i, kk)),
        pl.BlockSpec((tile_n, wk), lambda i, j, kk: (j, kk)),
        pl.BlockSpec((1, k_c * d, bands_t, gk),
                     lambda i, j, kk: (kk, 0, j, 0)),
    ]
    operands = [xp, jax.lax.bitcast_convert_type(words, jnp.int32), cb]
    if scale_block:
        in_specs.append(
            pl.BlockSpec((tile_n, tile_k // scale_block),
                         lambda i, j, kk: (j, kk)))
        operands.append(scales.astype(jnp.float32))

    y = pl.pallas_call(
        functools.partial(
            _kernel, d=d, k_c=k_c, container_bits=container_bits,
            rows_per_band=rows_per_band,
            words_per_group=group_cols // P, scale_block=scale_block),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_m, tile_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k_c * d, tile_n, wk), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return y[:M]
