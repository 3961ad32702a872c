"""Pieces of the plain float32 references, and of their lower-precision
control, that every architecture's ``logits_at`` (``bench/arch/``) builds
its forward from.

Packed weights are decoded here from the seeded payload
(``bench/weights.py``) by their format's definition: code ``i`` of row
``r`` is bits ``[4 (i mod 8), 4 (i mod 8) + 4)`` of word ``i // 8``, and
selects entry ``i`` of the int8 codebook of the row's band and the
column's group, times that codebook's scale. The rest is plain
``jax.numpy``: RMSNorm, rotary embedding on each head's two halves, causal
grouped-query softmax attention, and the matrix product ``mm``.

Nothing here imports the program. A reference pads its sequences to one
length (``inputs``) and runs layer by layer, so one layer's weights are
dense at a time, at ``default_matmul_precision("highest")``.

The control (``precision="fp8"``) is the same forward with every matrix
product taken in float8 e4m3 (``mm``): each weight row and each activation
row is scaled by its absmax to the format's range and rounded, products
accumulate in float32. It is what a forward one precision step below the
served bfloat16 gives.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
PRECISIONS = ("f32", "fp8")


def decode_matrix(m: dict, spec, r: int, c: int) -> jax.Array:
    """(r=out, c=in) float32 weights of one packed matrix of one layer, in
    ``spec.vq``'s format."""
    fmt = spec.vq
    cg, rg = fmt.plan(r, c)
    per_word = 32 // fmt.code_bits
    shifts = jnp.arange(per_word, dtype=jnp.uint32) * fmt.code_bits
    codes = (m["words"][:, :, None] >> shifts) & jnp.uint32(fmt.k - 1)
    codes = codes.reshape(r, c // fmt.d).astype(jnp.int32)
    cb = m["codebooks"].astype(jnp.float32) * m["cb_scale"][..., None, None]
    band = (jnp.arange(r) // rg)[:, None]
    group = (jnp.arange(c // fmt.d) * fmt.d // cg)[None, :]
    w = cb[group, band, codes]                    # (r, c/d, d)
    return w.reshape(r, c)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def decode(m, spec, r, c):
    """``decode_matrix``, compiled once per shape."""
    return decode_matrix(m, spec, r, c)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def q8(x):
    """Round rows of x to float8 e4m3 at their own absmax scale."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(FP8).astype(jnp.float32) * s


def mm(x, w, precision):
    """x (..., c) @ w (r, c)^T."""
    if precision == "fp8":
        x, w = q8(x), q8(w)
    return jnp.einsum("...c,rc->...r", x, w)


def rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv          # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v):
    """Causal grouped-query softmax attention of q (B, T, H, hd) over k, v
    (B, T, KV, hd), one sequence at a time; (B, T, H hd)."""
    _, T, H, hd = q.shape
    KV = k.shape[2]
    pos = jnp.arange(T)
    causal = pos[None, :] <= pos[:, None]                 # (Tq, Tk)

    def attend(args):
        qb, kb, vb = args                                 # one sequence
        qg = qb.reshape(T, KV, H // KV, hd)
        s = jnp.einsum("qkgh,skh->kgqs", qg, kb) / jnp.sqrt(float(hd))
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skh->qkgh", p, vb).reshape(T, H * hd)

    return jax.lax.map(attend, (q, k, v))


def inputs(seqs: list[np.ndarray], positions: list[np.ndarray],
           pad_to: int) -> tuple[np.ndarray, np.ndarray]:
    """Tokens (B, T) zero-padded to a multiple of ``pad_to``, and the rows
    (P, 2) = (sequence, position) whose logits are wanted."""
    B = len(seqs)
    T = -(-max(len(s) for s in seqs) // pad_to) * pad_to
    toks = np.zeros((B, T), np.int32)
    for b, s in enumerate(seqs):
        toks[b, :len(s)] = s
    idx = np.concatenate([np.stack([np.full(len(p), b), p], 1)
                          for b, p in enumerate(positions)]).astype(np.int32)
    return toks, idx


def by_sequence(out: np.ndarray, positions: list[np.ndarray]) -> list:
    """Rows of ``out`` (in ``inputs``'s order) split per sequence."""
    return np.split(out, np.cumsum([len(p) for p in positions])[:-1])
