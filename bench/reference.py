"""Plain float32 reference of the served models, and its lower-precision
control.

A straightforward decoder forward in ``jax.numpy`` at
``default_matmul_precision("highest")``: embedding, then per layer RMSNorm,
q/k/v projections (per-head q/k RMSNorm where the architecture has it),
rotary embedding on each head's two halves, causal grouped-query softmax
attention, output projection, residual, RMSNorm, SwiGLU MLP, residual;
final RMSNorm and the (tied or separate) head. Packed weights are decoded
here from the seeded payload (``bench/weights.py``) by their format's
definition: code ``i`` of row ``r`` is bits ``[4 (i mod 8), 4 (i mod 8) +
4)`` of word ``i // 8``, and selects entry ``i`` of the int8 codebook of
the row's band and the column's group, times that codebook's scale.

Nothing here imports the program. Sequences are padded to one length and
run layer by layer, so one layer's weights are dense at a time.

The control (``precision="fp8"``) is the same forward with every matrix
product taken in float8 e4m3: each weight row and each activation row is
scaled by its absmax to the format's range and rounded, products
accumulate in float32. It is what a forward one precision step below the
served bfloat16 gives.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.spec import ModelSpec

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def decode_matrix(m: dict, spec: ModelSpec, r: int, c: int) -> jax.Array:
    """(r=out, c=in) float32 weights of one packed matrix of one layer."""
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


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _q8(x):
    """Round rows of x to float8 e4m3 at their own absmax scale."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _mm(x, w, precision):
    """x (..., c) @ w (r, c)^T."""
    if precision == "fp8":
        x, w = _q8(x), _q8(w)
    return jnp.einsum("...c,rc->...r", x, w)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv          # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(h, w, spec: ModelSpec, precision: str):
    """One decoder layer over h (B, T, D) float32."""
    B, T, _ = h.shape
    H, KV, hd = spec.n_heads, spec.n_kv, spec.hd
    x = _rms(h, w["norm1"], spec.eps)
    q = _mm(x, w["wq"], precision).reshape(B, T, H, hd)
    k = _mm(x, w["wk"], precision).reshape(B, T, KV, hd)
    v = _mm(x, w["wv"], precision).reshape(B, T, KV, hd)
    if spec.qk_norm:
        q = _rms(q, w["q_norm"], spec.eps)
        k = _rms(k, w["k_norm"], spec.eps)
    pos = jnp.arange(T)
    q = _rope(q, pos, spec.rope_theta)
    k = _rope(k, pos, spec.rope_theta)
    causal = pos[None, :] <= pos[:, None]                 # (Tq, Tk)

    def attend(args):
        qb, kb, vb = args                                 # one sequence
        qg = qb.reshape(T, KV, H // KV, hd)
        s = jnp.einsum("qkgh,skh->kgqs", qg, kb) / jnp.sqrt(float(hd))
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skh->qkgh", p, vb).reshape(T, H * hd)

    o = jax.lax.map(attend, (q, k, v))
    h = h + _mm(o, w["wo"], precision)
    x = _rms(h, w["norm2"], spec.eps)
    f = jax.nn.silu(_mm(x, w["w_gate"], precision)) * _mm(x, w["w_in"],
                                                           precision)
    return h + _mm(f, w["w_out"], precision)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _logits(h, idx, norm, head, spec: ModelSpec, precision: str):
    """Logits (P, V) at rows idx (P, 2) = (sequence, position) of h."""
    x = _rms(h[idx[:, 0], idx[:, 1]], norm, spec.eps)
    return _mm(x, head, precision)


def _layer_weights(raw, spec: ModelSpec, i: int) -> dict:
    lay = raw["layers"]
    w = {n: lay[n][i].astype(jnp.float32) for n in ("norm1", "norm2")}
    if spec.qk_norm:
        w["q_norm"] = lay["q_norm"][i].astype(jnp.float32)
        w["k_norm"] = lay["k_norm"][i].astype(jnp.float32)
    for name, (_, r, c) in spec.targets().items():
        one = {k: a[i] for k, a in lay[name].items()}
        w[name] = _decode(one, spec, r, c)
    return w


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _decode(m, spec, r, c):
    return decode_matrix(m, spec, r, c)


def logits_at(raw: dict, spec: ModelSpec, seqs: list[np.ndarray],
              positions: list[np.ndarray], precision: str = "f32",
              pad_to: int = 128) -> list[np.ndarray]:
    """Reference logits of each token sequence at the given positions.

    ``seqs[b]`` is a whole token sequence (prompt and served tokens);
    ``positions[b]`` the positions whose next-token logits are wanted.
    Returns one (len(positions[b]), padded_vocab) float32 array per
    sequence. ``precision`` is "f32" (the reference) or "fp8" (the
    control)."""
    assert precision in ("f32", "fp8"), precision
    B = len(seqs)
    T = -(-max(len(s) for s in seqs) // pad_to) * pad_to
    toks = np.zeros((B, T), np.int32)
    for b, s in enumerate(seqs):
        toks[b, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        h = raw["embed"][jnp.asarray(toks)].astype(jnp.float32)
        for i in range(spec.n_layers):
            h = _layer(h, _layer_weights(raw, spec, i), spec, precision)
        head = (raw["embed"] if spec.tied else raw["lm_head"].T).astype(
            jnp.float32)
        idx = np.concatenate([np.stack([np.full(len(p), b), p], 1)
                              for b, p in enumerate(positions)]).astype(
            np.int32)
        out = np.asarray(_logits(h, jnp.asarray(idx),
                                 raw["final_norm"].astype(jnp.float32),
                                 head, spec, precision))
    splits = np.cumsum([len(p) for p in positions])[:-1]
    return np.split(out, splits)
