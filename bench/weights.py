"""Seeded weights, made on the device in one jitted call.

``raw_payload`` draws every array a configuration serves from the seed, in
the type it is served in: packed GPTVQ words (uint32, ``code_bits`` per
code), int8 codebooks with their float32 scales, and bfloat16 embedding,
head and norm scales. ``program_params`` wraps that payload into the
program's parameter tree (``VQLinear`` leaves with the static layout that
``quantize_model(pack=True)`` gives for the recipe). The plain reference
reads the same payload, so it never takes anything the program made.

Values are drawn so the decoded weights look like a fitted model's:
codebook entries ~ N(0, 1), scaled to int8 by each codebook's absmax as
``vq_linear.from_vq_result`` does, and a per-codebook scale that gives the
decoded weights a standard deviation of 1/sqrt(in_features). Codes are
uniform. Embedding rows past the published vocabulary are zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.spec import ModelSpec

EMBED_STD = 0.02
NORM_STD = 0.1


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed (seeds may exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _norm(key, shape):
    return (1.0 + NORM_STD * jax.random.normal(key, shape)).astype(
        jnp.bfloat16)


def _vq_matrix(key, spec: ModelSpec, L: int, r: int, c: int) -> dict:
    fmt = spec.vq
    cg, rg = fmt.plan(r, c)
    n_cg, n_bands = c // cg, r // rg
    per_word = 32 // fmt.code_bits
    kw, kc, ks = jax.random.split(key, 3)
    words = jax.random.bits(kw, (L, r, c // fmt.d // per_word), jnp.uint32)
    cb = jax.random.normal(kc, (L, n_cg, n_bands, fmt.k, fmt.d))
    qmax = 2 ** (fmt.codebook_bits - 1) - 1
    amax = jnp.max(jnp.abs(cb), axis=(-2, -1))
    step = amax / qmax
    codebooks = jnp.clip(jnp.round(cb / step[..., None, None]),
                         -qmax - 1, qmax).astype(jnp.int8)
    # per-codebook spread of a fitted layer, around the 1/sqrt(c) init
    spread = jnp.exp(0.1 * jax.random.normal(ks, step.shape))
    cb_scale = (step * spread / jnp.sqrt(c)).astype(jnp.float32)
    # blockwise normalization is off in these recipes: its leaves are zero
    return {"words": words, "codebooks": codebooks, "cb_scale": cb_scale,
            "scale_sint": jnp.zeros((L, n_cg, r, 1), jnp.int8),
            "scale_a": jnp.zeros((L, n_cg), jnp.float32),
            "scale_z": jnp.zeros((L, n_cg), jnp.float32)}


@functools.partial(jax.jit, static_argnums=(1,))
def _raw(key, spec: ModelSpec) -> dict:
    L, D, Vp = spec.n_layers, spec.d, spec.padded_vocab
    keys = iter(jax.random.split(key, 16))
    rows = jnp.arange(Vp)[:, None] < spec.vocab
    embed = jnp.where(rows, EMBED_STD * jax.random.normal(next(keys), (Vp, D)),
                      0.0).astype(jnp.bfloat16)
    raw = {"embed": embed, "final_norm": _norm(next(keys), (D,))}
    if not spec.tied:
        head = jax.random.normal(next(keys), (D, Vp)) / jnp.sqrt(D)
        raw["lm_head"] = jnp.where(rows.T, head, 0.0).astype(jnp.bfloat16)
    layers = {"norm1": _norm(next(keys), (L, D)),
              "norm2": _norm(next(keys), (L, D))}
    if spec.qk_norm:
        layers["q_norm"] = _norm(next(keys), (L, spec.hd))
        layers["k_norm"] = _norm(next(keys), (L, spec.hd))
    tkey = next(keys)
    for i, (name, (_, r, c)) in enumerate(spec.targets().items()):
        layers[name] = _vq_matrix(jax.random.fold_in(tkey, i), spec, L, r, c)
    raw["layers"] = layers
    return raw


def raw_payload(spec: ModelSpec, seed: int) -> dict:
    """Every served array of ``spec`` from ``seed`` (one device call)."""
    if spec.vq is None:
        raise ValueError(f"{spec.name}: only GPTVQ-packed weights are made")
    return _raw(seed_key(seed), spec)


def program_params(spec: ModelSpec, raw: dict, rule: str = "default"):
    """The program's parameter tree over ``raw``'s arrays (no copies)."""
    from repro.core.vq_linear import VQLinear

    lay = raw["layers"]

    def vq(name):
        _, r, c = spec.targets()[name]
        cg, rg = spec.vq.plan(r, c)
        return VQLinear(
            **lay[name], r=r, c=c, d=spec.vq.d, k=spec.vq.k, group_cols=cg,
            rows_per_band=rg, scale_block=0, rule=rule)

    attn = {n: vq(n) for n in ("wq", "wk", "wv", "wo")}
    if spec.qk_norm:
        attn["q_norm"], attn["k_norm"] = lay["q_norm"], lay["k_norm"]
    params = {"embed": raw["embed"], "final_norm": raw["final_norm"],
              "layers": {"norm1": lay["norm1"], "norm2": lay["norm2"],
                         "attn": attn,
                         "ffn": {n: vq(n) for n in
                                 ("w_gate", "w_in", "w_out")}}}
    if not spec.tied:
        params["lm_head"] = raw["lm_head"]
    return params
