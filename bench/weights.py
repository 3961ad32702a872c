"""Seeded weights in the served format: the pieces every architecture's
``raw_payload`` (``bench/arch/``) draws its arrays with.

A payload holds every array a configuration serves, drawn from the seed in
one jitted device call, in the type it is served in: packed GPTVQ words
(uint32, ``code_bits`` per code), int8 codebooks with their float32
scales, and bfloat16 embedding, head and norm scales. An architecture's
``program_params`` wraps that payload into the program's parameter tree
(``vq_linear``); its plain reference reads the same payload, so it never
takes anything the program made.

Values are drawn so the decoded weights look like a fitted model's:
codebook entries ~ N(0, 1), scaled to int8 by each codebook's absmax as
``vq_linear.from_vq_result`` does, and a per-codebook scale that gives the
decoded weights a standard deviation of 1/sqrt(in_features). Codes are
uniform. Embedding rows past the published vocabulary are zero.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EMBED_STD = 0.02
NORM_STD = 0.1


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed (seeds may exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def norm(key, shape):
    """RMSNorm scales 1 + 0.1 N(0, 1), in bfloat16."""
    return (1.0 + NORM_STD * jax.random.normal(key, shape)).astype(
        jnp.bfloat16)


def vq_matrix(key, spec, L: int, r: int, c: int) -> dict:
    """The packed leaves of an (r=out, c=in) matrix in each of ``L``
    layers, in ``spec.vq``'s format."""
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


def vq_linear(spec, leaves: dict, r: int, c: int):
    """The program's ``VQLinear`` over one matrix's packed leaves (no
    copies), with the static layout ``quantize_model(pack=True)`` gives."""
    from repro.core.vq_linear import VQLinear

    cg, rg = spec.vq.plan(r, c)
    return VQLinear(**leaves, r=r, c=c, d=spec.vq.d, k=spec.vq.k,
                    group_cols=cg, rows_per_band=rg, scale_block=0,
                    rule="default")
