"""A throwaway architecture for the harness's tests: a decoder whose
feed-forward is the program's non-gated relu^2, ``relu(x W_in)^2 W_out``,
with no ``w_gate``, no qk-norm and a tied head.

``test_bench_arch.py`` copies it into a copy of the benchmark as
``bench/arch/toy_relu2.py``: a new architecture as a new file, built from
the shared pieces of ``bench/weights.py`` and ``bench/reference.py``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref
from bench import weights
from bench.spec import VQFormat, vq_format

MODEL_TYPES = ("toy_relu2",)


@dataclasses.dataclass(frozen=True)
class Spec:
    name: str
    d: int
    n_layers: int
    n_heads: int
    n_kv: int
    hd: int
    d_ff: int
    vocab: int
    vocab_pad_multiple: int
    rope_theta: float
    eps: float
    max_positions: int
    vq: VQFormat | None

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return -(-self.vocab // m) * m

    def targets(self) -> dict[str, tuple[int, int]]:
        """Every matrix of a layer: name -> (r=out, c=in)."""
        H, KV, hd, D, F = self.n_heads, self.n_kv, self.hd, self.d, self.d_ff
        return {"wq": (H * hd, D), "wk": (KV * hd, D), "wv": (KV * hd, D),
                "wo": (D, H * hd), "w_in": (F, D), "w_out": (D, F)}


def spec(cfg: dict) -> Spec:
    return Spec(name=cfg["name"], d=cfg["hidden_size"],
                n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"],
                n_kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                vocab_pad_multiple=cfg["assumed"]["vocab_pad_multiple"],
                rope_theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]),
                max_positions=cfg["max_position_embeddings"],
                vq=vq_format(cfg))


def program_config(spec: Spec):
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=spec.name, family="dense", n_layers=spec.n_layers,
        d_model=spec.d, n_heads=spec.n_heads, n_kv_heads=spec.n_kv,
        head_dim=spec.hd, d_ff=spec.d_ff, vocab_size=spec.vocab,
        vocab_pad_multiple=spec.vocab_pad_multiple, activation="relu2",
        qk_norm=False, tie_embeddings=True, rope_theta=spec.rope_theta,
        norm_eps=spec.eps, max_seq_len=spec.max_positions, dtype="bfloat16")


@functools.partial(jax.jit, static_argnums=(1,))
def _raw(key, spec: Spec) -> dict:
    L, D, Vp = spec.n_layers, spec.d, spec.padded_vocab
    k_embed, k_final, k_norm1, k_norm2, k_mats = jax.random.split(key, 5)
    rows = jnp.arange(Vp)[:, None] < spec.vocab
    embed = jnp.where(rows, weights.EMBED_STD
                      * jax.random.normal(k_embed, (Vp, D)),
                      0.0).astype(jnp.bfloat16)
    layers = {"norm1": weights.norm(k_norm1, (L, D)),
              "norm2": weights.norm(k_norm2, (L, D))}
    for i, (name, (r, c)) in enumerate(spec.targets().items()):
        layers[name] = weights.vq_matrix(jax.random.fold_in(k_mats, i), spec,
                                         L, r, c)
    return {"embed": embed, "final_norm": weights.norm(k_final, (D,)),
            "layers": layers}


def raw_payload(spec: Spec, seed: int) -> dict:
    return _raw(weights.seed_key(seed), spec)


def program_params(spec: Spec, raw: dict):
    lay = raw["layers"]

    def vq(name):
        return weights.vq_linear(spec, lay[name], *spec.targets()[name])

    return {"embed": raw["embed"], "final_norm": raw["final_norm"],
            "layers": {"norm1": lay["norm1"], "norm2": lay["norm2"],
                       "attn": {n: vq(n) for n in ("wq", "wk", "wv", "wo")},
                       "ffn": {n: vq(n) for n in ("w_in", "w_out")}}}


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(h, w, spec: Spec, precision: str):
    B, T, _ = h.shape
    x = ref.rms(h, w["norm1"], spec.eps)
    q = ref.mm(x, w["wq"], precision).reshape(B, T, spec.n_heads, spec.hd)
    k = ref.mm(x, w["wk"], precision).reshape(B, T, spec.n_kv, spec.hd)
    v = ref.mm(x, w["wv"], precision).reshape(B, T, spec.n_kv, spec.hd)
    pos = jnp.arange(T)
    o = ref.causal_attention(ref.rope(q, pos, spec.rope_theta),
                             ref.rope(k, pos, spec.rope_theta), v)
    h = h + ref.mm(o, w["wo"], precision)
    x = ref.rms(h, w["norm2"], spec.eps)
    f = jnp.square(jax.nn.relu(ref.mm(x, w["w_in"], precision)))
    return h + ref.mm(f, w["w_out"], precision)


def logits_at(raw: dict, spec: Spec, seqs, positions, precision="f32",
              pad_to: int = 128) -> list:
    assert precision in ref.PRECISIONS, precision
    toks, idx = ref.inputs(seqs, positions, pad_to)
    lay = raw["layers"]
    with jax.default_matmul_precision("highest"):
        h = raw["embed"][jnp.asarray(toks)].astype(jnp.float32)
        for i in range(spec.n_layers):
            w = {n: lay[n][i].astype(jnp.float32) for n in ("norm1", "norm2")}
            for name, (r, c) in spec.targets().items():
                w[name] = ref.decode({k: a[i] for k, a in lay[name].items()},
                                     spec, r, c)
            h = _layer(h, w, spec, precision)
        x = ref.rms(h[idx[:, 0], idx[:, 1]],
                    raw["final_norm"].astype(jnp.float32), spec.eps)
        out = np.asarray(ref.mm(x, raw["embed"].astype(jnp.float32),
                                precision))
    return ref.by_sequence(out, positions)


def decode_flops(spec: Spec, n_tokens: int, context_sum: float,
                 n_logits: int) -> float:
    per_token = sum(r * c for r, c in spec.targets().values())
    return (2.0 * spec.n_layers * per_token * n_tokens
            + 4.0 * spec.n_layers * spec.n_heads * spec.hd * context_sum
            + 2.0 * spec.d * spec.vocab * n_logits)


def decode_vq_matmuls(spec: Spec, batch: int) -> list:
    return [(batch, r, c) for _ in range(spec.n_layers)
            for r, c in spec.targets().values()]


def paged_attention_layers(spec: Spec) -> tuple[int, int, int, int]:
    return spec.n_layers, spec.n_heads, spec.n_kv, spec.hd
