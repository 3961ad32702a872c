"""The dense decoder: every layer is grouped-query attention and a SwiGLU
MLP, seven packed matrices a layer.

The plain reference is a straightforward decoder forward: embedding, then
per layer RMSNorm, q/k/v projections (per-head q/k RMSNorm where the
architecture has it), rotary embedding, causal grouped-query softmax
attention, output projection, residual, RMSNorm, SwiGLU MLP, residual;
final RMSNorm and the (tied or separate) head.

Model FLOPs, counted from shapes: every matrix product of every layer,
attention over each token's context (the rows it attends to, itself
included), and the head over the published vocabulary for each token
whose logits are needed (every decoded token; the last token of each
prompt).
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

MODEL_TYPES = ("qwen3", "llama")

# qk-norm (a per-head RMSNorm of q and k before RoPE) is part of these
# published architectures; the keys of their config.json do not say so
QK_NORM_MODEL_TYPES = ("qwen3",)


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    d: int
    n_layers: int
    n_heads: int
    n_kv: int
    hd: int
    d_ff: int
    vocab: int
    vocab_pad_multiple: int
    tied: bool
    qk_norm: bool
    rope_theta: float
    eps: float
    max_positions: int
    vq: VQFormat | None

    @property
    def padded_vocab(self) -> int:
        """Rows of the embedding and head as the program allocates them;
        the rows past ``vocab`` are zero."""
        m = self.vocab_pad_multiple
        return -(-self.vocab // m) * m

    def targets(self) -> dict[str, tuple[str, int, int]]:
        """Every matrix of a layer: name -> (group, r=out, c=in)."""
        H, KV, hd, D, F = self.n_heads, self.n_kv, self.hd, self.d, self.d_ff
        return {"wq": ("attn", H * hd, D), "wk": ("attn", KV * hd, D),
                "wv": ("attn", KV * hd, D), "wo": ("attn", D, H * hd),
                "w_gate": ("ffn", F, D), "w_in": ("ffn", F, D),
                "w_out": ("ffn", D, F)}


def spec(cfg: dict) -> ModelSpec:
    heads = cfg["num_attention_heads"]
    return ModelSpec(
        name=cfg["name"], d=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=heads,
        n_kv=cfg["num_key_value_heads"],
        hd=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        vocab_pad_multiple=cfg["assumed"]["vocab_pad_multiple"],
        tied=bool(cfg.get("tie_word_embeddings", False)),
        qk_norm=cfg["model_type"] in QK_NORM_MODEL_TYPES,
        rope_theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        max_positions=cfg["max_position_embeddings"], vq=vq_format(cfg))


def program_config(spec: ModelSpec):
    """The program's ModelConfig for these sizes."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=spec.name, family="dense", n_layers=spec.n_layers,
        d_model=spec.d, n_heads=spec.n_heads, n_kv_heads=spec.n_kv,
        head_dim=spec.hd, d_ff=spec.d_ff, vocab_size=spec.vocab,
        vocab_pad_multiple=spec.vocab_pad_multiple, activation="swiglu",
        qk_norm=spec.qk_norm, tie_embeddings=spec.tied,
        rope_theta=spec.rope_theta, norm_eps=spec.eps,
        max_seq_len=spec.max_positions, dtype="bfloat16")


# --- the seeded payload and the program's tree over it --------------------

@functools.partial(jax.jit, static_argnums=(1,))
def _raw(key, spec: ModelSpec) -> dict:
    L, D, Vp = spec.n_layers, spec.d, spec.padded_vocab
    keys = iter(jax.random.split(key, 16))
    rows = jnp.arange(Vp)[:, None] < spec.vocab
    embed = jnp.where(rows, weights.EMBED_STD
                      * jax.random.normal(next(keys), (Vp, D)),
                      0.0).astype(jnp.bfloat16)
    raw = {"embed": embed, "final_norm": weights.norm(next(keys), (D,))}
    if not spec.tied:
        head = jax.random.normal(next(keys), (D, Vp)) / jnp.sqrt(D)
        raw["lm_head"] = jnp.where(rows.T, head, 0.0).astype(jnp.bfloat16)
    layers = {"norm1": weights.norm(next(keys), (L, D)),
              "norm2": weights.norm(next(keys), (L, D))}
    if spec.qk_norm:
        layers["q_norm"] = weights.norm(next(keys), (L, spec.hd))
        layers["k_norm"] = weights.norm(next(keys), (L, spec.hd))
    tkey = next(keys)
    for i, (name, (_, r, c)) in enumerate(spec.targets().items()):
        layers[name] = weights.vq_matrix(jax.random.fold_in(tkey, i), spec,
                                         L, r, c)
    raw["layers"] = layers
    return raw


def raw_payload(spec: ModelSpec, seed: int) -> dict:
    """Every served array of ``spec`` from ``seed`` (one device call)."""
    if spec.vq is None:
        raise ValueError(f"{spec.name}: only GPTVQ-packed weights are made")
    return _raw(weights.seed_key(seed), spec)


def program_params(spec: ModelSpec, raw: dict):
    """The program's parameter tree over ``raw``'s arrays (no copies)."""
    lay = raw["layers"]

    def vq(name):
        _, r, c = spec.targets()[name]
        return weights.vq_linear(spec, lay[name], r, c)

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


# --- the plain reference ---------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(h, w, spec: ModelSpec, precision: str):
    """One decoder layer over h (B, T, D) float32."""
    B, T, _ = h.shape
    H, KV, hd = spec.n_heads, spec.n_kv, spec.hd
    x = ref.rms(h, w["norm1"], spec.eps)
    q = ref.mm(x, w["wq"], precision).reshape(B, T, H, hd)
    k = ref.mm(x, w["wk"], precision).reshape(B, T, KV, hd)
    v = ref.mm(x, w["wv"], precision).reshape(B, T, KV, hd)
    if spec.qk_norm:
        q = ref.rms(q, w["q_norm"], spec.eps)
        k = ref.rms(k, w["k_norm"], spec.eps)
    pos = jnp.arange(T)
    q = ref.rope(q, pos, spec.rope_theta)
    k = ref.rope(k, pos, spec.rope_theta)
    o = ref.causal_attention(q, k, v)
    h = h + ref.mm(o, w["wo"], precision)
    x = ref.rms(h, w["norm2"], spec.eps)
    f = jax.nn.silu(ref.mm(x, w["w_gate"], precision)) * ref.mm(
        x, w["w_in"], precision)
    return h + ref.mm(f, w["w_out"], precision)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _logits(h, idx, norm, head, spec: ModelSpec, precision: str):
    """Logits (P, V) at rows idx (P, 2) = (sequence, position) of h."""
    x = ref.rms(h[idx[:, 0], idx[:, 1]], norm, spec.eps)
    return ref.mm(x, head, precision)


def _layer_weights(raw, spec: ModelSpec, i: int) -> dict:
    lay = raw["layers"]
    w = {n: lay[n][i].astype(jnp.float32) for n in ("norm1", "norm2")}
    if spec.qk_norm:
        w["q_norm"] = lay["q_norm"][i].astype(jnp.float32)
        w["k_norm"] = lay["k_norm"][i].astype(jnp.float32)
    for name, (_, r, c) in spec.targets().items():
        one = {k: a[i] for k, a in lay[name].items()}
        w[name] = ref.decode(one, spec, r, c)
    return w


def logits_at(raw: dict, spec: ModelSpec, seqs: list[np.ndarray],
              positions: list[np.ndarray], precision: str = "f32",
              pad_to: int = 128) -> list[np.ndarray]:
    """Reference logits of each token sequence at the given positions.

    ``seqs[b]`` is a whole token sequence (prompt and served tokens);
    ``positions[b]`` the positions whose next-token logits are wanted.
    Returns one (len(positions[b]), padded_vocab) float32 array per
    sequence. ``precision`` is "f32" (the reference) or "fp8" (the
    control)."""
    assert precision in ref.PRECISIONS, precision
    toks, idx = ref.inputs(seqs, positions, pad_to)
    with jax.default_matmul_precision("highest"):
        h = raw["embed"][jnp.asarray(toks)].astype(jnp.float32)
        for i in range(spec.n_layers):
            h = _layer(h, _layer_weights(raw, spec, i), spec, precision)
        head = (raw["embed"] if spec.tied else raw["lm_head"].T).astype(
            jnp.float32)
        out = np.asarray(_logits(h, jnp.asarray(idx),
                                 raw["final_norm"].astype(jnp.float32),
                                 head, spec, precision))
    return ref.by_sequence(out, positions)


# --- work counted from shapes ----------------------------------------------

def layer_flops_per_token(spec: ModelSpec) -> float:
    per_layer = sum(r * c for _, r, c in spec.targets().values())
    return 2.0 * spec.n_layers * per_layer


def head_flops(spec: ModelSpec) -> float:
    return 2.0 * spec.d * spec.vocab


def attention_flops(spec: ModelSpec, context: float) -> float:
    """q k^T and p v over ``context`` rows, every layer."""
    return 4.0 * spec.n_layers * spec.n_heads * spec.hd * context


def decode_flops(spec: ModelSpec, n_tokens: int, context_sum: float,
                 n_logits: int) -> float:
    """FLOPs of ``n_tokens`` tokens whose contexts sum to ``context_sum``,
    ``n_logits`` of which need the head."""
    return (n_tokens * layer_flops_per_token(spec)
            + attention_flops(spec, context_sum)
            + n_logits * head_flops(spec))


def decode_vq_matmuls(spec: ModelSpec, batch: int) -> list:
    """(M, r, c) of every VQ dequant-matmul call of one decode step: each
    matrix of each layer once, at the engine's batch width."""
    return [(batch, r, c) for _ in range(spec.n_layers)
            for _, r, c in spec.targets().values()]


def paged_attention_layers(spec: ModelSpec) -> tuple[int, int, int, int]:
    """Every layer attends through the paged pool."""
    return spec.n_layers, spec.n_heads, spec.n_kv, spec.hd
