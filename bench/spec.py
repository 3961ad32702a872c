"""A benchmark configuration file, read into the sizes the harness needs.

Configuration files (``bench/configs/<name>.json``) keep the published
``config.json`` keys at their top level, as the source states them, beside
the benchmark's own groups: ``weights`` (the served format), ``engine``
(the serving engine's options), ``reduced`` and ``assumed``.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

# qk-norm (a per-head RMSNorm of q and k before RoPE) is part of these
# published architectures; the keys of their config.json do not say so
QK_NORM_MODEL_TYPES = ("qwen3",)


@dataclasses.dataclass(frozen=True)
class VQFormat:
    """GPTVQ packed-weight format: d-dim codes of ``k`` entries, one int8
    codebook per (``group_cols`` columns x ``group_size // group_cols``
    rows) group."""
    d: int
    k: int
    group_size: int
    group_cols: int
    codebook_bits: int

    @property
    def code_bits(self) -> int:
        return (self.k - 1).bit_length()

    def plan(self, r: int, c: int) -> tuple[int, int]:
        """(group_cols, rows_per_band) of an (r=out, c=in) matrix: the
        widest divisor of c up to the group's columns, then the most rows
        up to the group's size."""
        cg = max(x for x in range(self.d, min(self.group_cols,
                                              self.group_size) + 1, self.d)
                 if c % x == 0)
        rg = max(x for x in range(1, max(1, self.group_size // cg) + 1)
                 if r % x == 0)
        return cg, rg


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

    @classmethod
    def from_config(cls, cfg: dict) -> "ModelSpec":
        heads = cfg["num_attention_heads"]
        hd = cfg.get("head_dim") or cfg["hidden_size"] // heads
        pad = cfg["assumed"]["vocab_pad_multiple"]
        w = cfg["weights"]
        vq = None
        if w["format"] == "gptvq":
            k = round(2 ** (w["d"] * w["bits_per_dim"]))
            vq = VQFormat(d=w["d"], k=k, group_size=w["group_size"],
                          group_cols=w["group_cols"],
                          codebook_bits=w["codebook_bits"])
        return cls(
            name=cfg["name"], d=cfg["hidden_size"],
            n_layers=cfg["num_hidden_layers"], n_heads=heads,
            n_kv=cfg["num_key_value_heads"], hd=hd,
            d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            vocab_pad_multiple=pad,
            tied=bool(cfg.get("tie_word_embeddings", False)),
            qk_norm=cfg["model_type"] in QK_NORM_MODEL_TYPES,
            rope_theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
            max_positions=cfg["max_position_embeddings"], vq=vq)

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

    def program_config(self):
        """The program's ModelConfig for these sizes."""
        from repro.configs.base import ModelConfig

        return ModelConfig(
            name=self.name, family="dense", n_layers=self.n_layers,
            d_model=self.d, n_heads=self.n_heads, n_kv_heads=self.n_kv,
            head_dim=self.hd, d_ff=self.d_ff, vocab_size=self.vocab,
            vocab_pad_multiple=self.vocab_pad_multiple, activation="swiglu",
            qk_norm=self.qk_norm, tie_embeddings=self.tied,
            rope_theta=self.rope_theta, norm_eps=self.eps,
            max_seq_len=self.max_positions, dtype="bfloat16")


def load(path: str | Path) -> dict:
    return json.loads(Path(path).read_text())
