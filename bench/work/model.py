"""Model FLOPs of the dense decoder, counted from shapes: every matrix
product of every layer, attention over each token's context (the rows it
attends to, itself included), and the head over the published vocabulary
for each token whose logits are needed (every decoded token; the last
token of each prompt)."""
from __future__ import annotations

from bench.spec import ModelSpec


def layer_flops_per_token(spec: ModelSpec) -> float:
    per_layer = sum(r * c for _, r, c in spec.targets().values())
    return 2.0 * spec.n_layers * per_layer


def head_flops(spec: ModelSpec) -> float:
    return 2.0 * spec.d * spec.vocab


def attention_flops(spec: ModelSpec, context: float) -> float:
    """q k^T and p v over ``context`` rows, every layer."""
    return 4.0 * spec.n_layers * spec.n_heads * spec.hd * context


def flops(spec: ModelSpec, n_tokens: int, context_sum: float,
          n_logits: int) -> float:
    """FLOPs of ``n_tokens`` tokens whose contexts sum to ``context_sum``,
    ``n_logits`` of which need the head."""
    return (n_tokens * layer_flops_per_token(spec)
            + attention_flops(spec, context_sum)
            + n_logits * head_flops(spec))
