"""Architectures: one module per architecture gives every step of a cell
that depends on the model's architecture.

``load(cfg)`` imports each module of this package and returns the one
whose ``MODEL_TYPES`` holds the configuration's published ``model_type``.
A type that no module, or more than one, claims is an error that names it
and the known types, raised while the cell is loaded, before any device
work. There is no default architecture.

A new architecture comes as new files only: ``bench/arch/<name>.py``, its
configuration (``bench/configs/``), the limits of its cells
(``bench/limits/``), any readers of its own (``bench/metrics/``) and its
entries in ``BENCHMARK.json``. No other file of the harness changes. The
module provides:

``MODEL_TYPES``
    the published ``model_type`` values it serves (a tuple of str).
``spec(cfg) -> spec``
    a frozen, hashable object of the sizes read from the configuration,
    with at least ``name``, ``vocab`` (the published vocabulary),
    ``padded_vocab`` (the embedding's rows as the program allocates them),
    ``n_layers`` and ``vq`` (the ``bench.spec.VQFormat`` of its packed
    weights, or None).
``raw_payload(spec, seed) -> dict``
    every served array, drawn from the seed in one jitted device call in
    the type it is served in (``bench/weights.py`` has the pieces).
``program_config(spec)``
    the program's ``ModelConfig`` for these sizes.
``program_params(spec, raw)``
    the program's parameter tree over the payload's arrays, no copies.
``logits_at(raw, spec, seqs, positions, precision="f32") -> list``
    the plain float32 reference (``precision="f32"``) and its control
    (``"fp8"``, every matrix product in float8): one (len(positions[b]),
    padded_vocab) float32 array of next-token logits per sequence. It
    imports nothing of the program, runs at
    ``default_matmul_precision("highest")``, and builds on
    ``bench/reference.py``.
``decode_flops(spec, n_tokens, context_sum, n_logits) -> float``
    the model FLOPs of ``n_tokens`` decoded tokens whose contexts sum to
    ``context_sum``, ``n_logits`` of which need the head (``mfu.decode``
    divides by them).
``decode_vq_matmuls(spec, batch) -> list | None``
    the ``(M, r, c)`` of every VQ dequant-matmul call of one decode step at
    the engine's batch width ``batch``; None where routing decides the
    calls, and ``vq_matmul_roofline`` then reports nothing.
``paged_attention_layers(spec) -> (n_layers, n_heads, n_kv, hd)``
    how many layers attend through the paged pool, and their heads
    (``paged_attn_roofline``); 0 layers where none does.
"""
from __future__ import annotations

import importlib
import pkgutil


def modules() -> list:
    """Every architecture module of this package."""
    return [importlib.import_module(f"{__name__}.{m.name}")
            for m in pkgutil.iter_modules(__path__)
            if not m.name.startswith("_")]


def load(cfg: dict):
    """The one architecture module that serves ``cfg["model_type"]``."""
    model_type = cfg.get("model_type")
    mods = modules()
    hits = [m for m in mods if model_type in m.MODEL_TYPES]
    if len(hits) != 1:
        known = sorted(t for m in mods for t in m.MODEL_TYPES)
        what = (f"{' and '.join(m.__name__ for m in hits)} all serve"
                if hits else "no architecture serves")
        raise SystemExit(f"bench: {what} model_type {model_type!r} of "
                         f"{cfg.get('name')!r}; known: {known}")
    return hits[0]
