"""Prefill / decode step builders.

Parameter trees may contain ``VQLinear`` leaves (bit-packed GPTVQ
weights), dequantized per layer-slice inside the layer scan
(core/vq_linear.dequant_tree — the "gather" path), or engine-prepped
``FusedVQLinear`` leaves whose matmuls run fused (``vq_impl`` "xla" /
"pallas": the dense weight never materializes; see core/vq_linear). Either
way these steps are agnostic to whether the model is dense bf16 or
VQ-compressed — the paper's technique is a drop-in serving format.

``make_paged_decode`` / ``make_slot_prefill`` are the paged serving
engine's fully-compiled tick functions (per-slot position vectors, page
tables, chunked prefill over B=1 slot views). ``make_prefill`` /
``make_decode`` remain the dense-cache builders used by launch/dryrun and
as the correctness reference for the paged path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.model_zoo import Model


def make_prefill(model: Model, last_only: bool = False):
    """Whole-prompt prefill from position 0 over a dense cache (dry-run and
    benchmark baselines). last_only=True returns only next-token logits —
    required at 32k+ sequence lengths where full (B, S, V) logits would
    dominate memory."""
    def prefill(params, batch, cache):
        logits, cache, _ = model.forward(params, batch, cache=cache, pos=0,
                                         last_only=last_only)
        return logits, cache

    return prefill


def make_decode(model: Model):
    def decode(params, tokens, cache, pos):
        """tokens: (B, S) int32; pos: scalar start position, or a per-slot
        (B,) vector when the cache is paged (each continuous-batching slot
        writes/attends at its own depth)."""
        logits, cache, _ = model.forward(
            params, {"tokens": tokens}, cache=cache, pos=pos)
        return logits, cache

    return decode


def make_paged_decode(model: Model, axes, paged_impl: str = "gather",
                      vq_impl: str | None = None):
    """One fully-compiled decode tick over a paged cache. ``axes`` is the
    per-leaf batch-axis tree from paged_cache.batch_axes. Folding the
    page-table refresh, the mid-prefill row restore, the PRNG split, AND
    the per-slot sampling into the jitted step keeps the tick at a single
    dispatch with a (B,) int32 device->host transfer — the eager tree-map
    variant cost more host time than the forward itself, and the separate
    sample dispatch + (B, V) logits round-trip dominated the batch=1
    decode gap vs the legacy dense engine (BENCH_serve.json).

    ``paged_impl`` is captured by the closure and threaded through the
    forward to attention._paged_apply — each engine's jitted decode bakes
    its own backend, no module-global mutation involved. ``vq_impl`` does
    the same for VQ-packed weight leaves (core/vq_linear.fused_matmul
    dispatch): the impl re-stamp is static metadata, so the backend is
    part of the traced graph."""
    from repro.serve import paged_cache as pc
    from repro.serve import sampling

    def decode(params, tokens, cache, pos, table, keep_mask, key, temps):
        """tokens (B, 1); pos (B,) per-slot write positions; table
        (B, n_pages) page rows for decoding slots (scratch elsewhere);
        keep_mask (B,) marks slots whose recurrent-state rows must keep
        their pre-tick values (slots still mid-prefill); key is the
        engine PRNG key (split in-graph, new key returned); temps (B,)
        per-slot temperatures (<= 0 greedy)."""
        with jax.named_scope("push_page_table"):
            cache = pc.push_page_table(cache, table)
        logits, new_cache, _ = model.forward(
            params, {"tokens": tokens}, cache=cache, pos=pos,
            paged_impl=paged_impl, vq_matmul_impl=vq_impl)
        with jax.named_scope("sample"):
            key, sub = jax.random.split(key)
            nxt = sampling.sample(sub, logits[:, -1], temperature=temps)
        with jax.named_scope("restore_masked"):
            new_cache = pc.restore_masked(cache, new_cache, axes, keep_mask)
        return nxt, key, new_cache

    return decode


def make_slot_prefill(model: Model, axes, vq_impl: str | None = None):
    """One fully-compiled chunked-prefill step: push the page table, slice
    a B=1 view of ``slot`` (traced — one trace serves every slot), run the
    chunk from position ``start``, merge the view back. Retraces only per
    power-of-two chunk width."""
    from repro.serve import paged_cache as pc

    def chunk(params, tokens, cache, slot, start, last_idx, table):
        with jax.named_scope("push_page_table"):
            cache = pc.push_page_table(cache, table)
        with jax.named_scope("slot_view"):
            view = pc.slot_view_dyn(cache, axes, slot)
        # prefill is pinned to the gather read path — including width-1
        # tail chunks, which would otherwise satisfy the fused path's
        # S == 1 shape test
        logits, new_view, _ = model.forward(
            params, {"tokens": tokens}, cache=view,
            pos=jnp.full((1,), start, jnp.int32), paged_impl="gather",
            vq_matmul_impl=vq_impl)
        # only the last *real* token's logits ever get sampled (chunks may
        # be padded up to their power-of-two bucket) — returning (V,)
        # instead of (1, C, V) keeps the host transfer flat
        with jax.named_scope("head"):
            last = jax.lax.dynamic_index_in_dim(logits[0], last_idx, 0,
                                                keepdims=False)
        with jax.named_scope("slot_merge"):
            cache = pc.slot_merge_dyn(cache, new_view, axes, slot)
        return last, cache

    return chunk
