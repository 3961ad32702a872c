"""Decoder-only LM assembly: dense GQA, MoE, and xLSTM block stacks.

Homogeneous stacks (dense/MoE) are stored with a leading layer axis and
applied with ``lax.scan`` (+ remat) — essential to keep HLO size and compile
time flat in depth (80-layer qwen2-72b on 512 devices). Heterogeneous stacks
(xLSTM's mLSTM/sLSTM mix) are unrolled python-side; those archs are shallow.

Cache pytrees mirror the layer structure: stacked leaves for scanned stacks,
lists for unrolled ones.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention, common as cm, mlp, moe, xlstm


# ---------------------------------------------------------------------------
# block kinds
# ---------------------------------------------------------------------------

def block_kind(cfg: ModelConfig, i: int) -> str:
    if cfg.family == "ssm" and cfg.name.startswith("xlstm"):
        return "slstm" if i in tuple(cfg.slstm_layers) else "mlstm"
    if cfg.family == "moe":
        return "moe"
    return "dense"


def homogeneous(cfg: ModelConfig) -> bool:
    kinds = {block_kind(cfg, i) for i in range(cfg.n_layers)}
    return len(kinds) == 1 and next(iter(kinds)) in ("dense", "moe")


# ---------------------------------------------------------------------------
# per-block init / specs / apply
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, kind: str, dtype):
    k1, k2 = jax.random.split(key)
    if kind in ("dense", "moe"):
        p = {
            "norm1": jnp.ones((cfg.d_model,), dtype),
            "attn": attention.init(k1, cfg, dtype),
            "norm2": jnp.ones((cfg.d_model,), dtype),
        }
        p["ffn"] = (moe.init(k2, cfg, dtype) if kind == "moe"
                    else mlp.init(k2, cfg, dtype))
        return p
    if kind == "mlstm":
        return {"norm1": jnp.ones((cfg.d_model,), dtype),
                "core": xlstm.mlstm_init(k1, cfg, dtype)}
    if kind == "slstm":
        return {"norm1": jnp.ones((cfg.d_model,), dtype),
                "core": xlstm.slstm_init(k1, cfg, dtype)}
    raise ValueError(kind)


def _block_specs(cfg: ModelConfig, kind: str):
    if kind in ("dense", "moe"):
        return {
            "norm1": P(None),
            "attn": attention.specs(cfg),
            "norm2": P(None),
            "ffn": moe.specs(cfg) if kind == "moe" else mlp.specs(cfg),
        }
    if kind == "mlstm":
        return {"norm1": P(None), "core": xlstm.mlstm_specs(cfg)}
    if kind == "slstm":
        return {"norm1": P(None), "core": xlstm.slstm_specs(cfg)}
    raise ValueError(kind)


def _block_apply(p, cfg: ModelConfig, kind: str, x, *, pos, cache,
                 paged_impl=None):
    """Returns (x, new_cache, aux_loss)."""
    from jax.ad_checkpoint import checkpoint_name

    from repro.core import vq_linear as vql_mod
    p = vql_mod.dequant_tree(p, cm.DTYPES[cfg.dtype])  # no-op if not VQ
    aux = jnp.zeros((), jnp.float32)
    if kind in ("dense", "moe"):
        h, new_kv = attention.apply(
            p["attn"], cfg, cm.rmsnorm(x, p["norm1"], cfg.norm_eps),
            pos=pos, cache=cache, paged_impl=paged_impl)
        # named so the selective remat policy can save it (§Perf it.9):
        # backward then skips re-running the flash-attention scan
        h = checkpoint_name(h, "attn_out")
        x = x + h
        with jax.named_scope("mlp"):
            h2 = cm.rmsnorm(x, p["norm2"], cfg.norm_eps)
            if kind == "moe":
                f, aux = moe.apply(p["ffn"], cfg, h2)
            else:
                f = mlp.apply(p["ffn"], cfg, h2)
        return x + f, new_kv, aux
    if kind == "mlstm":
        h, new_c = xlstm.mlstm_apply(
            p["core"], cfg, cm.rmsnorm(x, p["norm1"], cfg.norm_eps), cache)
        return x + h, new_c, aux
    if kind == "slstm":
        xin = cm.rmsnorm(x, p["norm1"], cfg.norm_eps)
        h, new_c = xlstm.slstm_apply(p["core"], cfg, xin, cache)
        x = x + h
        x = x + xlstm.slstm_ffn(
            p["core"], cfg, cm.rmsnorm(x, p["core"]["ffn_norm"], cfg.norm_eps))
        return x, new_c, aux
    raise ValueError(kind)


def _block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                 dtype=jnp.bfloat16, paged=None):
    if kind in ("dense", "moe"):
        if paged is not None:
            return attention.init_paged_cache(cfg, batch, max_len, paged,
                                              dtype)
        return attention.init_cache(cfg, batch, max_len, dtype)
    # recurrent state is O(1) per slot — stays slot-resident even when the
    # attention leaves are paged
    if kind == "mlstm":
        return xlstm.mlstm_cache(cfg, batch)
    if kind == "slstm":
        return xlstm.slstm_cache(cfg, batch)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# whole-model init / specs
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> dict:
    dtype = cm.DTYPES[cfg.dtype]
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    params: dict[str, Any] = {
        "embed": cm.embed_init(k_emb, cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = cm.dense_init(
            k_head, cfg.d_model, cfg.padded_vocab, dtype=dtype)
    keys = jax.random.split(k_layers, cfg.n_layers)
    if homogeneous(cfg):
        kind = block_kind(cfg, 0)
        params["layers"] = jax.vmap(
            lambda k: _block_init(k, cfg, kind, dtype))(keys)
    else:
        params["layers"] = [
            _block_init(keys[i], cfg, block_kind(cfg, i), dtype)
            for i in range(cfg.n_layers)
        ]
    return params


def param_specs(cfg: ModelConfig) -> dict:
    specs: dict[str, Any] = {
        "embed": P("model", "data"),
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P("data", "model")
    if homogeneous(cfg):
        kind = block_kind(cfg, 0)
        one = _block_specs(cfg, kind)
        specs["layers"] = jax.tree.map(
            lambda s: P(None, *s), one,
            is_leaf=lambda x: isinstance(x, P))
    else:
        specs["layers"] = [
            _block_specs(cfg, block_kind(cfg, i)) for i in range(cfg.n_layers)
        ]
    return specs


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, paged=None):
    """``paged``: an attention.PagedLayout — attention leaves become shared
    block pools + per-slot page tables (serving); None keeps the dense
    (B, max_len) layout (training/eval)."""
    if homogeneous(cfg):
        kind = block_kind(cfg, 0)
        one = _block_cache(cfg, kind, batch, max_len, dtype, paged)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.n_layers, *x.shape)), one)
    return [
        _block_cache(cfg, block_kind(cfg, i), batch, max_len, dtype, paged)
        for i in range(cfg.n_layers)
    ]


def cache_specs(cfg: ModelConfig):
    """Sharding for KV caches: batch over (pod, data), heads over model.

    For long-context single-sequence decode the sequence dim of attention
    caches is sharded over 'data' instead (sequence parallelism) — see
    launch/dryrun.py which picks the spec based on the shape cell.
    """
    def kv_spec(_):
        return P(None, ("pod", "data"), None, "model", None) \
            if homogeneous(cfg) else P(("pod", "data"), None, "model", None)

    if homogeneous(cfg):
        one = _block_cache(cfg, block_kind(cfg, 0), 1, 8)
        return jax.tree.map(lambda x: kv_spec(x), one)
    out = []
    for i in range(cfg.n_layers):
        kind = block_kind(cfg, i)
        one = _block_cache(cfg, kind, 1, 8)
        if kind in ("dense", "moe"):
            out.append(jax.tree.map(lambda x: P(("pod", "data"), None, "model", None), one))
        else:
            out.append(jax.tree.map(lambda x: P(("pod", "data")), one))
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tokens, extra_embeds=None):
    x = params["embed"][tokens]  # gather
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    return x


def unembed(params, cfg: ModelConfig, x):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return cm.matmul(x, w).astype(jnp.float32)


def forward(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,
    *,
    pos: jax.Array | int = 0,
    cache=None,
    extra_embeds=None,
    remat: bool = True,
    last_only: bool = False,
    paged_impl: str | None = None,
    vq_matmul_impl: str | None = None,
):
    """Returns (logits, new_cache, aux_loss). ``paged_impl`` selects the
    decode attention backend over PagedKVCache leaves (see
    attention._paged_apply); None falls back to the module default.
    ``vq_matmul_impl`` re-stamps FusedVQLinear leaves ("gather" | "xla" |
    "pallas" | "fused") — static metadata only, so each jitted closure
    bakes its own VQ backend (see core/vq_linear)."""
    from repro.core import vq_linear as vql_mod
    if vq_matmul_impl is not None:
        params = vql_mod.retag_fused(params, vq_matmul_impl)
    top = {k: v for k, v in params.items() if k != "layers"}
    params = {**params, **vql_mod.dequant_tree(top, cm.DTYPES[cfg.dtype])}
    with jax.named_scope("embed"):
        x = embed_tokens(params, cfg, tokens, extra_embeds)
    dp = _dp_axes()
    if dp and tokens.shape[0] % _axes_size(dp) == 0:
        x = jax.lax.with_sharding_constraint(x, P(dp, None, None))

    # mixed-precision recipes (core/recipe.py) make per-layer packed
    # metadata heterogeneous, so finalize falls back to a list of layer
    # trees even for a homogeneous stack — the layer loop below handles
    # that (and slices/updates a stacked cache per layer); the scan fast
    # path needs the layers actually stacked.
    layers_stacked = not isinstance(params["layers"], list)
    if homogeneous(cfg) and layers_stacked:
        kind = block_kind(cfg, 0)

        if cache is None:
            # Megatron-style sequence parallelism at layer boundaries: the
            # scan carry (the only tensor live for every layer's backward
            # residuals) shards its seq dim over 'model' instead of being
            # replicated — 16x less stored activation at qwen2-72b scale
            # (§Perf iteration 2). XLA re-gathers inside the block where
            # attention needs the full sequence. (The MoE shard_map path
            # re-gathers the sequence at its boundary — in_specs are
            # authoritative — so SP composes with expert parallelism.)
            sp = (_dp_axes() is not None
                  and x.shape[1] % _axes_size(("model",)) == 0)

            def body(carry, layer_p):
                h = carry
                if sp:
                    h = jax.lax.with_sharding_constraint(
                        h, P(_dp_axes(), "model", None))
                h, new_c, aux = _block_apply(
                    layer_p, cfg, kind, h, pos=pos, cache=None)
                return h, aux

            if remat == "save_attn":
                # selective remat: keep the per-layer attention outputs
                # resident so backward recompute skips the attention fwd
                # (the expensive part of the 1.33x re-forward budget) at
                # the cost of one extra (B,S,D) per layer (§Perf it.9)
                policy = jax.checkpoint_policies.save_only_these_names(
                    "attn_out")
                body_fn = jax.checkpoint(body, policy=policy)
            elif remat:
                body_fn = jax.checkpoint(body)
            else:
                body_fn = body
            x, auxs = jax.lax.scan(body_fn, x, params["layers"])
            new_cache = None
        else:
            # cache travels in the CARRY and is updated layer-slice in
            # place: with donated inputs XLA aliases the whole ring of
            # buffers, halving decode HBM vs a scan-ys cache (EXPERIMENTS
            # §Perf iteration 1).
            def body(carry, layer_p):
                h, cache_all, i = carry
                with jax.named_scope("layer_cache_read"):
                    layer_cache = jax.tree.map(
                        lambda a: jax.lax.dynamic_index_in_dim(
                            a, i, 0, keepdims=False), cache_all)
                h, new_c, aux = _block_apply(
                    layer_p, cfg, kind, h, pos=pos, cache=layer_cache,
                    paged_impl=paged_impl)
                with jax.named_scope("layer_cache_write"):
                    cache_all = jax.tree.map(
                        lambda a, n: jax.lax.dynamic_update_index_in_dim(
                            a, n.astype(a.dtype), i, 0), cache_all, new_c)
                return (h, cache_all, i + 1), aux

            body_fn = jax.checkpoint(body) if remat else body
            (x, new_cache, _), auxs = jax.lax.scan(
                body_fn, (x, cache, jnp.zeros((), jnp.int32)),
                params["layers"])
        aux = jnp.sum(auxs)
    else:
        # cache layout follows init_cache: a per-layer list for
        # heterogeneous configs, a layer-stacked tree for homogeneous
        # configs whose params went heterogeneous (mixed recipe)
        cache_is_list = isinstance(cache, list)
        new_cache = [] if cache_is_list or cache is None else cache
        aux = jnp.zeros((), jnp.float32)
        for i, layer_p in enumerate(params["layers"]):
            kind = block_kind(cfg, i)
            if cache is None:
                c_i = None
            elif cache_is_list:
                c_i = cache[i]
            else:
                with jax.named_scope("layer_cache_read"):
                    c_i = jax.tree.map(lambda a: a[i], cache)
            fn = functools.partial(_block_apply, layer_p, cfg, kind,
                                   pos=pos, cache=c_i,
                                   paged_impl=paged_impl)
            if remat:
                fn = jax.checkpoint(lambda h, _fn=fn: _fn(h))
            x, new_c, a = fn(x)
            if cache_is_list:
                new_cache.append(new_c)
            elif cache is not None:
                with jax.named_scope("layer_cache_write"):
                    new_cache = jax.tree.map(
                        lambda a, n: a.at[i].set(n.astype(a.dtype)),
                        new_cache, new_c)
            aux = aux + a
        if cache is None:
            new_cache = None

    with jax.named_scope("head"):
        if last_only:
            x = x[:, -1:]  # prefill: only the next-token logits are needed
            # — avoids materializing the (B, S, V) tensor (638 TB for
            # qwen2-72b prefill_32k before this slice; EXPERIMENTS §Dry-run)
        x = cm.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(params, cfg, x)
    dp = _dp_axes()
    if dp and logits.shape[0] % _axes_size(dp) == 0:
        logits = jax.lax.with_sharding_constraint(logits, P(dp, None, "model"))
    return logits, new_cache, aux


def _ambient_mesh():
    try:
        import jax._src.mesh as jmesh
        m = jmesh.thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:
        return None


def _dp_axes():
    """Data-parallel axes present in the ambient mesh ('pod' on multi-pod)."""
    m = _ambient_mesh()
    if m is None:
        return None
    dp = tuple(a for a in ("pod", "data") if a in m.axis_names)
    return dp or None


def _axes_size(axes) -> int:
    m = _ambient_mesh()
    size = dict(zip(m.axis_names, m.devices.shape))
    total = 1
    for a in axes:
        total *= size[a]
    return total
