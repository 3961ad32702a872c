"""GQA attention: init/specs/apply, flash-style chunked softmax, KV cache.

Layout convention: activations (B, S, D); q/k/v (B, S, H, hd).
The chunked path (two-level scan with online softmax) keeps the score tile
at (B, KV, G, Tq, Ts) so 32k-token prefill fits VMEM-scale working sets —
the pure-JAX analogue of flash attention; a Pallas version is a §Perf item.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import common as cm
from repro.obs import dispatch as obs_dispatch

NEG_INF = -1e30

# Flash-attention backend: "xla" (portable two-level scan, the default and
# the dry-run path) or "pallas" (kernels/flash_attention.py — the TPU fast
# path; runs in interpret mode off-TPU). Set via set_flash_impl().
# ``counts`` records how often each impl was *dispatched* (trace-time for
# jitted callers) — the regression tests pin dispatch decisions against it
# through the obs.dispatch API (snapshot_dispatch_counters /
# reset_dispatch_counters); the registered dict here IS the live counter,
# so the bump sites stay one plain increment on the trace path.
_FLASH_IMPL = {"impl": "xla",
               "counts": obs_dispatch.register_dispatch(
                   "flash", ("xla", "pallas"))}


def set_flash_impl(impl: str):
    assert impl in ("xla", "pallas")
    _FLASH_IMPL["impl"] = impl


# Paged decode-attention backend for _paged_apply's S == 1 path:
#   "gather" — scatter then attend over the page-table-gathered logical
#              view (portable XLA; the pre-fused path and the baseline)
#   "xla"    — kernels/ref.paged_attention_ref via kernels/ops (the oracle;
#              same math routed through the fused dispatch boundary)
#   "pallas" — kernels/paged_attention.py fused TPU kernel (in-kernel page
#              gather; interpret mode off-TPU — tests only, not a perf path)
# The serving engine threads its choice explicitly (apply(paged_impl=...),
# captured per-engine by serve_step's jitted closures; prefill is pinned to
# "gather" there even for width-1 chunks). This module global is only the
# default for callers that don't pass one — it is read at trace time.
_PAGED_IMPL = {"impl": "gather",
               "counts": obs_dispatch.register_dispatch(
                   "paged", ("gather", "xla", "pallas"))}


def set_paged_impl(impl: str):
    assert impl in ("gather", "xla", "pallas")
    _PAGED_IMPL["impl"] = impl


def init(key, cfg: ModelConfig, dtype=jnp.float32, d_in: int | None = None):
    D = d_in or cfg.d_model
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": cm.dense_init(ks[0], D, H * hd, dtype=dtype),
        "wk": cm.dense_init(ks[1], D, KV * hd, dtype=dtype),
        "wv": cm.dense_init(ks[2], D, KV * hd, dtype=dtype),
        "wo": cm.dense_init(ks[3], H * hd, cfg.d_model, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def specs(cfg: ModelConfig):
    s = {
        "wq": P("data", "model"),
        "wk": P("data", "model"),
        "wv": P("data", "model"),
        "wo": P("model", "data"),
    }
    if cfg.qkv_bias:
        s.update({"bq": P("model"), "bk": P("model"), "bv": P("model")})
    if cfg.qk_norm:
        s.update({"q_norm": P(None), "k_norm": P(None)})
    return s


class KVCache(NamedTuple):
    k: jax.Array  # (B, S_max, KV, hd)
    v: jax.Array


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, max_len, cfg.n_kv_heads, cfg.hd)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


# ---------------------------------------------------------------------------
# paged KV cache (serving)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KVQuantSpec:
    """Storage format of the paged KV pool.

    ``bits=16`` is passthrough: pages hold the cache dtype verbatim (the
    default; bf16/fp32 depending on the engine). ``bits`` in {8, 4} stores
    int8 code pages (int4 packed two-per-byte along the head dim) plus f32
    per-row per-kv-head scales that page alongside them — page writes
    quantize in-graph and every read path (gather / XLA oracle / fused
    Pallas kernel) dequantizes on the fly through kernels/kv_quant.py, so
    a logical fp view of the pool is never materialized.

    ``mode="vq"`` (``KVQuantSpec.of("vq2")``; bits=2) stores vector-
    quantized pages instead: 4-bit codebook indices over d=2 vectors
    along the head dim (2 bits per value), against per-(pool, kv-head)
    codebooks carried as cache leaves (``PagedKVCache.k_codebook`` /
    ``v_codebook``). Per-row amax scales are kept, so the zero-row and
    stale-row invariants are identical to the scalar formats.
    """
    bits: int = 16
    mode: str = "scalar"

    def __post_init__(self):
        assert self.mode in ("scalar", "vq"), self.mode
        if self.mode == "vq":
            assert self.bits == 2, self.bits
        else:
            assert self.bits in (16, 8, 4), self.bits

    @classmethod
    def of(cls, bits) -> "KVQuantSpec":
        """Parse an engine/CLI ``kv_cache_bits`` value: 16/8/4 or the
        string "vq2"."""
        if isinstance(bits, KVQuantSpec):
            return bits
        from repro.kernels import kv_quant
        if bits == kv_quant.VQ_BITS:
            return cls(bits=2, mode="vq")
        return cls(bits=int(bits))

    @property
    def quantized(self) -> bool:
        return self.bits < 16

    @property
    def vq(self) -> bool:
        return self.mode == "vq"

    @property
    def fmt(self):
        """The kernels/kv_quant.py format token: int bits or "vq2"
        (what the byte-accounting helpers take as ``bits``)."""
        from repro.kernels import kv_quant
        return kv_quant.VQ_BITS if self.vq else self.bits

    def storage_cols(self, hd: int) -> int:
        from repro.kernels import kv_quant
        return kv_quant.storage_cols(hd, self.fmt) if self.quantized else hd


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Shape of a paged KV pool: ``num_blocks`` fixed-size blocks of
    ``page_size`` tokens each, shared by every serving slot.

    Block 0 is reserved as a scratch block: page-table entries of inactive
    slots point there, so their (discarded) decode writes never touch live
    data. The serve-side allocator (serve/paged_cache.py) hands out block
    ids 1..num_blocks-1.

    ``kv`` is the page storage format (KVQuantSpec). Carrying it on the
    layout means every family's ``init_cache`` builds quantized pools with
    no signature change, and the cache leaves self-describe their format
    to the read/write paths (the spec can never disagree with the storage).
    """
    num_blocks: int
    page_size: int
    kv: KVQuantSpec = KVQuantSpec()

    def n_pages(self, max_len: int) -> int:
        return -(-max_len // self.page_size)


class PagedKVCache(NamedTuple):
    """KV pool + per-slot page table.

    ``k``/``v`` carry NO batch axis — blocks are a shared pool; which slot
    owns which block is entirely encoded in ``page_table`` (logical page p
    of slot b lives in physical block ``page_table[b, p]``). Keeping the
    page table a cache *leaf* means the family assemblies' layer scans
    thread it exactly like any dense cache leaf — no forward-signature
    change beyond ``pos`` accepting per-slot vectors.

    Quantized pools (KVQuantSpec bits < 16) store int8 code pages in
    ``k``/``v`` (int4 packed two codes per byte, so the last axis is
    hd//2) and per-row per-kv-head f32 scales in ``k_scale``/``v_scale``;
    passthrough pools leave the scale leaves None (jax treats None as an
    empty subtree, so the pytree contract of every existing caller is
    unchanged).

    Vector-quantized pools (KVQuantSpec mode "vq") additionally carry
    the frozen per-(pool, kv-head) codebooks as cache leaves
    (``k_codebook``/``v_codebook``, (KV, 16, 2) f32); ``k``/``v`` then
    hold packed 4-bit codebook indices (last axis hd//4). Codebook
    presence — not the spec — is what the read/write paths key on, the
    same self-description rule the scalar formats use for scales.
    """
    k: jax.Array           # (num_blocks, page_size, KV, storage_cols)
    v: jax.Array           # (num_blocks, page_size, KV, storage_cols)
    page_table: jax.Array  # (B, n_pages) int32; 0 = scratch block
    k_scale: jax.Array | None = None  # (num_blocks, page_size, KV) f32
    v_scale: jax.Array | None = None  # (num_blocks, page_size, KV) f32
    k_codebook: jax.Array | None = None  # (KV, VQ_K, VQ_D) f32
    v_codebook: jax.Array | None = None  # (KV, VQ_K, VQ_D) f32


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int,
                     layout: PagedLayout, dtype=jnp.bfloat16) -> PagedKVCache:
    table = jnp.zeros((batch, layout.n_pages(max_len)), jnp.int32)
    if layout.kv.quantized:
        from repro.kernels import kv_quant
        shape = (layout.num_blocks, layout.page_size, cfg.n_kv_heads,
                 layout.kv.storage_cols(cfg.hd))
        sshape = shape[:-1]
        cb = (kv_quant.default_codebook(cfg.n_kv_heads)
              if layout.kv.vq else None)
        return PagedKVCache(
            jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8), table,
            jnp.zeros(sshape, jnp.float32), jnp.zeros(sshape, jnp.float32),
            cb, cb)
    shape = (layout.num_blocks, layout.page_size, cfg.n_kv_heads, cfg.hd)
    return PagedKVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                        table)


# ---------------------------------------------------------------------------
# softmax attention cores
# ---------------------------------------------------------------------------

def _plain_attention(q, k, v, mask):
    """q (B,Sq,H,hd), k/v (B,Sk,KV,hd), mask broadcastable (B,1,1,Sq,Sk).

    Inputs stay in their storage dtype (bf16 caches are NOT up-cast — a
    32k-seq cache slice in f32 would double decode HBM); accumulation is
    f32 via preferred_element_type.
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qh = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qh, k,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(hd).astype(jnp.float32)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


def flash_attention(q, k, v, *, causal: bool, q_offset=0,
                    q_chunk: int = 512, kv_chunk: int = 1024):
    """Two-level chunked attention with online softmax (memory O(tile))."""
    if _FLASH_IMPL["impl"] == "pallas" and isinstance(q_offset, int):
        # the kernel handles causal masking at any static row offset, so a
        # nonzero q_offset (e.g. a chunk with an empty cache prefix, where
        # Sk == Sq and positions are absolute) no longer silently falls
        # back to the XLA scan. Traced offsets keep the XLA path (the
        # kernel's mask is built at trace time).
        from repro.kernels.flash_attention import flash_attention_tpu
        on_tpu = jax.default_backend() == "tpu"
        _FLASH_IMPL["counts"]["pallas"] += 1
        return flash_attention_tpu(q, k, v, causal=causal,
                                   q_offset=q_offset, interpret=not on_tpu)
    _FLASH_IMPL["counts"]["xla"] += 1
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    Tq = min(q_chunk, Sq)
    Ts = min(kv_chunk, Sk)
    assert Sq % Tq == 0 and Sk % Ts == 0
    nq, nk = Sq // Tq, Sk // Ts
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    qh = q.reshape(B, nq, Tq, KV, G, hd).transpose(1, 0, 3, 4, 2, 5)
    # (nq, B, KV, G, Tq, hd)
    kh = k.reshape(B, nk, Ts, KV, hd).transpose(1, 0, 3, 2, 4)  # (nk,B,KV,Ts,hd)
    vh = v.reshape(B, nk, Ts, KV, hd).transpose(1, 0, 3, 2, 4)

    k_pos = jnp.arange(Sk).reshape(nk, Ts)

    def q_block(args):
        qi, qb = args  # qb: (B, KV, G, Tq, hd)
        q_pos = q_offset + qi * Tq + jnp.arange(Tq)

        def kv_step(carry, xs):
            m, l, acc = carry
            kb, vb, kp = xs

            def compute(carry):
                m, l, acc = carry
                s = jnp.einsum("bkgqh,bksh->bkgqs", qb.astype(jnp.float32),
                               kb.astype(jnp.float32)) * scale
                if causal:
                    msk = kp[None, :] <= q_pos[:, None]  # (Tq, Ts)
                    s = jnp.where(msk[None, None, None], s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new[..., None])
                l2 = l * alpha + jnp.sum(p, axis=-1)
                acc2 = acc * alpha[..., None] + jnp.einsum(
                    "bkgqs,bksh->bkgqh", p, vb.astype(jnp.float32))
                return m_new, l2, acc2

            if causal and nk >= 8:
                # causal chunk skip: kv chunks strictly after this q block
                # are fully masked — lax.cond skips their compute at run
                # time, halving long-context attention FLOPs (§Perf it.7).
                # Gated to nk >= 8: at short seq the cond's extra backward
                # residuals cost ~1 GiB while attention is <0.1% of step
                # FLOPs (dbrx train_4k measurement).
                needed = kp[0] <= q_pos[-1]
                carry = jax.lax.cond(needed, compute, lambda c: c, carry)
            else:
                carry = compute(carry)
            return carry, None

        m0 = jnp.full((B, KV, G, Tq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, Tq), jnp.float32)
        a0 = jnp.zeros((B, KV, G, Tq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kh, vh, k_pos))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.lax.map(q_block, (jnp.arange(nq), qh))  # (nq,B,KV,G,Tq,hd)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# full layer apply
# ---------------------------------------------------------------------------

def pre_out(p, cfg: ModelConfig, x, *, pos: jax.Array | int = 0,
            causal: bool = True, use_rope: bool = True,
            flash_threshold: int = 2048):
    """Self-attention up to (but not including) ``wo``; returns (B,S,H*hd).

    The Hessian tap for the output projection: GPTVQ quantizes ``wo``
    against the distribution of its *inputs*, which is exactly this
    pre-projection attention output (core/adapters/*).
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x)
    if use_rope:
        pos_arr = cm.position_ids(pos, B, S)
        q = cm.apply_rope(q, pos_arr, cfg.rope_theta)
        k = cm.apply_rope(k, pos_arr, cfg.rope_theta)
    if S > flash_threshold:
        o = flash_attention(q, k, v, causal=causal)
    else:
        if causal:
            msk = (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])
            msk = msk[None, None, None]
        else:
            msk = jnp.ones((1, 1, 1, S, S), bool)
        o = _plain_attention(q, k, v, msk)
    return o.reshape(B, S, -1)


def cross_pre_out(p, cfg: ModelConfig, x, memory, *, flash_threshold=2048):
    """Cross-attention up to (but not including) ``wo``; returns (B,S,H*hd)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, kv_x=memory)
    Sk = memory.shape[1]
    if max(S, Sk) > flash_threshold:
        o = flash_attention(q, k, v, causal=False)
    else:
        msk = jnp.ones((1, 1, 1, S, Sk), bool)
        o = _plain_attention(q, k, v, msk)
    return o.reshape(B, S, -1)


def _project_qkv(p, cfg: ModelConfig, x, kv_x=None):
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    kv_x = x if kv_x is None else kv_x
    Skv = kv_x.shape[1]
    q = cm.matmul(x, p["wq"])
    k = cm.matmul(kv_x, p["wk"])
    v = cm.matmul(kv_x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, Skv, KV, hd)
    v = v.reshape(B, Skv, KV, hd)
    if cfg.qk_norm:
        q = cm.rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = cm.rmsnorm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def apply(
    p,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    pos: jax.Array | int = 0,
    cache: KVCache | None = None,
    causal: bool = True,
    use_rope: bool = True,
    flash_threshold: int = 2048,
    paged_impl: str | None = None,
):
    """Self-attention. Returns (y, new_cache).

    * prefill/train: x is (B, S, D); if a cache is given the fresh K/V are
      written at positions [pos, pos+S).
    * decode: x is (B, 1, D); attends over cache[:pos+1].
    * paged (serving): cache is a PagedKVCache and ``pos`` may be a per-slot
      (B,) vector — K/V are scattered into each slot's blocks through the
      page table and attention reads back through a page-table gather, so
      every slot decodes at its own depth (no shared write position).
    """
    B, S, D = x.shape
    if cache is None:
        # cache-free path shares its math with the quantizer's Hessian tap
        o = pre_out(p, cfg, x, pos=pos, causal=causal, use_rope=use_rope,
                    flash_threshold=flash_threshold)
        return cm.matmul(o, p["wo"]).astype(x.dtype), None
    with jax.named_scope("attn_qkv"):
        q, k, v = _project_qkv(p, cfg, x)
        pos_arr = cm.position_ids(pos, B, S)  # (B, S)
        if use_rope:
            q = cm.apply_rope(q, pos_arr, cfg.rope_theta)
            k = cm.apply_rope(k, pos_arr, cfg.rope_theta)

    if isinstance(cache, PagedKVCache):
        return _paged_apply(p, cache, q, k, v, pos_arr, x.dtype,
                            impl=paged_impl)

    ck = jax.lax.dynamic_update_slice(
        cache.k, k.astype(cache.k.dtype), (0, jnp.asarray(pos), 0, 0))
    cv = jax.lax.dynamic_update_slice(
        cache.v, v.astype(cache.v.dtype), (0, jnp.asarray(pos), 0, 0))
    new_cache = KVCache(ck, cv)
    if S == 1:
        # decode: attend over the whole cache with a length mask
        Sk = ck.shape[1]
        valid = (jnp.arange(Sk) <= jnp.asarray(pos))[None, None, None, None, :]
        o = _plain_attention(q, ck, cv, valid)
        return cm.matmul(o.reshape(B, S, -1), p["wo"]).astype(x.dtype), new_cache
    k, v = ck[:, : S + 0], cv[:, : S + 0]  # prefill from position 0

    if S > flash_threshold:
        o = flash_attention(q, k, v, causal=causal)
    else:
        Sk = k.shape[1]
        if causal:
            msk = (jnp.arange(Sk)[None, :] <= jnp.arange(S)[:, None])
            msk = msk[None, None, None]
        else:
            msk = jnp.ones((1, 1, 1, S, Sk), bool)
        o = _plain_attention(q, k, v, msk)
    y = cm.matmul(o.reshape(B, S, -1), p["wo"])
    return y.astype(x.dtype), new_cache


def _paged_apply(p, cache: PagedKVCache, q, k, v, pos_arr, out_dtype,
                 impl: str | None = None):
    """Scatter new K/V through the page table, attend over the gathered
    logical view. ``pos_arr`` is (B, S): the absolute position of every new
    token per slot (S > 1 during chunked prefill, S == 1 at decode).

    Writes from slots whose page-table entries are 0 land in the reserved
    scratch block; reads are masked to ``kpos <= pos`` per slot, so stale
    data in recycled blocks and the scratch block never leak into live
    rows.

    Decode (S == 1) dispatches on ``impl`` (falling back to the
    set_paged_impl() module default): "pallas" runs the fused kernel
    (kernels/paged_attention.py) whose BlockSpec index maps gather K/V
    pages in-kernel through the page table; "xla" runs the same math
    through the oracle (kernels/ref.py). The default "gather" — and
    chunked prefill at any impl (the engine pins prefill closures to
    "gather", including width-1 tail chunks) — materializes the
    (B, n_pages*page_size, KV, hd) logical view per layer, the same
    working set as a dense cache read.

    Quantized pools (the cache's scale leaves are present): fresh K/V rows
    are quantized in-graph right here — per-row per-kv-head amax scales,
    int8 codes (int4 packed two-per-byte) — and every read path dequants
    on the fly. Stale codes AND stale scales in recycled/scratch blocks
    decode to finite garbage that the same ``kpos <= pos`` mask discards.
    The format is inferred from the cache leaves themselves (scales
    present + stored column count), so it can never disagree with the
    storage the engine allocated via PagedLayout.kv.

    VQ pools (the cache's codebook leaves are present): rows store 4-bit
    codebook indices instead of scalar codes. The codebooks are frozen
    (the engine calibrates them once at load, before any serving write),
    so assignment at this scatter site is a pure deterministic function
    of the written row — replayed and interleaved writes stay
    bit-identical, the same property the scalar round gives.
    """
    from repro.kernels import kv_quant as kvq

    B, S = pos_arr.shape
    page_size = cache.k.shape[1]
    n_pages = cache.page_table.shape[-1]
    quantized = cache.k_scale is not None
    vq = cache.k_codebook is not None
    if vq:
        kv_bits = kvq.VQ_BITS
    elif quantized:
        kv_bits = kvq.infer_bits(cache.k.shape[-1], q.shape[-1])
    else:
        kv_bits = kvq.PASSTHROUGH_BITS
    with jax.named_scope("kv_write"):
        page = pos_arr // page_size
        blk = jnp.take_along_axis(
            cache.page_table, jnp.minimum(page, n_pages - 1), axis=1)
        # positions past the table extent (a padded prefill chunk can
        # overhang max_len) go to scratch — clipping them into the last
        # page would overwrite live K/V
        blk = jnp.where(page < n_pages, blk, 0)
        off = pos_arr % page_size
        if quantized:
            if vq:
                kc, ks = kvq.vq_quantize_rows(k, cache.k_codebook)
                vc, vs = kvq.vq_quantize_rows(v, cache.v_codebook)
            else:
                kc, ks = kvq.quantize_kv(k, kv_bits)
                vc, vs = kvq.quantize_kv(v, kv_bits)
            ck = cache.k.at[blk, off].set(kc)
            cv = cache.v.at[blk, off].set(vc)
            cks = cache.k_scale.at[blk, off].set(ks)
            cvs = cache.v_scale.at[blk, off].set(vs)
        else:
            ck = cache.k.at[blk, off].set(k.astype(cache.k.dtype))
            cv = cache.v.at[blk, off].set(v.astype(cache.v.dtype))
            cks = cvs = None
    new_cache = PagedKVCache(ck, cv, cache.page_table, cks, cvs,
                             cache.k_codebook, cache.v_codebook)

    impl = impl or _PAGED_IMPL["impl"]
    with jax.named_scope("attention"):
        if S == 1 and impl in ("xla", "pallas"):
            from repro.kernels import ops
            _PAGED_IMPL["counts"][impl] += 1
            o = ops.paged_attention(
                q[:, 0], ck, cv, cache.page_table, pos_arr[:, 0],
                k_scale=cks, v_scale=cvs,
                k_codebook=cache.k_codebook, v_codebook=cache.v_codebook,
                use_pallas=(impl == "pallas"),
                interpret=jax.default_backend() != "tpu")
        else:
            _PAGED_IMPL["counts"]["gather"] += 1
            o = _gathered_attention(cache, ck, cv, cks, cvs, q, pos_arr,
                                    vq, quantized, kv_bits)
    with jax.named_scope("attn_out"):
        y = cm.matmul(o.reshape(B, S, -1), p["wo"]).astype(out_dtype)
    return y, new_cache


def _gathered_attention(cache: PagedKVCache, ck, cv, cks, cvs, q, pos_arr,
                        vq: bool, quantized: bool, kv_bits: int):
    """Attention over the (B, n_pages*page_size) logical view gathered
    through the page table, dequantizing quantized pools on the fly."""
    from repro.kernels import kv_quant as kvq

    B = pos_arr.shape[0]
    Sk = cache.page_table.shape[-1] * cache.k.shape[1]
    kg = ck[cache.page_table].reshape(B, Sk, *ck.shape[2:])
    vg = cv[cache.page_table].reshape(B, Sk, *cv.shape[2:])
    if vq:
        kg = kvq.vq_dequant_rows(
            kg, cks[cache.page_table].reshape(B, Sk, kg.shape[2]),
            cache.k_codebook)
        vg = kvq.vq_dequant_rows(
            vg, cvs[cache.page_table].reshape(B, Sk, vg.shape[2]),
            cache.v_codebook)
    elif quantized:
        kg = kvq.dequant_rows(
            kg, cks[cache.page_table].reshape(B, Sk, kg.shape[2]), kv_bits)
        vg = kvq.dequant_rows(
            vg, cvs[cache.page_table].reshape(B, Sk, vg.shape[2]), kv_bits)
    # per-slot causal + length mask over logical positions
    msk = jnp.arange(Sk)[None, None, :] <= pos_arr[:, :, None]  # (B, S, Sk)
    return _plain_attention(q, kg, vg, msk[:, None, None])


def cross_apply(p, cfg: ModelConfig, x, memory, *, flash_threshold=2048):
    """Cross-attention (whisper decoder): keys/values from encoder memory."""
    o = cross_pre_out(p, cfg, x, memory, flash_threshold=flash_threshold)
    return cm.matmul(o, p["wo"]).astype(x.dtype)
