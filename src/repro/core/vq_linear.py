"""VQ-compressed linear layers: the serving-side representation.

A quantized linear stores, per weight matrix W (r=out, c=in):

  * ``words``      — bit-packed centroid indices (uint32), the HBM payload:
                     ``log2(k)``-bit codes, ``c/d`` codes per row.
  * ``codebooks``  — int8 centroids (n_cg, n_bands, k, d) + per-codebook
                     fp32 scale (n_cg, n_bands). Tiny; lives in VMEM on TPU.
  * ``scale_sint`` — optional 4-bit log-domain blockwise normalization codes
                     (packed as int8 here; 2 codes/byte in the bpv math).

Three execution paths, selected per-engine via ``vq_matmul_impl``:
  * "gather" — ``dequant_tree`` densifies VQLinear leaves per layer-slice
    inside the model forward (portable default; what every caller did
    before the fused path existed).
  * "xla"    — fused-boundary oracle over ``FusedVQLinear`` leaves, two
    M-shaped regimes: decode-shaped calls (M <= 4) reconstruct the dense
    tile from the PRE-FOLDED artifacts and GEMV — the gather path's
    structure minus its per-tick ``cb_scale`` multiply and ``exp2``, so
    it is strictly cheaper; prefill-shaped calls gather the codebook
    d-vectors straight from the packed words (per-call unpack is two
    iota broadcasts and a shift) and contract them with the activation
    spans in one einsum, never materializing the dense weight. Runs
    everywhere; pinned bitwise-close to the Pallas kernel by the
    differential suite.
  * "pallas" — kernels/vq_dequant_matmul.py decodes codes+codebooks inside
    VMEM and feeds the MXU directly; the dense weight never exists in HBM.

FusedVQLinear prep-pass contract (``prepare_fused`` / ``prepare_fused_tree``,
run ONCE at engine load — serve/engine.Engine calls it when
``vq_matmul_impl != "gather"``):
  * ``codebooks_f`` = int8 codebooks x ``cb_scale``, folded to fp32 — the
    per-step codebook-side scale work becomes zero.
  * codes stay PACKED: both fused paths stream only ``words`` (the true
    HBM payload, reported by payload_bytes()) and decode in-flight. An
    earlier prep variant materialized int32 offset codes for the XLA
    path; the 4-byte-per-code index traffic made decode-shaped matmuls
    slower than the gather path it replaced, so the prep artifact is
    gone and the flat (group, band) codebook offsets are rebuilt per
    call from two iota vectors (see ``_flat_codes``).
  * ``scales``     = the blockwise normalization plane
    exp2(a*sint + z) pre-expanded to (r, c / scale_block) fp32 — folding
    into the shared codebooks is impossible (scales vary per row within a
    band), so the plane multiplies the decoded tile instead; scale_block
    != 0 recipes keep the fused path.
  * leading stack dims (MoE (E, ...), scanned layers (L, ...), hybrid
    trunk (n_groups, per, ...)) are preserved verbatim: layer scans slice
    the stacked leaves exactly like dense params, and
    models/common.expert_matmul maps the fused matmul over expert stacks.
  * leaves whose rows are not packed on word boundaries stay VQLinear
    (gather path per-leaf) — the kernel needs row-aligned words.
  * the chosen impl is stamped on each leaf (static metadata), so it is
    baked into any jitted closure that captures the tree; the model
    forwards' ``vq_matmul_impl=`` argument re-stamps at trace time.

Sharding: indices shard along rows together with ``n_bands`` (row bands) and
along columns together with ``n_cg`` (column groups); both group boundaries
are multiples of 128/256 so TP shard edges always align.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.bpv import VQConfig
from repro.core.gptvq import VQResult
from repro.obs import dispatch as obs_dispatch


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class VQLinear:
    """Pytree holding one VQ-compressed weight matrix."""

    words: jax.Array        # (r, c/d*code_bits/32) uint32 packed indices
    codebooks: jax.Array    # (n_cg, n_bands, k, d) int8
    cb_scale: jax.Array     # (n_cg, n_bands) f32
    scale_sint: jax.Array   # (n_cg, r, cg/Ns) int8 (zeros if normalization off)
    scale_a: jax.Array      # (n_cg,) f32
    scale_z: jax.Array      # (n_cg,) f32
    # -- static metadata --
    r: int = dataclasses.field(metadata=dict(static=True), default=0)
    c: int = dataclasses.field(metadata=dict(static=True), default=0)
    d: int = dataclasses.field(metadata=dict(static=True), default=1)
    k: int = dataclasses.field(metadata=dict(static=True), default=2)
    group_cols: int = dataclasses.field(metadata=dict(static=True), default=256)
    rows_per_band: int = dataclasses.field(metadata=dict(static=True), default=1)
    scale_block: int = dataclasses.field(metadata=dict(static=True), default=0)
    # recipe provenance: the rule that produced this leaf ("" when packed
    # outside a recipe run) — lets serve/report reconstruct the mix
    rule: str = dataclasses.field(metadata=dict(static=True), default="")

    @property
    def code_bits(self) -> int:
        return max(1, (self.k - 1).bit_length())

    @property
    def n_cg(self) -> int:
        return self.c // self.group_cols

    @property
    def n_bands(self) -> int:
        return self.r // self.rows_per_band

    def payload_bytes(self) -> int:
        """True HBM footprint of the compressed layer."""
        return (
            self.words.size * 4
            + self.codebooks.size
            + self.cb_scale.size * 4
            + (self.scale_sint.size // 2 if self.scale_block else 0)
            + self.scale_a.size * 4
            + self.scale_z.size * 4
        )


def from_vq_result(res: VQResult) -> VQLinear:
    """Pack a quantizer output into the serving format."""
    cfg = res.cfg
    idx = res.arrays.indices  # (r, c/d)
    code_bits = max(1, (cfg.k - 1).bit_length())
    cbits = packing.container_bits(code_bits)
    lanes = 32 // cbits
    r, nspans = idx.shape
    # pack per row so row-sharding stays trivial
    assert nspans % lanes == 0 or (nspans * r) % lanes == 0
    if nspans % lanes == 0:
        words = jax.vmap(lambda row: packing.pack(row, code_bits))(idx)
    else:
        words = packing.pack(idx.reshape(-1), code_bits).reshape(r, -1)

    C = res.arrays.codebooks
    if res.codebook_scale is not None:
        s = res.codebook_scale
    else:
        qmax = 2 ** (cfg.codebook_bits - 1) - 1
        absmax = jnp.max(jnp.abs(C), axis=(2, 3))
        s = jnp.where(absmax == 0, 1.0, absmax / qmax)
    Cq = jnp.clip(jnp.round(C / s[..., None, None]), -128, 127).astype(jnp.int8)

    return VQLinear(
        words=words,
        codebooks=Cq,
        cb_scale=s.astype(jnp.float32),
        scale_sint=res.arrays.scale_sint.astype(jnp.int8),
        scale_a=res.arrays.scale_a,
        scale_z=res.arrays.scale_z,
        r=res.r,
        c=res.c,
        d=cfg.d,
        k=cfg.k,
        group_cols=res.group_cols,
        rows_per_band=res.rows_per_band,
        scale_block=cfg.scale_block,
    )


def unpack_indices(vql: VQLinear) -> jax.Array:
    """(r, c/d) int32 codes from the packed words (in-graph shifts/masks)."""
    nspans = vql.c // vql.d
    code_bits = vql.code_bits
    cbits = packing.container_bits(code_bits)
    lanes = 32 // cbits
    if nspans % lanes == 0:
        return jax.vmap(lambda row: packing.unpack(row, code_bits, nspans))(
            vql.words
        )
    return packing.unpack(vql.words.reshape(-1), code_bits, vql.r * nspans).reshape(
        vql.r, nspans
    )


def dequantize(vql: VQLinear, dtype=jnp.bfloat16) -> jax.Array:
    """Reconstruct W (r, c) — the XLA (non-fused) path."""
    idx = unpack_indices(vql)
    n_cg, n_bands = vql.n_cg, vql.n_bands
    rg, cg, d = vql.rows_per_band, vql.group_cols, vql.d
    spans_pg = cg // d
    C = vql.codebooks.astype(jnp.float32) * vql.cb_scale[..., None, None]
    idx4 = idx.reshape(n_bands, rg, n_cg, spans_pg)
    g_ix = jnp.arange(n_cg)[None, None, :, None]
    b_ix = jnp.arange(n_bands)[:, None, None, None]
    Wn = C[g_ix, b_ix, idx4].reshape(n_bands, rg, n_cg, cg).reshape(vql.r, vql.c)
    if vql.scale_block:
        s = jnp.exp2(
            vql.scale_a[:, None, None] * vql.scale_sint.astype(jnp.float32)
            + vql.scale_z[:, None, None]
        )
        s = jnp.repeat(s, vql.scale_block, axis=2).transpose(1, 0, 2).reshape(
            vql.r, vql.c
        )
        Wn = Wn * s
    return Wn.astype(dtype)


def apply(vql: VQLinear, x: jax.Array, *, dtype=jnp.bfloat16) -> jax.Array:
    """y = x @ W^T with on-the-fly dequantization (XLA path)."""
    W = dequantize(vql, dtype)
    return x.astype(dtype) @ W.T


def dequant_tree(tree, dtype=jnp.bfloat16, densify_fused=False):
    """Replace any VQLinear leaves with dense (in, out) weight arrays.

    Layout-agnostic across the model zoo: non-matmul leaves (norm scales,
    conv kernels, SSM scan parameters A_log/dt_bias/D_skip, LoRA factors,
    biases) pass through untouched, and VQLinear leaves with leading stack
    dims — MoE expert stacks (E, ...), scanned layer stacks (L, ...), the
    hybrid trunk's (n_groups, per, ...) — vmap the dequantization over
    every leading axis of the packed words.

    FusedVQLinear leaves pass through UNtouched (they are consumed at the
    matmul sites via models/common.matmul) unless ``densify_fused=True`` —
    used by callers that must mutate the dense weight (the hybrid family's
    shared-attention LoRA deltas are added onto the base matrix).

    Called by the model assemblies on each *layer slice* inside their layer
    scan, so only one layer's weights are ever dense at a time; everything
    else streams through HBM bit-packed. No-op for plain parameter trees.
    """
    def f(x):
        if isinstance(x, FusedVQLinear):
            if not densify_fused:
                return x
            deq = lambda v: fused_dequantize(v, dtype).T
            for _ in range(x.words.ndim - 2):
                deq = jax.vmap(deq)
            return deq(x)
        if not isinstance(x, VQLinear):
            return x
        _VQ_IMPL["counts"]["gather"] += 1  # trace-time dispatch pin
        # leading batch dims (expert / layer / group stacks) vmap away
        deq = lambda v: dequantize(v, dtype).T
        for _ in range(x.words.ndim - 2):
            deq = jax.vmap(deq)
        return deq(x)

    return jax.tree.map(f, tree, is_leaf=_is_vq_leaf)


def _is_vq_leaf(x) -> bool:
    return isinstance(x, (VQLinear, FusedVQLinear))


def tree_has_vq(tree) -> bool:
    """True if the tree holds any packed leaves (raw or engine-prepped)."""
    return any(_is_vq_leaf(x) for x in jax.tree.leaves(
        tree, is_leaf=_is_vq_leaf))


# ---------------------------------------------------------------------------
# Fused serving path: engine-load prep pass + per-matmul dispatch
# ---------------------------------------------------------------------------

# Trace-time dispatch counter, same contract as models/attention._PAGED_IMPL:
# counts bump when a path is *traced* into a computation, pinning regressions
# where a requested impl silently falls back. "gather" counts dense
# materializations in dequant_tree; "xla"/"pallas" count fused matmuls.
# Registered in obs.dispatch so snapshot/reset_dispatch_counters cover it.
_VQ_IMPL = {"impl": "gather",
            "counts": obs_dispatch.register_dispatch(
                "vq", ("gather", "xla", "pallas"))}


def set_vq_impl(impl: str) -> None:
    """Set the module-default VQ matmul impl (leaf stamps take precedence)."""
    assert impl in ("gather", "xla", "pallas", "fused"), impl
    _VQ_IMPL["impl"] = impl


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FusedVQLinear:
    """Engine-prepped VQLinear: all per-step scale/unpack work pre-folded.

    Produced once at engine load by ``prepare_fused`` (see the module
    docstring for the full contract); consumed at the model matmul sites by
    ``fused_matmul`` via models/common.matmul."""

    words: jax.Array        # (..., r, c/d*code_bits/32) uint32 — the payload
    codebooks_f: jax.Array  # (..., n_cg, n_bands, k, d) f32, cb_scale folded
    scales: Any             # (..., r, c/Ns) f32 plane, or None
    # -- static metadata (mirrors VQLinear) --
    r: int = dataclasses.field(metadata=dict(static=True), default=0)
    c: int = dataclasses.field(metadata=dict(static=True), default=0)
    d: int = dataclasses.field(metadata=dict(static=True), default=1)
    k: int = dataclasses.field(metadata=dict(static=True), default=2)
    group_cols: int = dataclasses.field(metadata=dict(static=True), default=256)
    rows_per_band: int = dataclasses.field(metadata=dict(static=True), default=1)
    scale_block: int = dataclasses.field(metadata=dict(static=True), default=0)
    rule: str = dataclasses.field(metadata=dict(static=True), default="")
    impl: str = dataclasses.field(metadata=dict(static=True), default="xla")

    @property
    def code_bits(self) -> int:
        return max(1, (self.k - 1).bit_length())

    def payload_bytes(self) -> int:
        """HBM bytes streamed per decode tick (packed words + folded
        codebooks + scale plane — both fused paths decode in-flight)."""
        return (self.words.size * 4 + self.codebooks_f.size * 4
                + (self.scales.size * 4 if self.scales is not None else 0))


def prepare_fused(vql: VQLinear, impl: str = "xla") -> FusedVQLinear | VQLinear:
    """One-time VQLinear -> FusedVQLinear prep (leading stack dims kept).

    Returns the leaf unchanged when its rows are not packed on uint32 word
    boundaries (the kernel's layout precondition) — that leaf simply stays
    on the gather path."""
    nspans = vql.c // vql.d
    cbits = packing.container_bits(vql.code_bits)
    lanes = 32 // cbits
    if nspans % lanes != 0:
        return vql
    lead = vql.words.shape[:-2]

    codebooks_f = (vql.codebooks.astype(jnp.float32)
                   * vql.cb_scale[..., None, None])

    scales = None
    if vql.scale_block:
        s = jnp.exp2(
            vql.scale_a[..., :, None, None]
            * vql.scale_sint.astype(jnp.float32)
            + vql.scale_z[..., :, None, None]
        )  # (..., n_cg, r, cg/Ns)
        scales = jnp.swapaxes(s, -3, -2).reshape(
            *lead, vql.r, vql.c // vql.scale_block)

    return FusedVQLinear(
        words=vql.words, codebooks_f=codebooks_f, scales=scales,
        r=vql.r, c=vql.c, d=vql.d, k=vql.k, group_cols=vql.group_cols,
        rows_per_band=vql.rows_per_band, scale_block=vql.scale_block,
        rule=vql.rule, impl=impl)


def prepare_fused_tree(tree, impl: str = "xla"):
    """Engine-load prep pass: VQLinear leaves -> FusedVQLinear (in place of
    the tree; dense leaves untouched)."""
    def f(x):
        if isinstance(x, VQLinear):
            return prepare_fused(x, impl)
        return x

    return jax.tree.map(f, tree, is_leaf=_is_vq_leaf)


def retag_fused(tree, impl: str):
    """Re-stamp the impl on every FusedVQLinear leaf (trace-time only — the
    stamp is static metadata, no device work)."""
    def f(x):
        if isinstance(x, FusedVQLinear) and x.impl != impl:
            return dataclasses.replace(x, impl=impl)
        return x

    return jax.tree.map(f, tree, is_leaf=_is_vq_leaf)


def _flat_codes(fvl: FusedVQLinear) -> jax.Array:
    """(r, c/d) int32 codes with the flat (group, band) codebook offset
    added — rebuilt per call from the packed ``words``. The unpack is a
    broadcast shift/mask and the offsets are two iota vectors, so the
    per-call index traffic stays at the packed-words footprint (a
    materialized int32 code plane costs 4 bytes per code and made
    decode-shaped XLA matmuls slower than the gather path)."""
    nspans = fvl.c // fvl.d
    cbits = packing.container_bits(fvl.code_bits)
    lanes = 32 // cbits
    mask = jnp.uint32(2**cbits - 1)
    shifts = jnp.arange(lanes, dtype=jnp.uint32) * cbits
    codes = ((fvl.words[..., None] >> shifts) & mask).reshape(
        fvl.r, nspans).astype(jnp.int32)
    spans_pg = fvl.group_cols // fvl.d
    n_bands = fvl.r // fvl.rows_per_band
    g = jnp.arange(nspans, dtype=jnp.int32) // spans_pg
    b = jnp.arange(fvl.r, dtype=jnp.int32) // fvl.rows_per_band
    return codes + (g[None, :] * n_bands + b[:, None]) * fvl.k


def _reconstruct(fvl: FusedVQLinear) -> jax.Array:
    """Dense f32 W (r, c) from the pre-folded artifacts.

    Mirrors ``dequantize``'s 4-D advanced-index gather (XLA lowers the
    small per-(group, band) codebook lookup measurably better than a flat
    ``take`` over concatenated codebooks) but reads ``codebooks_f`` and
    the pre-expanded ``scales`` plane, so the per-tick ``cb_scale``
    multiply and ``exp2`` of the gather path are gone — this is the
    gather path minus the folding work, which is why the decode-shaped
    fused matmul uses it."""
    nspans = fvl.c // fvl.d
    cbits = packing.container_bits(fvl.code_bits)
    lanes = 32 // cbits
    mask = jnp.uint32(2**cbits - 1)
    shifts = jnp.arange(lanes, dtype=jnp.uint32) * cbits
    idx = ((fvl.words[..., None] >> shifts) & mask).reshape(
        fvl.r, nspans).astype(jnp.int32)
    n_bands = fvl.r // fvl.rows_per_band
    n_cg = fvl.c // fvl.group_cols
    rg, spans_pg = fvl.rows_per_band, fvl.group_cols // fvl.d
    idx4 = idx.reshape(n_bands, rg, n_cg, spans_pg)
    g_ix = jnp.arange(n_cg)[None, None, :, None]
    b_ix = jnp.arange(n_bands)[:, None, None, None]
    W = fvl.codebooks_f[g_ix, b_ix, idx4].reshape(
        n_bands, rg, n_cg, fvl.group_cols).reshape(fvl.r, fvl.c)
    if fvl.scales is not None:
        W = (W.reshape(fvl.r, -1, fvl.scale_block)
             * fvl.scales[:, :, None]).reshape(fvl.r, fvl.c)
    return W


def fused_dequantize(fvl: FusedVQLinear, dtype=jnp.bfloat16) -> jax.Array:
    """Dense W (r, c) from a prepped leaf (hybrid LoRA densify + tests)."""
    return _reconstruct(fvl).astype(dtype)


def fused_matmul(x: jax.Array, fvl: FusedVQLinear, *, impl: str | None = None,
                 interpret: bool | None = None, tile_m: int = 128,
                 tile_n: int = 128, tile_k: int = 256) -> jax.Array:
    """y = x @ W_io where W_io is the (in, out) dense view of ``fvl``.

    x may carry any leading dims (``(B, S, K)`` decode shapes flatten to a
    single M). Dispatch: explicit ``impl`` > leaf stamp > module default;
    "fused" resolves to "pallas" on TPU, "xla" elsewhere."""
    assert fvl.words.ndim == 2, (
        "stacked FusedVQLinear must go through models/common.expert_matmul "
        "or a layer scan slice")
    impl = impl or fvl.impl or _VQ_IMPL["impl"]
    if impl == "fused":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert impl in ("gather", "xla", "pallas"), impl
    _VQ_IMPL["counts"][impl] += 1

    lead, K = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, K)
    if impl == "pallas":
        from repro.kernels.vq_dequant_matmul import vq_dequant_matmul

        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        y = vq_dequant_matmul(
            x2, fvl.words, fvl.codebooks_f, fvl.scales,
            d=fvl.d, k_c=fvl.k,
            container_bits=packing.container_bits(fvl.code_bits),
            rows_per_band=fvl.rows_per_band, group_cols=fvl.group_cols,
            scale_block=fvl.scale_block, tile_m=tile_m, tile_n=tile_n,
            tile_k=tile_k, interpret=interpret)
    else:
        # "xla" (and the "gather" stamp, which at a fused leaf means the
        # same fused contraction), two M-shaped regimes measured on the
        # bench host:
        #   decode-shaped (M <= 4): reconstruct the dense tile from the
        #     PRE-FOLDED artifacts and GEMV. Same structure as the gather
        #     path minus its per-tick cb_scale multiply and exp2, so it
        #     wins ~1.1-1.3x at every layer shape; every span-contraction
        #     formulation tried here lost to the plain GEMV at M=1.
        #   prefill-shaped (M > 4): gather codebook d-vectors straight
        #     from the packed words and contract them with the activation
        #     spans (dense W never materialized) — 1.9-2.6x over gather
        #     at M=8.
        M = x2.shape[0]
        Ns, d_, c_ = fvl.scale_block, fvl.d, fvl.c
        if M <= 4 or (fvl.scales is not None and Ns % d_ != 0):
            # (the Ns % d != 0 case — spans straddling scale blocks —
            # also lands here at any M: the span contraction can't apply
            # a sub-span scale)
            y = jax.lax.dot_general(
                x2.astype(jnp.float32), _reconstruct(fvl),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            cb_flat = fvl.codebooks_f.reshape(-1, d_)
            g = jnp.take(cb_flat, _flat_codes(fvl), axis=0)  # (r, c/d, d)
            P = x2.astype(jnp.float32).reshape(M, c_ // d_, d_)
            if fvl.scales is None:
                y = jnp.einsum("nsd,msd->mn", g, P)
            else:
                gb = g.reshape(fvl.r, c_ // Ns, Ns // d_, d_)
                Pb = P.reshape(M, c_ // Ns, Ns // d_, d_)
                y = jnp.einsum("nbsd,mbsd->mn",
                               gb * fvl.scales[:, :, None, None], Pb)
    return y.reshape(*lead, fvl.r)


def quantize_array(
    W: jax.Array, H: jax.Array | None, cfg: VQConfig, key=None
) -> VQLinear:
    """Convenience: full GPTVQ pipeline on one matrix -> serving format."""
    from repro.core import hessian as hes
    from repro.core.codebook_compress import codebook_update, quantize_codebooks
    from repro.core.gptvq import gptvq_quantize_matrix

    if H is None:
        H = jnp.eye(W.shape[1], dtype=jnp.float32)
    U = hes.inv_hessian_cholesky(H, cfg.percdamp)
    res = gptvq_quantize_matrix(W, U, cfg, key)
    res = codebook_update(res, W, H)
    res = quantize_codebooks(res)
    return from_vq_result(res)
