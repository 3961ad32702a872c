"""Differential suite for the fused paged-attention decode kernel.

Every case runs kernels/paged_attention.py in interpret mode (no TPU
required) against the pure-XLA oracle kernels/ref.paged_attention_ref, and
the oracle itself is anchored against models/attention._paged_apply's
gather path once — so kernel == oracle == the serving engine's read math.

Coverage: page_size/n_pages/GQA-group/head-dim shape sweep, ragged
per-slot positions, recycled-block staleness (a freed block re-mapped to
another slot, its stale tail poisoned), and the scratch-block-0 masking
invariant (block 0 filled with huge values must never leak into output) —
each across page storage formats in {16, 8, 4, vq2} (passthrough fp
pages, int8/packed-int4 code pages with per-row per-kv-head scales, and
vector-quantized pages: packed 4-bit codebook indices over d=2 head-dim
vectors with per-(pool, kv-head) codebooks). For the quantized formats
the staleness invariants additionally poison the *scales* of masked
rows: a stale scale must be discarded exactly like a stale key. The
quantized oracles are also pinned bitwise against the fp oracle
evaluated on the kv_quant-decoded pool, so every read path shares one
decode expression down to the last ulp. At serving widths, slots on the
edges of the kernel's page blocks read as the oracle does while pages
past their positions and scratch block 0 hold NaN or huge values.

The kernel runs in the TPU interpreter: its DMAs and semaphores are
simulated and VMEM no copy reached reads as NaN. ``ops.paged_attention``
(the engine's dispatch) runs Pallas's HLO interpreter on the CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import kv_quant as kvq
from repro.kernels import ops, ref
from repro.kernels.paged_attention import pages_per_block, \
    paged_attention_tpu

pytestmark = pytest.mark.kernels

# the TPU interpreter: DMAs and semaphores simulated, VMEM starts as NaN
INTERPRET = pltpu.InterpretParams()

BITS = [16, 8, 4, kvq.VQ_BITS]


def make_case(seed, *, B, H, KV, hd, page_size, n_pages, num_blocks,
              pos=None, dtype=jnp.float32, bits=16):
    """Random pools + a valid-looking page table: each slot maps its first
    pages to distinct physical blocks, the rest to scratch (block 0).
    ``bits`` < 16 quantizes the pools row-wise into code pages + scales;
    ``bits == "vq2"`` vector-quantizes them against random per-kv-head
    codebooks (scales/codebooks None where the format has none)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    kp = jax.random.normal(ks[1], (num_blocks, page_size, KV, hd), dtype)
    vp = jax.random.normal(ks[2], (num_blocks, page_size, KV, hd), dtype)
    ksc = vsc = kcb = vcb = None
    if bits == kvq.VQ_BITS:
        kcb = jax.random.normal(ks[4], (KV, kvq.VQ_K, kvq.VQ_D))
        vcb = jax.random.normal(ks[5], (KV, kvq.VQ_K, kvq.VQ_D))
        kp, ksc = kvq.vq_quantize_rows(kp, kcb)
        vp, vsc = kvq.vq_quantize_rows(vp, vcb)
    elif bits < 16:
        kp, ksc = kvq.quantize_kv(kp, bits)
        vp, vsc = kvq.quantize_kv(vp, bits)
    if pos is None:
        pos = jax.random.randint(ks[3], (B,), 0, n_pages * page_size)
    pos = jnp.asarray(pos, jnp.int32)
    rng = np.random.RandomState(seed)
    table = np.zeros((B, n_pages), np.int32)
    free = list(rng.permutation(np.arange(1, num_blocks)))
    for b in range(B):
        live = int(pos[b]) // page_size + 1
        for p in range(min(live, n_pages)):
            table[b, p] = free.pop() if free else 0
    return q, kp, vp, jnp.asarray(table), pos, ksc, vsc, kcb, vcb


def assert_matches_oracle(q, kp, vp, table, pos, ksc=None, vsc=None,
                          kcb=None, vcb=None, tol=2e-5):
    got = paged_attention_tpu(q, kp, vp, table, pos, k_scale=ksc,
                              v_scale=vsc, k_codebook=kcb, v_codebook=vcb,
                              interpret=INTERPRET)
    want = ref.paged_attention_ref(q, kp, vp, table, pos, k_scale=ksc,
                                   v_scale=vsc, k_codebook=kcb,
                                   v_codebook=vcb)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


class TestDifferentialSweep:
    @pytest.mark.parametrize("bits", BITS)
    @pytest.mark.parametrize(
        "B,H,KV,hd,page_size,n_pages,num_blocks",
        [
            (1, 4, 4, 32, 8, 4, 8),     # MHA, B=1 decode (the bench case)
            (2, 8, 4, 32, 16, 4, 12),   # G=2 GQA
            (3, 8, 2, 64, 8, 6, 32),    # G=4, deep tables, big pool
            (4, 8, 1, 16, 4, 8, 40),    # MQA (KV=1), tiny pages
            (2, 16, 4, 8, 32, 2, 6),    # wide heads, narrow hd, 2 pages
            (5, 4, 2, 32, 1, 16, 90),   # degenerate page_size=1
        ],
    )
    def test_matches_oracle(self, B, H, KV, hd, page_size, n_pages,
                            num_blocks, bits):
        case = make_case(0, B=B, H=H, KV=KV, hd=hd, page_size=page_size,
                         n_pages=n_pages, num_blocks=num_blocks, bits=bits)
        assert_matches_oracle(*case)

    @pytest.mark.parametrize("bits", BITS)
    @pytest.mark.parametrize("seed", range(4))
    def test_ragged_positions(self, seed, bits):
        """Slots at wildly different depths in one batch — including a
        fresh slot at pos 0 and one on its last mapped row."""
        B, page_size, n_pages = 4, 8, 4
        pos = [0, 1, page_size * n_pages - 1, 2 * page_size]
        case = make_case(seed, B=B, H=8, KV=4, hd=32, page_size=page_size,
                         n_pages=n_pages, num_blocks=20, pos=pos, bits=bits)
        assert_matches_oracle(*case)

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, 4e-2)])
    def test_dtypes(self, dtype, tol):
        q, kp, vp, table, pos, _, _, _, _ = make_case(
            1, B=2, H=8, KV=4, hd=32, page_size=8, n_pages=4,
            num_blocks=12, dtype=dtype)
        got = paged_attention_tpu(q, kp, vp, table, pos,
                                  interpret=INTERPRET)
        assert got.dtype == dtype
        assert_matches_oracle(q, kp, vp, table, pos, tol=tol)


class TestQuantizedDecode:
    @pytest.mark.parametrize("bits", [8, 4])
    def test_storage_really_shrinks(self, bits):
        """The quantized pool must be byte-for-byte smaller: int8 stores
        hd int8 columns, int4 packs two codes per byte (hd//2) — not
        low-bit values parked in wide containers."""
        hd = 32
        _, kp, _, _, _, ksc, _, _, _ = make_case(
            0, B=1, H=4, KV=2, hd=hd, page_size=8, n_pages=2,
            num_blocks=6, bits=bits)
        assert kp.dtype == jnp.int8
        assert kp.shape[-1] == (hd if bits == 8 else hd // 2)
        assert ksc.shape == kp.shape[:-1] and ksc.dtype == jnp.float32

    @pytest.mark.parametrize("bits", [8, 4])
    def test_quantized_oracle_bitwise_vs_decoded_pool(self, bits):
        """One decode expression to rule every read path: the quantized
        oracle must equal the fp oracle run on the kv_quant-decoded pool
        BITWISE — dequant happens before attention math, identically."""
        q, kp, vp, table, pos, ksc, vsc, _, _ = make_case(
            7, B=3, H=8, KV=4, hd=32, page_size=8, n_pages=4,
            num_blocks=16, bits=bits)
        quant = ref.paged_attention_ref(q, kp, vp, table, pos,
                                        k_scale=ksc, v_scale=vsc)
        kd = kvq.dequant_rows(kp, ksc, bits)
        vd = kvq.dequant_rows(vp, vsc, bits)
        fp = ref.paged_attention_ref(q, kd, vd, table, pos)
        np.testing.assert_array_equal(np.asarray(quant), np.asarray(fp))

    def test_int4_pack_roundtrip_bitwise(self):
        codes = jnp.asarray(
            np.random.RandomState(0).randint(-7, 8, size=(5, 8, 2, 16)),
            jnp.int8)
        rt = kvq.unpack_int4(kvq.pack_int4(codes))
        np.testing.assert_array_equal(np.asarray(rt), np.asarray(codes))

    def test_zero_rows_decode_to_zero(self):
        """An all-zero row quantizes to scale 0 / codes 0 and decodes to
        exactly 0.0 — no NaN from the amax=0 division guard."""
        x = jnp.zeros((4, 2, 16))
        for bits in (8, 4):
            codes, scales = kvq.quantize_kv(x, bits)
            assert float(jnp.max(jnp.abs(scales))) == 0.0
            out = kvq.dequant_rows(codes, scales, bits)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.zeros_like(np.asarray(out)))

    @pytest.mark.parametrize("bits,err", [(8, 0.006), (4, 0.1)])
    def test_roundtrip_error_bounded(self, bits, err):
        """Per-row amax scaling bounds |x - dq(q(x))| by scale/2 per
        element: ~amax/254 at int8, ~amax/14 at int4."""
        x = jax.random.normal(jax.random.PRNGKey(3), (16, 4, 64))
        codes, scales = kvq.quantize_kv(x, bits)
        dq = kvq.dequant_rows(codes, scales, bits)
        amax = float(jnp.max(jnp.abs(x)))
        assert float(jnp.max(jnp.abs(dq - x))) <= err * amax


class TestMaskingInvariants:
    @pytest.mark.parametrize("bits", BITS)
    def test_scratch_block_never_leaks(self, bits):
        """Block 0 is the reserved scratch block: inactive slots' writes
        land there, so it holds garbage — codes AND scales. Poison both
        with huge values — no live slot's output may move (its kpos are
        all > pos or mapped to blocks != 0 at kpos <= pos)."""
        q, kp, vp, table, pos, ksc, vsc, kcb, vcb = make_case(
            2, B=3, H=8, KV=4, hd=32, page_size=8, n_pages=4, num_blocks=16,
            pos=[5, 17, 30], bits=bits)
        assert int(jnp.min(table[:, 0])) > 0  # live pages avoid scratch
        base = paged_attention_tpu(q, kp, vp, table, pos, k_scale=ksc,
                                   v_scale=vsc, k_codebook=kcb,
                                   v_codebook=vcb, interpret=INTERPRET)
        if bits == 16:
            kp2 = kp.at[0].set(1e4)
            vp2 = vp.at[0].set(-1e4)
            ksc2, vsc2 = ksc, vsc
        else:
            kp2 = kp.at[0].set(127)
            vp2 = vp.at[0].set(-127)
            ksc2 = ksc.at[0].set(1e4)   # stale scale poisoning
            vsc2 = vsc.at[0].set(1e4)
        poisoned = paged_attention_tpu(q, kp2, vp2, table, pos,
                                       k_scale=ksc2, v_scale=vsc2,
                                       k_codebook=kcb, v_codebook=vcb,
                                       interpret=INTERPRET)
        np.testing.assert_allclose(np.asarray(base), np.asarray(poisoned),
                                   rtol=1e-6, atol=1e-6)
        assert_matches_oracle(q, kp2, vp2, table, pos, ksc2, vsc2, kcb, vcb)

    @pytest.mark.parametrize("bits", BITS)
    def test_idle_slot_pos0_is_finite(self, bits):
        """An idle slot (all-scratch table, pos 0) attends exactly one
        scratch row: output must be finite (no empty-softmax NaN), and the
        kernel must agree with the oracle on it."""
        q, kp, vp, table, pos, ksc, vsc, kcb, vcb = make_case(
            3, B=2, H=4, KV=2, hd=16, page_size=8, n_pages=2, num_blocks=6,
            pos=[9, 0], bits=bits)
        table = table.at[1].set(0)
        assert_matches_oracle(q, kp, vp, table, pos, ksc, vsc, kcb, vcb)
        out = paged_attention_tpu(q, kp, vp, table, pos, k_scale=ksc,
                                  v_scale=vsc, k_codebook=kcb,
                                  v_codebook=vcb, interpret=INTERPRET)
        assert bool(jnp.all(jnp.isfinite(out)))

    @pytest.mark.parametrize("bits", BITS)
    def test_recycled_block_staleness(self, bits):
        """A block freed by one slot and handed to another still holds the
        old slot's rows past the new owner's write depth — codes and, for
        quantized pools, their scales. The kpos <= pos rule must hide the
        stale tail: poisoning rows (and scale rows) past ``pos`` of the
        slot's last live page changes nothing."""
        page_size, n_pages = 8, 3
        q, kp, vp, table, pos, ksc, vsc, kcb, vcb = make_case(
            4, B=1, H=8, KV=4, hd=32, page_size=page_size, n_pages=n_pages,
            num_blocks=8, pos=[11], bits=bits)  # last live page row off = 3
        last_blk = int(table[0, 1])   # page holding pos 11
        off = 11 % page_size
        base = paged_attention_tpu(q, kp, vp, table, pos, k_scale=ksc,
                                   v_scale=vsc, k_codebook=kcb,
                                   v_codebook=vcb, interpret=INTERPRET)
        kmag, vmag = (7e3, -7e3) if bits == 16 else (127, -127)
        # stale tail: rows (off+1..) of the slot's own last page
        kp2 = kp.at[last_blk, off + 1:].set(kmag)
        vp2 = vp.at[last_blk, off + 1:].set(vmag)
        ksc2, vsc2 = ksc, vsc
        if bits != 16:
            ksc2 = ksc.at[last_blk, off + 1:].set(9e3)
            vsc2 = vsc.at[last_blk, off + 1:].set(9e3)
        # and a mapped-but-beyond-depth page (logical page 2, kpos 16..23)
        far_blk = int(table[0, 2])
        if far_blk > 0:
            kp2 = kp2.at[far_blk].set(kmag)
            vp2 = vp2.at[far_blk].set(vmag)
            if bits != 16:
                ksc2 = ksc2.at[far_blk].set(9e3)
                vsc2 = vsc2.at[far_blk].set(9e3)
        poisoned = paged_attention_tpu(q, kp2, vp2, table, pos,
                                       k_scale=ksc2, v_scale=vsc2,
                                       k_codebook=kcb, v_codebook=vcb,
                                       interpret=INTERPRET)
        np.testing.assert_allclose(np.asarray(base), np.asarray(poisoned),
                                   rtol=1e-6, atol=1e-6)
        assert_matches_oracle(q, kp2, vp2, table, pos, ksc2, vsc2, kcb, vcb)


class TestPageBlocks:
    """The kernel walks each slot's pages in blocks of pages_per_block and
    copies only live pages. Cases at serving widths (16 query heads over 8
    kv heads of 128, pages of 16 rows, 20 pages a slot: not a multiple of
    the block) put one slot inside its first page — first, so that most of
    its block is VMEM no page was ever copied to — one on the last row of
    its first block, one on the first row of the next, an idle slot at
    pos 0 on an all-scratch table, and one at full depth. Poison fills scratch
    block 0 and blocks mapped past each live slot's last page — stale
    blocks of an earlier owner — with NaN or huge values (codes at their
    extremes, scales poisoned, for the packed formats). Live slots must
    read as the oracle does on the clean pool; the idle slot, which
    attends scratch row 0, as the oracle on the poisoned pool."""

    FORMATS = [(16, jnp.float32, 2e-5), (16, jnp.bfloat16, 4e-2),
               (8, jnp.float32, 2e-5), (4, jnp.float32, 2e-5),
               (kvq.VQ_BITS, jnp.float32, 2e-5)]

    @pytest.mark.parametrize("poison", ["none", "huge", "nan"])
    @pytest.mark.parametrize("bits,dtype,tol", FORMATS,
                             ids=["f32", "bf16", "8", "4", "vq2"])
    def test_block_edges_match_oracle(self, bits, dtype, tol, poison):
        H, KV, hd, page_size, n_pages = 16, 8, 128, 16, 20
        pool_dtype = dtype if bits == 16 else jnp.int8
        ppb = pages_per_block(H, hd, page_size, KV, n_pages, pool_dtype,
                              bits)
        assert 1 < ppb < n_pages and n_pages % ppb
        rows, full = ppb * page_size, n_pages * page_size
        pos = [5, rows - 1, rows, 0, full - 1]
        B, idle = len(pos), 3
        num_blocks = B * n_pages + 8
        q, kp, vp, table, pos, ksc, vsc, kcb, vcb = make_case(
            11, B=B, H=H, KV=KV, hd=hd, page_size=page_size,
            n_pages=n_pages, num_blocks=num_blocks, pos=pos, dtype=dtype,
            bits=bits)
        table = np.array(table)
        table[idle] = 0
        # pages past pos of the live slots map to stale blocks (the rest
        # stay on scratch block 0)
        spare = sorted(set(range(1, num_blocks)) - set(table.ravel()))
        for b in (0, 1, 2):
            dead = int(pos[b]) // page_size + 1
            table[b, dead:dead + 2] = spare[:2]
            spare = spare[2:]
        table = jnp.asarray(table)
        stale = [0] + [int(x) for b in (0, 1, 2)
                       for x in table[b, int(pos[b]) // page_size + 1:]
                       if int(x)]
        clean = (kp, vp, ksc, vsc)
        if poison != "none":
            bad = jnp.nan if poison == "nan" else 1e4
            if bits == 16:
                kp = kp.at[jnp.asarray(stale)].set(bad)
                vp = vp.at[jnp.asarray(stale)].set(-bad)
            else:
                code = -1 if bits == kvq.VQ_BITS else 127
                kp = kp.at[jnp.asarray(stale)].set(code)
                vp = vp.at[jnp.asarray(stale)].set(code)
                ksc = ksc.at[jnp.asarray(stale)].set(bad)
                vsc = vsc.at[jnp.asarray(stale)].set(bad)
        got = np.asarray(paged_attention_tpu(
            q, kp, vp, table, pos, k_scale=ksc, v_scale=vsc,
            k_codebook=kcb, v_codebook=vcb, interpret=INTERPRET),
            np.float32)
        want_clean = np.asarray(ref.paged_attention_ref(
            q, clean[0], clean[1], table, pos, k_scale=clean[2],
            v_scale=clean[3], k_codebook=kcb, v_codebook=vcb), np.float32)
        want = np.asarray(ref.paged_attention_ref(
            q, kp, vp, table, pos, k_scale=ksc, v_scale=vsc,
            k_codebook=kcb, v_codebook=vcb), np.float32)
        live = [b for b in range(B) if b != idle]
        np.testing.assert_allclose(got[live], want_clean[live],
                                   rtol=tol, atol=tol)
        np.testing.assert_allclose(got[idle], want[idle], rtol=tol, atol=tol)
        assert np.isfinite(got[live]).all()


class TestVQPages:
    def test_storage_really_shrinks(self):
        """A vq2 page stores hd//4 packed-index int8 columns per row —
        2 bits/value in the pool, 6x fewer bytes per row than int4."""
        hd = 32
        _, kp, _, _, _, ksc, _, kcb, vcb = make_case(
            0, B=1, H=4, KV=2, hd=hd, page_size=8, n_pages=2,
            num_blocks=6, bits=kvq.VQ_BITS)
        assert kp.dtype == jnp.int8
        assert kp.shape[-1] == hd // 4
        assert ksc.shape == kp.shape[:-1] and ksc.dtype == jnp.float32
        assert kcb.shape == (2, kvq.VQ_K, kvq.VQ_D)
        assert vcb.shape == (2, kvq.VQ_K, kvq.VQ_D)

    def test_vq_oracle_bitwise_vs_decoded_pool(self):
        """Same one-decode-expression pin as the scalar formats: the vq
        oracle must equal the fp oracle on the vq_dequant_rows-decoded
        pool BITWISE."""
        q, kp, vp, table, pos, ksc, vsc, kcb, vcb = make_case(
            7, B=3, H=8, KV=4, hd=32, page_size=8, n_pages=4,
            num_blocks=16, bits=kvq.VQ_BITS)
        vq = ref.paged_attention_ref(q, kp, vp, table, pos, k_scale=ksc,
                                     v_scale=vsc, k_codebook=kcb,
                                     v_codebook=vcb)
        kd = kvq.vq_dequant_rows(kp, ksc, kcb)
        vd = kvq.vq_dequant_rows(vp, vsc, vcb)
        fp = ref.paged_attention_ref(q, kd, vd, table, pos)
        np.testing.assert_array_equal(np.asarray(vq), np.asarray(fp))

    def test_codebook_poison_masked_rows_inert(self):
        """Stale codes in masked rows must stay inert even when they
        index the most extreme codebook entries: replace every masked
        row's packed indices with 0xFF (entry 15 twice) after making
        entry 15 huge — no live output may move."""
        q, kp, vp, table, pos, ksc, vsc, kcb, vcb = make_case(
            8, B=2, H=4, KV=2, hd=16, page_size=4, n_pages=4,
            num_blocks=10, pos=[5, 9], bits=kvq.VQ_BITS)
        kcb = kcb.at[:, 15].set(1e4)
        vcb = vcb.at[:, 15].set(-1e4)
        base = paged_attention_tpu(q, kp, vp, table, pos, k_scale=ksc,
                                   v_scale=vsc, k_codebook=kcb,
                                   v_codebook=vcb, interpret=INTERPRET)
        # poison the scratch block's codes toward the huge entry
        kp2 = kp.at[0].set(-1)  # 0xFF -> nibbles (15, 15)
        vp2 = vp.at[0].set(-1)
        ksc2 = ksc.at[0].set(9e3)
        vsc2 = vsc.at[0].set(9e3)
        poisoned = paged_attention_tpu(q, kp2, vp2, table, pos,
                                       k_scale=ksc2, v_scale=vsc2,
                                       k_codebook=kcb, v_codebook=vcb,
                                       interpret=INTERPRET)
        np.testing.assert_allclose(np.asarray(base), np.asarray(poisoned),
                                   rtol=1e-6, atol=1e-6)
        assert_matches_oracle(q, kp2, vp2, table, pos, ksc2, vsc2, kcb, vcb)


class TestServingPathConsistency:
    def test_oracle_matches_paged_apply_gather(self):
        """Anchor the oracle against the serving engine's actual gather
        read path (models/attention._paged_apply decode): identical wo=I
        layer outputs for the same pool/table/pos."""
        from repro.configs import SMOKE
        from repro.models import attention

        cfg = SMOKE["llama2-7b"].scaled(
            dtype="float32", n_layers=1, d_model=128, vocab_size=64,
            max_seq_len=32)
        B, H, KV, hd = 2, cfg.n_heads, cfg.n_kv_heads, cfg.hd
        page_size, n_pages, num_blocks = 4, 8, 12
        q, kp, vp, table, pos, _, _, _, _ = make_case(
            5, B=B, H=H, KV=KV, hd=hd, page_size=page_size,
            n_pages=n_pages, num_blocks=num_blocks, pos=[6, 21])
        cache = attention.PagedKVCache(kp, vp, table)
        p = {"wo": jnp.eye(H * hd, dtype=jnp.float32)}
        knew = jax.random.normal(jax.random.PRNGKey(9), (B, 1, KV, hd))
        vnew = jax.random.normal(jax.random.PRNGKey(10), (B, 1, KV, hd))

        attention.set_paged_impl("gather")
        try:
            got_g, newc = attention._paged_apply(
                p, cache, q[:, None], knew, vnew, pos[:, None], jnp.float32)
        finally:
            attention.set_paged_impl("gather")
        # oracle on the post-scatter pools (the write the gather path did)
        want = ref.paged_attention_ref(q, newc.k, newc.v, table, pos)
        np.testing.assert_allclose(
            np.asarray(got_g[:, 0]), np.asarray(want).reshape(B, H * hd),
            rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_quantized_oracle_matches_paged_apply_gather(self, bits):
        """Same anchor for quantized pools: _paged_apply quantizes the
        fresh K/V in-graph (write site) and its gather path dequantizes —
        the oracle on the post-scatter code pools + scales must agree."""
        from repro.configs import SMOKE
        from repro.models import attention

        cfg = SMOKE["llama2-7b"].scaled(
            dtype="float32", n_layers=1, d_model=128, vocab_size=64,
            max_seq_len=32)
        B, H, KV, hd = 2, cfg.n_heads, cfg.n_kv_heads, cfg.hd
        q, kp, vp, table, pos, ksc, vsc, _, _ = make_case(
            5, B=B, H=H, KV=KV, hd=hd, page_size=4, n_pages=8,
            num_blocks=12, pos=[6, 21], bits=bits)
        cache = attention.PagedKVCache(kp, vp, table, ksc, vsc)
        p = {"wo": jnp.eye(H * hd, dtype=jnp.float32)}
        knew = jax.random.normal(jax.random.PRNGKey(9), (B, 1, KV, hd))
        vnew = jax.random.normal(jax.random.PRNGKey(10), (B, 1, KV, hd))
        got, newc = attention._paged_apply(
            p, cache, q[:, None], knew, vnew, pos[:, None], jnp.float32,
            impl="gather")
        assert newc.k.dtype == jnp.int8  # the write stayed quantized
        want = ref.paged_attention_ref(q, newc.k, newc.v, table, pos,
                                       k_scale=newc.k_scale,
                                       v_scale=newc.v_scale)
        np.testing.assert_allclose(
            np.asarray(got[:, 0]), np.asarray(want).reshape(B, H * hd),
            rtol=2e-5, atol=2e-5)

    def test_vq_oracle_matches_paged_apply_gather(self):
        """Same anchor for vq2 pools: _paged_apply vector-quantizes the
        fresh K/V in-graph against the cache's frozen codebooks and its
        gather path decodes through the codebook — the oracle on the
        post-scatter index pools + scales + codebooks must agree."""
        from repro.configs import SMOKE
        from repro.models import attention

        cfg = SMOKE["llama2-7b"].scaled(
            dtype="float32", n_layers=1, d_model=128, vocab_size=64,
            max_seq_len=32)
        B, H, KV, hd = 2, cfg.n_heads, cfg.n_kv_heads, cfg.hd
        q, kp, vp, table, pos, ksc, vsc, kcb, vcb = make_case(
            5, B=B, H=H, KV=KV, hd=hd, page_size=4, n_pages=8,
            num_blocks=12, pos=[6, 21], bits=kvq.VQ_BITS)
        cache = attention.PagedKVCache(kp, vp, table, ksc, vsc, kcb, vcb)
        p = {"wo": jnp.eye(H * hd, dtype=jnp.float32)}
        knew = jax.random.normal(jax.random.PRNGKey(9), (B, 1, KV, hd))
        vnew = jax.random.normal(jax.random.PRNGKey(10), (B, 1, KV, hd))
        got, newc = attention._paged_apply(
            p, cache, q[:, None], knew, vnew, pos[:, None], jnp.float32,
            impl="gather")
        assert newc.k.dtype == jnp.int8
        assert newc.k.shape[-1] == hd // 4  # the write stayed vq-packed
        want = ref.paged_attention_ref(q, newc.k, newc.v, table, pos,
                                       k_scale=newc.k_scale,
                                       v_scale=newc.v_scale,
                                       k_codebook=kcb, v_codebook=vcb)
        np.testing.assert_allclose(
            np.asarray(got[:, 0]), np.asarray(want).reshape(B, H * hd),
            rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("bits", BITS)
    def test_ops_dispatch(self, bits):
        """use_pallas toggles kernel vs oracle; both agree."""
        q, kp, vp, table, pos, ksc, vsc, kcb, vcb = make_case(
            6, B=2, H=4, KV=4, hd=16, page_size=4, n_pages=4, num_blocks=10,
            bits=bits)
        o_k = ops.paged_attention(q, kp, vp, table, pos, k_scale=ksc,
                                  v_scale=vsc, k_codebook=kcb,
                                  v_codebook=vcb, use_pallas=True,
                                  interpret=True)
        o_r = ops.paged_attention(q, kp, vp, table, pos, k_scale=ksc,
                                  v_scale=vsc, k_codebook=kcb,
                                  v_codebook=vcb, use_pallas=False)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   rtol=2e-5, atol=2e-5)
