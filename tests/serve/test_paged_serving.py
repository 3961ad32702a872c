"""Paged serving subsystem tests: block allocator, chunked-prefill plan,
capacity-aware admission, token accounting, preemption, the mixed-length
continuous-batching regression (the shared-max-position bug: interleaved
admission of staggered-length prompts must be token-identical to serving
each request alone), quantized KV pages (int8/int4 pools: solo-vs-
interleaved token identity, an explicit int8 logit-drift bound vs the
fp32-cache anchor, and byte-denominated pool sizing headroom), and the
fused VQ-dequant matmul serving path (vq_matmul_impl: gather/xla/pallas
greedy token identity over VQ-packed checkpoints + dispatch-counter
pinning)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import FAMILY_REPRESENTATIVE, SMOKE
from repro.models import model_zoo
from repro.serve.engine import Engine, Request
from repro.serve.paged_cache import BlockAllocator
from repro.serve.scheduler import CapacityError, next_chunk_len
from repro.serve.serve_step import make_decode, make_prefill

FAMILIES = list(FAMILY_REPRESENTATIVE)  # dense moe vlm ssm hybrid audio
_MODELS: dict = {}


def family_model(family: str):
    """Cached smoke model per family (params are deterministic per key)."""
    if family not in _MODELS:
        if family == "dense":
            cfg = SMOKE["llama2-7b"].scaled(
                dtype="float32", n_layers=2, d_model=64, vocab_size=256,
                max_seq_len=64)
        else:
            cfg = SMOKE[FAMILY_REPRESENTATIVE[family]].scaled(
                dtype="float32")
        model = model_zoo.build(cfg)
        _MODELS[family] = (model,
                           model.init_params(jax.random.PRNGKey(0)))
    return _MODELS[family]


def dense_model():
    return family_model("dense")[0]


def hybrid_model():
    return family_model("hybrid")[0]


def greedy_reqs(prompts, n=6, rid0=0):
    return [Request(rid=rid0 + i, prompt=p, max_new_tokens=n)
            for i, p in enumerate(prompts)]


class TestBlockAllocator:
    def test_alloc_free_exhaust(self):
        a = BlockAllocator(5)  # block 0 reserved scratch -> 4 usable
        assert a.capacity == 4
        got = a.alloc(3)
        assert len(got) == 3 and all(0 < b < 5 for b in got)
        assert a.alloc(2) is None  # all-or-nothing
        assert a.free_blocks == 1
        a.free(got)
        assert a.free_blocks == 4

    def test_scratch_never_handed_out(self):
        a = BlockAllocator(4)
        assert 0 not in a.alloc(3)

    def test_double_free_raises(self):
        a = BlockAllocator(4)
        got = a.alloc(1)
        a.free(got)
        with pytest.raises(ValueError):
            a.free(got)
        assert a.free_blocks == 3  # free list not corrupted by the raise

    def test_free_unknown_or_invalid_id_raises(self):
        a = BlockAllocator(4)
        a.alloc(1)
        with pytest.raises(ValueError):
            a.free([3])   # in range but never handed out
        with pytest.raises(ValueError):
            a.free([0])   # scratch is never allocatable
        with pytest.raises(ValueError):
            a.free([99])  # out of range

    def test_refcount_share_release(self):
        a = BlockAllocator(4)
        (b,) = a.alloc(1)
        assert a.refcount(b) == 1 and a.shared_blocks == 0
        a.share([b, b])
        assert a.refcount(b) == 3 and a.shared_blocks == 1
        a.release([b])
        a.release([b])
        assert a.refcount(b) == 1 and a.free_blocks == 2
        a.release([b])
        assert a.refcount(b) == 0 and a.free_blocks == 3
        with pytest.raises(ValueError):
            a.release([b])  # already back in the pool

    def test_share_unallocated_raises(self):
        a = BlockAllocator(4)
        with pytest.raises(ValueError):
            a.share([2])

    def test_shared_block_survives_one_release(self):
        """The prefix-sharing contract: a block referenced by two holders
        stays out of the free list until BOTH release it."""
        a = BlockAllocator(4)
        (b,) = a.alloc(1)
        a.share([b])
        a.release([b])
        assert a.free_blocks == 2 and a.refcount(b) == 1
        got = a.alloc(2)
        assert b not in got  # still held — never re-handed out
        a.release([b])
        assert a.free_blocks == 1


class TestChunkPlan:
    def test_pow2_decomposition_covers_prompt(self):
        for S in (1, 2, 5, 13, 64, 100, 255):
            sizes, rem = [], S
            while rem:
                c = next_chunk_len(rem, 64)
                assert c & (c - 1) == 0 and c <= 64
                sizes.append(c)
                rem -= c
            assert sum(sizes) == S
            # O(log): at most ceil(S/max) full chunks + log2(max) tail
            assert len(sizes) <= S // 64 + 7, (S, sizes)


class TestAdmissionAndStats:
    def test_stats_initialized_before_run(self):
        model = dense_model()
        params = model.init_params(jax.random.PRNGKey(0))
        eng = Engine(model, params, max_batch=2, max_len=48)
        assert eng.stats["tokens"] == 0  # no AttributeError pre-run

    def test_capacity_error_is_typed_and_graceful(self):
        model = dense_model()
        params = model.init_params(jax.random.PRNGKey(0))
        eng = Engine(model, params, max_batch=2, max_len=32, page_size=4)
        rng = np.random.RandomState(0)
        with pytest.raises(CapacityError):
            eng.admit(Request(rid=0, prompt=rng.randint(0, 255, size=30),
                              max_new_tokens=8))
        # run() rejects the oversized request but still serves the rest
        bad = Request(rid=1, prompt=rng.randint(0, 255, size=30),
                      max_new_tokens=8)
        ok = Request(rid=2, prompt=rng.randint(0, 255, size=5),
                     max_new_tokens=4)
        eng.run([bad, ok])
        assert bad.error is not None and bad.out_tokens == []
        assert len(ok.out_tokens) == 4

    def test_token_accounting_counts_final_tick(self):
        """Regression: tokens sampled on a request's final tick used to be
        dropped (the old run() counted surviving slots after step() freed
        finished ones)."""
        model = dense_model()
        params = model.init_params(jax.random.PRNGKey(0))
        eng = Engine(model, params, max_batch=3, max_len=48)
        rng = np.random.RandomState(0)
        reqs = greedy_reqs([rng.randint(0, 255, size=5 + i)
                            for i in range(5)], n=4)
        eng.run(reqs)
        assert eng.stats["tokens"] == sum(len(r.out_tokens) for r in reqs)
        assert eng.stats["tokens"] == 20

    def test_prefill_compiles_pow2_variants_only(self):
        """Admitting prompts of many distinct lengths must only trace the
        step fn at power-of-two chunk widths (plus the decode shape)."""
        model = dense_model()
        params = model.init_params(jax.random.PRNGKey(0))
        eng = Engine(model, params, max_batch=2, max_len=64, page_size=8,
                     prefill_chunk=16)
        rng = np.random.RandomState(0)
        reqs = greedy_reqs([rng.randint(0, 255, size=s)
                            for s in (3, 5, 7, 9, 11, 13, 21)], n=2)
        eng.run(reqs)
        sizes = {1, 2, 4, 8, 16}  # pow2 chunks <= prefill_chunk
        assert eng._prefill_fn._cache_size() <= len(sizes)
        assert eng._decode_fn._cache_size() == 1


class TestMixedLengthContinuousBatching:
    """THE regression test for the shared-max-position bug: late-admitted
    slots used to write at the oldest slot's position, leaving gaps.

    Runs on every zoo family (attention caches page; recurrent state stays
    slot-resident; audio decodes against resident cross-K/V; MoE routes
    per-row so batched rows stay independent), with the paged decode
    attention on the fused-kernel path ("pallas", interpret off-TPU) and
    the gather path — interleaved continuous batching must be
    token-identical to serving each request alone under either impl."""

    @pytest.mark.parametrize("impl", ["gather", "pallas"])
    @pytest.mark.parametrize("family", FAMILIES)
    def test_interleaved_matches_solo(self, family, impl):
        if family == "ssm" and impl == "pallas":
            pytest.skip("ssm has no attention KV leaves — no paged "
                        "attention to fuse (covered by gather run)")
        model, params = family_model(family)
        rng = np.random.RandomState(1)
        V = model.cfg.vocab_size - 1
        prompts = [rng.randint(0, V, size=s) for s in (5, 9, 3, 12)]
        eng = Engine(model, params, max_batch=2, max_len=64, page_size=8,
                     paged_attn_impl=impl)
        reqs = greedy_reqs(prompts)
        eng.run(reqs)
        assert all(len(r.out_tokens) == 6 for r in reqs)
        for i, p in enumerate(prompts):
            solo = Engine(model, params, max_batch=2, max_len=64,
                          page_size=8, paged_attn_impl=impl)
            r = greedy_reqs([p], rid0=100 + i)[0]
            solo.run([r])
            assert r.out_tokens == reqs[i].out_tokens, (family, impl, i)

    def test_width1_prefill_chunk_keeps_gather_path(self, dispatch_counters):
        """Regression: a prompt whose pow2 decomposition ends in a width-1
        chunk satisfies the fused path's S == 1 shape test — prefill must
        still be pinned to the gather read path (only the decode closure
        bakes the fused impl). Pinned via the "paged" dispatch counters
        (obs/dispatch), which increment at trace time; the fixture zeroes
        them so the counts below are absolute."""
        model, params = family_model("dense")
        eng = Engine(model, params, max_batch=1, max_len=64, page_size=8,
                     prefill_chunk=16, paged_attn_impl="pallas")
        rng = np.random.RandomState(7)
        # 17 = 16 + 1: the tail prefill chunk is width 1
        req = greedy_reqs([rng.randint(0, 255, size=17)], n=3)[0]
        eng.run([req])
        counts = dispatch_counters()["paged"]
        assert len(req.out_tokens) == 3
        # exactly one fused trace (the decode closure); every prefill
        # trace — including the width-1 tail chunk — took gather
        assert counts["pallas"] == 1
        assert counts["gather"] > 0

    def test_padded_chunk_overhanging_max_len_matches_reference(self):
        """A prompt whose padded prefill bucket overhangs the page-table
        extent must not corrupt its own live K/V (regression: out-of-range
        pages used to be clipped into the slot's last page)."""
        model = dense_model()
        params = model.init_params(jax.random.PRNGKey(0))
        rng = np.random.RandomState(3)
        # 40 tokens pad to a 64-wide chunk; positions 48..63 overhang the
        # 48-token table and must land in scratch
        prompt = rng.randint(0, 255, size=40)
        eng = Engine(model, params, max_batch=1, max_len=48, page_size=16)
        req = greedy_reqs([prompt])[0]
        eng.run([req])

        cache = model.init_cache(1, 48, dtype=jnp.float32)
        logits, cache = jax.jit(make_prefill(model))(
            params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]}, cache)
        decode = jax.jit(make_decode(model))
        tok = int(jnp.argmax(logits[0, len(prompt) - 1]))
        ref, pos = [tok], len(prompt)
        for _ in range(5):
            logits, cache = decode(params, jnp.asarray([[tok]], jnp.int32),
                                   cache, pos)
            tok = int(jnp.argmax(logits[0, -1]))
            ref.append(tok)
            pos += 1
        assert ref == req.out_tokens

    def test_empty_prompt_rejected(self):
        model = dense_model()
        params = model.init_params(jax.random.PRNGKey(0))
        eng = Engine(model, params, max_batch=2, max_len=48)
        with pytest.raises(CapacityError):
            eng.admit(Request(rid=0, prompt=np.zeros(0, np.int32),
                              max_new_tokens=4))

    def test_dense_reference_decode_anchor(self):
        """Paged greedy decode must match a plain dense-cache decode loop
        (the pre-paged serving path) token for token."""
        model = dense_model()
        params = model.init_params(jax.random.PRNGKey(0))
        rng = np.random.RandomState(2)
        prompt = rng.randint(0, 255, size=7)
        eng = Engine(model, params, max_batch=3, max_len=48, page_size=4)
        req = greedy_reqs([prompt])[0]
        eng.run([req])

        cache = model.init_cache(1, 48, dtype=jnp.float32)
        prefill = jax.jit(make_prefill(model))
        decode = jax.jit(make_decode(model))
        logits, cache = prefill(
            params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]}, cache)
        tok = int(jnp.argmax(logits[0, len(prompt) - 1]))
        ref, pos = [tok], len(prompt)
        for _ in range(5):
            logits, cache = decode(params, jnp.asarray([[tok]], jnp.int32),
                                   cache, pos)
            tok = int(jnp.argmax(logits[0, -1]))
            ref.append(tok)
            pos += 1
        assert ref == req.out_tokens


class TestQuantizedKVPages:
    """int8/int4 paged KV pools (KVQuantSpec): serving correctness on top
    of the kernel-level differential suite — interleaved continuous
    batching must stay token-identical to solo serving under a quantized
    pool (quantization is deterministic per written row, so the codes a
    slot produces do not depend on its neighbors), and int8 logits must
    stay within an explicit drift bound of the fp32-cache anchor."""

    @pytest.mark.parametrize("family,impl", [
        ("dense", "gather"),   # portable write+read path
        ("dense", "pallas"),   # fused in-kernel dequant, interpret mode
        ("hybrid", "xla"),     # fused dispatch via the oracle + ssm state
    ])
    def test_interleaved_matches_solo_int8(self, family, impl):
        model, params = family_model(family)
        rng = np.random.RandomState(4)
        V = model.cfg.vocab_size - 1
        prompts = [rng.randint(0, V, size=s) for s in (5, 9, 3, 12)]
        eng = Engine(model, params, max_batch=2, max_len=64, page_size=8,
                     paged_attn_impl=impl, kv_cache_bits=8)
        reqs = greedy_reqs(prompts)
        eng.run(reqs)
        assert all(len(r.out_tokens) == 6 for r in reqs)
        for i, p in enumerate(prompts):
            solo = Engine(model, params, max_batch=2, max_len=64,
                          page_size=8, paged_attn_impl=impl,
                          kv_cache_bits=8)
            r = greedy_reqs([p], rid0=200 + i)[0]
            solo.run([r])
            assert r.out_tokens == reqs[i].out_tokens, (family, impl, i)

    @pytest.mark.parametrize("family", ["dense", "hybrid"])
    def test_int8_logit_drift_vs_fp32_anchor(self, family):
        """Greedy decode over an int8-page pool, logits compared step by
        step against the identical loop over a passthrough fp32 pool.
        Measured drift is ~0.03-0.07 on a ~3-4 logit scale for these
        models; 0.25 is a >3x margin that still fails on any masking or
        scale-handling bug (those blow drift past the logit scale)."""
        from repro.models.attention import KVQuantSpec, PagedLayout
        from repro.serve import paged_cache as pc

        model, params = family_model(family)
        max_len, page_size = 48, 8
        n_pages = max_len // page_size
        rng = np.random.RandomState(5)
        prompt = rng.randint(0, model.cfg.vocab_size - 1, size=9)
        table = np.arange(1, n_pages + 1, dtype=np.int32)[None]

        def logit_trace(bits):
            layout = PagedLayout(n_pages + 1, page_size, KVQuantSpec(bits))
            cache = model.init_cache(1, max_len, dtype=jnp.float32,
                                     paged=layout)
            cache = pc.push_page_table(cache, table)
            logits, cache, _ = model.forward(
                params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]},
                cache=cache, pos=jnp.zeros((1,), jnp.int32))
            out, pos = [logits[0, -1]], len(prompt)
            tok = int(jnp.argmax(logits[0, -1]))
            for _ in range(6):
                logits, cache, _ = model.forward(
                    params, {"tokens": jnp.asarray([[tok]], jnp.int32)},
                    cache=cache, pos=jnp.full((1,), pos, jnp.int32))
                out.append(logits[0, -1])
                tok = int(jnp.argmax(logits[0, -1]))
                pos += 1
            return out

        anchor = logit_trace(16)
        quant = logit_trace(8)
        drift = max(float(jnp.max(jnp.abs(a - b)))
                    for a, b in zip(anchor, quant))
        assert drift < 0.25, (family, drift)

    def test_pool_bytes_headroom(self):
        """Byte-denominated sizing: at a fixed pool budget the quantized
        formats must expose the page-count headroom that motivates them
        (int8 ~3.5x, int4 ~6x over the fp32 CPU-host pools; both >= 2x)."""
        from repro.serve.paged_cache import pool_blocks_for_bytes

        model = dense_model()
        cfg = model.cfg
        budget = 1 << 20
        fp = pool_blocks_for_bytes(budget, cfg, 8, 16, jnp.float32)
        i8 = pool_blocks_for_bytes(budget, cfg, 8, 8, jnp.float32)
        i4 = pool_blocks_for_bytes(budget, cfg, 8, 4, jnp.float32)
        # at this smoke config's hd=16 the f32 scale overhead is 4/20 of
        # an int8 row and 4/12 of an int4 row, so the exact ratios are
        # 3.2x / 5.3x (not 4x / 8x) — the accounting must reflect that
        assert i8 >= 3 * fp and i4 >= 5 * fp

    def test_engine_pool_bytes_ctor(self):
        """Engine(pool_bytes=...) sizes the allocator from bytes; the
        quantized engine gets more usable pages from the same budget and
        still serves correctly."""
        model, params = family_model("dense")
        cfg = model.cfg
        from repro.kernels import kv_quant
        budget = 40 * kv_quant.page_bytes(8, cfg.n_kv_heads, cfg.hd, 16,
                                          dtype_bytes=4)
        fp = Engine(model, params, max_batch=2, max_len=64, page_size=8,
                    pool_bytes=budget)
        q8 = Engine(model, params, max_batch=2, max_len=64, page_size=8,
                    pool_bytes=budget, kv_cache_bits=8)
        assert fp.scheduler.allocator.capacity == 39
        assert q8.scheduler.allocator.capacity >= 2 * 39
        rng = np.random.RandomState(6)
        reqs = greedy_reqs([rng.randint(0, 255, size=7)], n=4)
        q8.run(reqs)
        assert len(reqs[0].out_tokens) == 4


class TestVQKVPages:
    """vq2 vector-quantized KV pages (kv_cache_bits="vq2"): pages hold
    packed 4-bit codebook indices over d=2 head-dim vectors, with
    per-(pool, kv-head) codebooks EM-calibrated at engine load and then
    frozen. Assignment is a deterministic per-row argmin against frozen
    codebooks, so the serving invariants of the scalar formats carry
    over unchanged: interleaved continuous batching and preemption
    replay must stay token-identical to solo/unpressured serving, and
    logits must stay within an explicit drift bound of the fp32-cache
    anchor when decoding the same token path."""

    @pytest.mark.parametrize("impl", ["gather", "pallas"])
    def test_interleaved_matches_solo_vq2(self, impl):
        model, params = family_model("dense")
        rng = np.random.RandomState(14)
        prompts = [rng.randint(0, 255, size=s) for s in (5, 9, 3, 12)]
        eng = Engine(model, params, max_batch=2, max_len=64, page_size=8,
                     paged_attn_impl=impl, kv_cache_bits="vq2")
        reqs = greedy_reqs(prompts)
        eng.run(reqs)
        assert all(len(r.out_tokens) == 6 for r in reqs)
        for i, p in enumerate(prompts):
            # calibration is deterministic, so each solo engine freezes
            # the same codebooks as the interleaved one
            solo = Engine(model, params, max_batch=2, max_len=64,
                          page_size=8, paged_attn_impl=impl,
                          kv_cache_bits="vq2")
            r = greedy_reqs([p], rid0=800 + i)[0]
            solo.run([r])
            assert r.out_tokens == reqs[i].out_tokens, (impl, i)

    def test_vq2_logit_drift_vs_fp32_anchor(self):
        """Decode over a calibrated vq2 pool, teacher-forced onto the
        fp32 anchor's greedy token path so every step compares logits for
        identical inputs (free-running traces diverge in token space and
        then compare logits of different sequences — meaningless).

        Drift is the per-step RMS logit difference across the vocab, max
        over steps: the scale-stable statistic (a single-logit max is an
        order statistic of |V| near-iid errors — it grows with vocab
        size, not with cache quality). Measured ~0.5-0.7 here on this
        random-weight model's ~1.0 RMS logit scale — 2 bits/value is
        coarse — while any masking, scale, or codebook-indexing bug
        decorrelates the logits entirely and blows RMS drift past the
        ~1.4 level of independent draws; 1.0 separates the two
        regimes."""
        from repro.models.attention import KVQuantSpec, PagedLayout
        from repro.serve import paged_cache as pc
        from repro.serve.engine import calibrate_vq_codebooks

        model, params = family_model("dense")
        max_len, page_size = 48, 8
        n_pages = max_len // page_size
        rng = np.random.RandomState(15)
        prompt = rng.randint(0, model.cfg.vocab_size - 1, size=9)
        table = np.arange(1, n_pages + 1, dtype=np.int32)[None]

        def logit_trace(bits, forced=None):
            layout = PagedLayout(n_pages + 1, page_size,
                                 KVQuantSpec.of(bits))
            cache = model.init_cache(1, max_len, dtype=jnp.float32,
                                     paged=layout)
            if bits == "vq2":
                cache = calibrate_vq_codebooks(model, params, cache,
                                               page_size=page_size,
                                               calib_len=32)
            cache = pc.push_page_table(cache, table)
            logits, cache, _ = model.forward(
                params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]},
                cache=cache, pos=jnp.zeros((1,), jnp.int32))
            out, toks, pos = [logits[0, -1]], [], len(prompt)
            tok = int(jnp.argmax(logits[0, -1]))
            for i in range(6):
                if forced is not None:
                    tok = forced[i]
                toks.append(tok)
                logits, cache, _ = model.forward(
                    params, {"tokens": jnp.asarray([[tok]], jnp.int32)},
                    cache=cache, pos=jnp.full((1,), pos, jnp.int32))
                out.append(logits[0, -1])
                tok = int(jnp.argmax(logits[0, -1]))
                pos += 1
            return out, toks

        anchor, anchor_toks = logit_trace(16)
        vq, _ = logit_trace("vq2", forced=anchor_toks)
        drift = max(float(jnp.sqrt(jnp.mean((a - b) ** 2)))
                    for a, b in zip(anchor, vq))
        assert drift < 1.0, drift
        # int8 on the same forced path sits two orders below — the vq2
        # drift is quantization coarseness, not a broken read path
        i8, _ = logit_trace(8, forced=anchor_toks)
        drift8 = max(float(jnp.sqrt(jnp.mean((a - b) ** 2)))
                     for a, b in zip(anchor, i8))
        assert drift8 < 0.05, drift8

    def test_vq2_preemption_replay_identical(self):
        """Recompute-style preemption replays the whole sequence through
        the same frozen codebooks; the rewritten pages are bit-identical
        to the originals, so outputs must match the unpressured run."""
        model, params = family_model("dense")
        rng = np.random.RandomState(16)
        prompts = [rng.randint(0, 255, size=s) for s in (10, 14, 7)]
        big = Engine(model, params, max_batch=2, max_len=64, page_size=4,
                     kv_cache_bits="vq2")
        ref = greedy_reqs(prompts, n=8)
        big.run(ref)
        assert big.stats["preemptions"] == 0
        tight = Engine(model, params, max_batch=2, max_len=64, page_size=4,
                       num_blocks=9, kv_cache_bits="vq2")
        out = greedy_reqs(prompts, n=8, rid0=10)
        tight.run(out)
        assert tight.stats["preemptions"] > 0
        for a, b in zip(ref, out):
            assert a.out_tokens == b.out_tokens

    def test_pool_bytes_headroom_vq2(self):
        """At this smoke config's hd=16 a vq2 row is 8 B (4 B packed
        indices + 4 B scale) vs 64 B fp32, so the page headroom lands
        just under 8x after the codebook overhead is charged against the
        budget (the >= 10x acceptance figure is at the bench hd=32,
        where the fixed 4 B scale amortizes over twice the row)."""
        from repro.serve.paged_cache import pool_blocks_for_bytes

        model = dense_model()
        cfg = model.cfg
        budget = 1 << 20
        fp = pool_blocks_for_bytes(budget, cfg, 8, 16, jnp.float32)
        vq = pool_blocks_for_bytes(budget, cfg, 8, "vq2", jnp.float32)
        i4 = pool_blocks_for_bytes(budget, cfg, 8, 4, jnp.float32)
        assert vq >= 7 * fp
        assert vq > i4  # strictly beyond the best scalar format

    def test_engine_pool_bytes_ctor_vq2(self):
        """Engine(pool_bytes=..., kv_cache_bits="vq2") sizes the
        allocator from bytes (codebook overhead included) and still
        serves correctly."""
        model, params = family_model("dense")
        cfg = model.cfg
        from repro.kernels import kv_quant
        budget = 40 * kv_quant.page_bytes(8, cfg.n_kv_heads, cfg.hd, 16,
                                          dtype_bytes=4)
        fp = Engine(model, params, max_batch=2, max_len=64, page_size=8,
                    pool_bytes=budget)
        vq = Engine(model, params, max_batch=2, max_len=64, page_size=8,
                    pool_bytes=budget, kv_cache_bits="vq2")
        assert fp.scheduler.allocator.capacity == 39
        assert vq.scheduler.allocator.capacity >= 7 * 39
        rng = np.random.RandomState(17)
        reqs = greedy_reqs([rng.randint(0, 255, size=7)], n=4)
        vq.run(reqs)
        assert len(reqs[0].out_tokens) == 4


_VQ_PACKED: dict = {}


def vq_packed_params(family: str):
    """Cached VQ-packed (GPTVQ + pack) params per family — the checkpoints
    the fused serving tests decode against."""
    if family not in _VQ_PACKED:
        from repro.core.bpv import VQConfig
        from repro.core.pipeline import quantize_model
        from repro.data.calibration import calibration_tokens

        model, params = family_model(family)
        calib = calibration_tokens(model.cfg.vocab_size, n_sequences=4,
                                   seq_len=32)
        cfg = VQConfig(d=2, bits_per_dim=2, group_size=2048, em_iters=3,
                       codebook_update_iters=0)
        _VQ_PACKED[family], _ = quantize_model(model, params, calib,
                                               "gptvq", cfg, pack=True)
    return _VQ_PACKED[family]


class TestFusedVQServing:
    """The fused VQ-dequant matmul serving path (Engine vq_matmul_impl=):
    greedy decode over a VQ-packed checkpoint must be token-identical
    across the gather (per-layer densify), XLA-fused, and Pallas-fused
    paths, on dense, MoE (stacked expert leaves), and hybrid (fused trunk
    + densified shared-attention LoRA) families — and the "vq" dispatch
    counters (obs/dispatch) must pin which path actually traced."""

    @pytest.mark.parametrize("family,impl", [
        ("dense", "xla"),     # fused-boundary oracle
        ("dense", "pallas"),  # in-VMEM decode kernel, interpret mode
        ("moe", "xla"),       # stacked expert leaves via expert_matmul
        ("hybrid", "xla"),    # fused trunk + dense shared-attn LoRA
    ])
    def test_fused_matches_gather(self, family, impl, dispatch_counters):
        model, _ = family_model(family)
        qparams = vq_packed_params(family)
        rng = np.random.RandomState(8)
        V = model.cfg.vocab_size - 1
        prompts = [rng.randint(0, V, size=s) for s in (5, 9, 3)]

        ref = Engine(model, qparams, max_batch=2, max_len=64, page_size=8,
                     vq_matmul_impl="gather")
        ref_reqs = greedy_reqs(prompts)
        ref.run(ref_reqs)
        assert all(len(r.out_tokens) == 6 for r in ref_reqs)

        before = dispatch_counters()["vq"]
        eng = Engine(model, qparams, max_batch=2, max_len=64, page_size=8,
                     vq_matmul_impl=impl)
        reqs = greedy_reqs(prompts, rid0=300)
        eng.run(reqs)
        counts = dispatch_counters()["vq"]
        assert counts[impl] > before[impl], \
            f"{impl} path never traced — silent fallback"
        for a, b in zip(ref_reqs, reqs):
            assert a.out_tokens == b.out_tokens, (family, impl, a.rid)

    def test_interleaved_matches_solo_vq_fused(self):
        """Continuous batching on the fused path: interleaved admission of
        staggered prompts over a VQ-packed checkpoint must stay
        token-identical to serving each request alone ("fused" resolves
        per-backend: Pallas on TPU, the XLA oracle elsewhere)."""
        model, _ = family_model("dense")
        qparams = vq_packed_params("dense")
        rng = np.random.RandomState(9)
        prompts = [rng.randint(0, 255, size=s) for s in (5, 9, 3, 12)]
        eng = Engine(model, qparams, max_batch=2, max_len=64, page_size=8,
                     vq_matmul_impl="fused")
        reqs = greedy_reqs(prompts)
        eng.run(reqs)
        assert all(len(r.out_tokens) == 6 for r in reqs)
        for i, p in enumerate(prompts):
            solo = Engine(model, qparams, max_batch=2, max_len=64,
                          page_size=8, vq_matmul_impl="fused")
            r = greedy_reqs([p], rid0=400 + i)[0]
            solo.run([r])
            assert r.out_tokens == reqs[i].out_tokens, i

    def test_fused_resolves_per_backend(self):
        """Engine(vq_matmul_impl="fused") resolves to the concrete impl at
        ctor time: off-TPU that is the XLA oracle, never Pallas."""
        model, _ = family_model("dense")
        qparams = vq_packed_params("dense")
        eng = Engine(model, qparams, max_batch=1, max_len=64, page_size=8,
                     vq_matmul_impl="fused")
        expected = "pallas" if jax.default_backend() == "tpu" else "xla"
        assert eng.vq_matmul_impl == expected


class TestPrefixSharing:
    """Prefix-sharing subsystem (serve/prefix_cache.py): admitted
    requests whose prompt prefix is already cached point their page
    tables at the shared physical blocks and skip those prefill chunks.
    Cached pages are byte-identical to what a private prefill would have
    written (content is a pure function of token ids + absolute
    positions), so warm serving must be greedy-token-identical to cold
    solo serving — checked here on the dense family with both the gather
    and fused decode read paths, and on hybrid (where the engine must
    detect the slot-resident ssm state and keep the cache inert rather
    than serve from state it cannot replay)."""

    def _shared_prompts(self, V, n=4, header=40, rng_seed=11):
        rng = np.random.RandomState(rng_seed)
        header_toks = rng.randint(0, V, size=header)
        return [np.concatenate([header_toks,
                                rng.randint(0, V, size=3 + i)])
                for i in range(n)]

    @pytest.mark.parametrize("family,impl", [
        ("dense", "gather"),
        ("dense", "pallas"),   # fused in-kernel page gather, interpret
        ("hybrid", "xla"),     # fused dispatch; cache must stay inert
    ])
    def test_shared_prefix_matches_solo(self, family, impl):
        model, params = family_model(family)
        V = model.cfg.vocab_size - 1
        prompts = self._shared_prompts(V)
        warm = Engine(model, params, max_batch=2, max_len=96, page_size=16,
                      paged_attn_impl=impl, prefix_cache=True)
        reqs = greedy_reqs(prompts)
        warm.run(reqs)
        assert all(len(r.out_tokens) == 6 for r in reqs)
        if family == "dense":
            # max_batch=2: the first pair admits before anything is
            # cached; every later request must hit the 2 shared pages
            assert warm.stats["prefix_hits"] >= len(prompts) - 2
            assert warm.stats["prefix_hit_tokens"] >= 32
        else:
            # slot-resident recurrent state detected structurally:
            # sharing stays off no matter what the ctor asked for
            assert warm.prefix_cache is None
        for i, p in enumerate(prompts):
            solo = Engine(model, params, max_batch=2, max_len=96,
                          page_size=16, paged_attn_impl=impl)
            r = greedy_reqs([p], rid0=500 + i)[0]
            solo.run([r])
            assert r.out_tokens == reqs[i].out_tokens, (family, impl, i)

    def test_prefix_hit_skips_prefill_chunks(self):
        """The point of the subsystem: a warm admission must run strictly
        fewer prefill chunks than its cold run (shared pages enter the
        page table without a forward), and emit the prefix_hit event."""
        model, params = family_model("dense")
        prompts = self._shared_prompts(254, n=2, header=64)
        kw = dict(max_batch=1, max_len=128, page_size=16, prefill_chunk=16)

        cold = Engine(model, params, **kw)
        cold.run(greedy_reqs([prompts[1]], n=2))
        warm = Engine(model, params, prefix_cache=True, **kw)
        warm.run(greedy_reqs([prompts[0]], n=2))       # populates cache
        chunks_before = warm.stats["prefill_chunks"]
        warm.run(greedy_reqs([prompts[1]], n=2, rid0=1))
        warm_chunks = warm.stats["prefill_chunks"] - chunks_before
        cold_chunks = cold.stats["prefill_chunks"]
        # 64 shared header tokens = 4 full pages skipped at chunk 16
        assert warm_chunks <= cold_chunks - 4, (warm_chunks, cold_chunks)
        hits = [e for e in warm.telemetry.events.events
                if e["event"] == "prefix_hit"]
        assert hits and hits[-1]["pages"] >= 4

    def test_preempted_sharer_releases_not_frees(self):
        """A preempted sequence holding shared pages must leave them
        alive for the cache/co-sharers (release, never free) and still
        complete token-identically after replay."""
        model, params = family_model("dense")
        prompts = self._shared_prompts(254, n=3, header=32)
        ref_out = []
        for i, p in enumerate(prompts):
            solo = Engine(model, params, max_batch=2, max_len=96,
                          page_size=8)
            r = greedy_reqs([p], n=8, rid0=600 + i)[0]
            solo.run([r])
            ref_out.append(r.out_tokens)
        # oversubscribed pool: 12 usable blocks for 2 live seqs needing
        # up to ~12 combined plus the cache's references -> preemptions
        # and cache evictions both fire
        tight = Engine(model, params, max_batch=2, max_len=96, page_size=8,
                       num_blocks=13, prefix_cache=True)
        reqs = greedy_reqs(prompts, n=8, rid0=700)
        tight.run(reqs)
        for r, ref in zip(reqs, ref_out):
            assert r.out_tokens == ref, r.rid
        alloc = tight.scheduler.allocator
        for b in tight.prefix_cache.blocks():
            assert alloc.refcount(b) == 1  # only the cache holds them


class TestForkedSampling:
    """Request(n=) parallel sampling: n-1 children fork off the parent's
    prompt blocks once its prefill completes."""

    def test_forks_greedy_identical_to_solo(self):
        model, params = family_model("dense")
        rng = np.random.RandomState(12)
        prompt = rng.randint(0, 254, size=40)
        solo = Engine(model, params, max_batch=1, max_len=96, page_size=16)
        sr = greedy_reqs([prompt])[0]
        solo.run([sr])

        eng = Engine(model, params, max_batch=3, max_len=96, page_size=16,
                     prefix_cache=True)
        parent = Request(rid=0, prompt=prompt, max_new_tokens=6, n=3)
        eng.run([parent])
        assert parent.done and len(parent.forks) == 2
        assert parent.out_tokens == sr.out_tokens
        for child in parent.forks:
            assert child.done and child.out_tokens == sr.out_tokens, \
                child.rid
        # children admitted after the parent's prefill registered the
        # prompt's full pages: every one of them must be a prefix hit
        assert eng.stats["prefix_hits"] >= 2
        assert eng.scheduler.allocator.shared_blocks > 0 or \
            eng.stats["prefix_hit_tokens"] > 0

    def test_forks_without_prefix_cache_still_serve(self):
        """n>1 must degrade gracefully with the cache off: children
        re-prefill privately and stay greedy-identical."""
        model, params = family_model("dense")
        rng = np.random.RandomState(13)
        prompt = rng.randint(0, 254, size=20)
        eng = Engine(model, params, max_batch=2, max_len=64, page_size=8)
        parent = Request(rid=0, prompt=prompt, max_new_tokens=4, n=3)
        eng.run([parent])
        assert parent.done and all(c.done for c in parent.forks)
        for child in parent.forks:
            assert child.out_tokens == parent.out_tokens


class TestPreemption:
    def test_pool_exhaustion_preempts_and_completes(self):
        """With an oversubscribed pool the youngest request is evicted and
        recomputed; greedy outputs still match the unpressured engine."""
        model = dense_model()
        params = model.init_params(jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 255, size=s) for s in (10, 14, 7)]
        big = Engine(model, params, max_batch=2, max_len=64, page_size=4)
        ref = greedy_reqs(prompts, n=8)
        big.run(ref)
        assert big.stats["preemptions"] == 0

        tight = Engine(model, params, max_batch=2, max_len=64, page_size=4,
                       num_blocks=9)  # 8 usable; 2 live seqs need up to 12
        out = greedy_reqs(prompts, n=8, rid0=10)
        tight.run(out)
        assert tight.stats["preemptions"] > 0
        for a, b in zip(ref, out):
            assert a.out_tokens == b.out_tokens
        assert tight.stats["tokens"] == sum(len(r.out_tokens) for r in out)


class TestAttnLiveBlockShare:
    def test_share_follows_decode_positions(self):
        """serve.attn_live_block_share: every decode tick observes the
        share of the paged kernel's page blocks its positions reach — a
        slot its blocks up to the one holding pos, an idle or prefilling
        slot (pos 0) its first — computed from the positions the tick
        hands the device and the kernel's own pages_per_block."""
        from repro.kernels.paged_attention import pages_per_block

        cfg = SMOKE["llama2-7b"].scaled(
            dtype="float32", n_layers=1, d_model=256, n_heads=2,
            n_kv_heads=2, head_dim=128, d_ff=256, vocab_size=256,
            max_seq_len=512)
        model = model_zoo.build(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        page_size, max_len, max_batch = 16, 512, 3
        n_pages = max_len // page_size
        ppb = pages_per_block(cfg.n_heads, cfg.hd, page_size,
                              cfg.n_kv_heads, n_pages, jnp.float32, 16)
        assert ppb < n_pages   # more than one block per slot
        rows, n_blocks = ppb * page_size, -(-n_pages // ppb)
        eng = Engine(model, params, max_batch=max_batch, max_len=max_len,
                     page_size=page_size)
        seen = []
        decode = eng._decode_fn

        def spy(*args):
            seen.append(np.asarray(args[3]))
            return decode(*args)

        eng._decode_fn = spy
        rng = np.random.RandomState(0)
        # one slot decodes across a block edge, one stays in its first
        # block, the third slot stays idle
        reqs = greedy_reqs([rng.randint(0, 255, size=rows - 4),
                            rng.randint(0, 255, size=20)], n=8)
        eng.run(reqs)
        want = [float(np.sum(p // rows + 1)) / (max_batch * n_blocks)
                for p in seen]
        h = eng.telemetry.registry.histogram("serve.attn_live_block_share")
        assert h.count == len(want) == eng.stats["decode_ticks"]
        np.testing.assert_allclose([h.sum, h.min, h.max],
                                   [sum(want), min(want), max(want)])
        assert len(set(want)) > 1   # the edge was crossed
