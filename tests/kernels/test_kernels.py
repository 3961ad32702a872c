"""Pallas kernel validation: interpret-mode sweeps vs pure-jnp oracles, plus
consistency with the VQLinear serving path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing
from repro.core.bpv import VQConfig
from repro.core import vq_linear as vql_mod
from repro.kernels import ops, ref

from tests.core.test_quant_core import make_problem

pytestmark = pytest.mark.kernels


def make_vq_inputs(key, *, N, K, d, bits, rows_per_band, group_cols, k_c=None):
    k_c = k_c or 2 ** (d * bits)
    n_cg, n_bands = K // group_cols, N // rows_per_band
    k1, k2 = jax.random.split(key)
    codes = jax.random.randint(k1, (N, K // d), 0, k_c)
    code_bits = max(1, (k_c - 1).bit_length())
    words = jax.vmap(lambda r: packing.pack(r, code_bits))(codes)
    C = jax.random.normal(k2, (n_cg, n_bands, k_c, d))
    return words, C, code_bits


class TestVQDequantMatmul:
    @pytest.mark.parametrize(
        "M,N,K,d,bits,rg,cg",
        [
            (8, 64, 256, 2, 2, 8, 256),
            (16, 128, 512, 2, 2, 8, 256),
            (8, 64, 256, 1, 3, 4, 256),   # 3-bit codes in 4-bit containers
            (8, 64, 512, 4, 2, 16, 256),
            (8, 64, 256, 2, 4, 2, 128),
        ],
    )
    def test_matches_oracle(self, M, N, K, d, bits, rg, cg):
        key = jax.random.PRNGKey(42)
        words, C, code_bits = make_vq_inputs(
            key, N=N, K=K, d=d, bits=bits, rows_per_band=rg, group_cols=cg)
        x = jax.random.normal(jax.random.PRNGKey(1), (M, K))
        from repro.kernels.vq_dequant_matmul import vq_dequant_matmul
        y = vq_dequant_matmul(
            x, words, C, d=d, k_c=2 ** (d * bits),
            container_bits=packing.container_bits(code_bits),
            rows_per_band=rg, group_cols=cg,
            tile_m=min(8, M), tile_n=min(64, N), tile_k=min(256, K),
            interpret=True)
        y_ref = ref.vq_dequant_matmul_ref(
            x, words, C, d=d, code_bits=code_bits, rows_per_band=rg,
            group_cols=cg)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        key = jax.random.PRNGKey(0)
        words, C, code_bits = make_vq_inputs(
            key, N=64, K=256, d=2, bits=2, rows_per_band=8, group_cols=256)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 256)).astype(dtype)
        from repro.kernels.vq_dequant_matmul import vq_dequant_matmul
        y = vq_dequant_matmul(
            x, words, C, d=2, k_c=16,
            container_bits=4, rows_per_band=8, group_cols=256,
            tile_m=8, tile_n=64, tile_k=256, interpret=True)
        y_ref = ref.vq_dequant_matmul_ref(
            x.astype(jnp.float32), words, C, d=2, code_bits=code_bits,
            rows_per_band=8, group_cols=256)
        tol = 1e-4 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=tol, atol=tol)

    def test_consistent_with_vqlinear_serving_path(self):
        """kernel(x, packed) == x @ dequantize(packed).T for a real quantizer
        output (end-to-end: GPTVQ -> pack -> kernel)."""
        W, X, H, U = make_problem(r=64, c=256)
        cfg = VQConfig(d=2, bits_per_dim=2, group_size=2048, em_iters=10,
                       codebook_update_iters=0)
        vql = vql_mod.quantize_array(W, H, cfg)
        x = jax.random.normal(jax.random.PRNGKey(3), (8, 256))
        y_kernel = ops.vql_matmul(x, vql, use_pallas=True, interpret=True,
                                  tile_m=8, tile_n=64, tile_k=256)
        y_dense = x @ vql_mod.dequantize(vql, jnp.float32).T
        np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_dense),
                                   rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("M", [1, 3, 5, 17])
    def test_decode_shaped_m(self, M):
        """Decode batches (M = 1..batch, not tile-aligned) must not trip the
        tile_m divisibility assert: the wrapper pads M up and slices back."""
        key = jax.random.PRNGKey(6)
        words, C, code_bits = make_vq_inputs(
            key, N=64, K=256, d=2, bits=2, rows_per_band=8, group_cols=256)
        x = jax.random.normal(jax.random.PRNGKey(7), (M, 256))
        from repro.kernels.vq_dequant_matmul import vq_dequant_matmul
        y = vq_dequant_matmul(
            x, words, C, d=2, k_c=16,
            container_bits=4, rows_per_band=8, group_cols=256,
            tile_m=128, tile_n=64, tile_k=256, interpret=True)
        assert y.shape == (M, 64)
        y_ref = ref.vq_dequant_matmul_ref(
            x, words, C, d=2, code_bits=code_bits, rows_per_band=8,
            group_cols=256)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_ragged_n_k_snap_to_layout(self):
        """N/K not divisible by the requested tile sizes: the wrapper snaps
        tile_n to a band multiple and tile_k to a lane-aligned group
        multiple instead of asserting."""
        key = jax.random.PRNGKey(8)
        words, C, code_bits = make_vq_inputs(
            key, N=96, K=384, d=2, bits=2, rows_per_band=8, group_cols=128)
        x = jax.random.normal(jax.random.PRNGKey(9), (4, 384))
        from repro.kernels.vq_dequant_matmul import vq_dequant_matmul
        y = vq_dequant_matmul(
            x, words, C, d=2, k_c=16,
            container_bits=4, rows_per_band=8, group_cols=128,
            tile_m=128, tile_n=128, tile_k=256, interpret=True)
        y_ref = ref.vq_dequant_matmul_ref(
            x, words, C, d=2, code_bits=code_bits, rows_per_band=8,
            group_cols=128)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("Ns,tk", [(16, 256), (64, 128), (32, 64)])
    def test_blockwise_scales(self, Ns, tk):
        """scale_block != 0: the pre-expanded (N, K/Ns) normalization plane
        is applied to the decoded tile inside the kernel."""
        key = jax.random.PRNGKey(10)
        words, C, code_bits = make_vq_inputs(
            key, N=64, K=512, d=2, bits=2, rows_per_band=8, group_cols=256)
        scales = jnp.exp2(jax.random.normal(
            jax.random.PRNGKey(11), (64, 512 // Ns)) * 0.5)
        x = jax.random.normal(jax.random.PRNGKey(12), (8, 512))
        from repro.kernels.vq_dequant_matmul import vq_dequant_matmul
        y = vq_dequant_matmul(
            x, words, C, scales, d=2, k_c=16,
            container_bits=4, rows_per_band=8, group_cols=256,
            scale_block=Ns, tile_m=8, tile_n=64, tile_k=tk, interpret=True)
        y_ref = ref.vq_dequant_matmul_ref(
            x, words, C, scales, d=2, code_bits=code_bits, rows_per_band=8,
            group_cols=256, scale_block=Ns)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)


class TestFusedVQLinear:
    """prepare_fused / fused_matmul: the engine-load prep pass and the
    per-matmul dispatch that serve/engine.Engine(vq_matmul_impl=...) uses."""

    def _quantized(self, *, scale_block=0, r=64, c=256):
        W, X, H, U = make_problem(r=r, c=c)
        cfg = VQConfig(d=2, bits_per_dim=2, group_size=2048, em_iters=8,
                       codebook_update_iters=0, scale_block=scale_block)
        return vql_mod.quantize_array(W, H, cfg)

    @pytest.mark.parametrize("sb", [0, 8])
    def test_prepare_matches_dequantize(self, sb):
        """fused_dequantize(prepare_fused(v)) == dequantize(v): prep folds
        cb_scale + the exp2 scale plane without changing the weights."""
        vql = self._quantized(scale_block=sb)
        fvl = vql_mod.prepare_fused(vql)
        assert isinstance(fvl, vql_mod.FusedVQLinear)
        assert (fvl.scales is not None) == bool(sb)
        W_f = vql_mod.fused_dequantize(fvl, jnp.float32)
        W_g = vql_mod.dequantize(vql, jnp.float32)
        np.testing.assert_allclose(np.asarray(W_f), np.asarray(W_g),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("sb", [0, 8])
    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_fused_matmul_matches_dense(self, sb, impl):
        """Both fused impls == x @ dequantize(v).T, with and without
        blockwise normalization, for decode-shaped and prefill-shaped x."""
        vql = self._quantized(scale_block=sb)
        fvl = vql_mod.prepare_fused(vql)
        W = vql_mod.dequantize(vql, jnp.float32)
        for M in (1, 8):
            x = jax.random.normal(jax.random.PRNGKey(M), (M, 256))
            y = vql_mod.fused_matmul(x, fvl, impl=impl, interpret=True,
                                     tile_n=64, tile_k=256)
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(x @ W.T), rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_stacked_expert_leaves(self, impl):
        """MoE-style stacked leaves (leading E on every array) route through
        models/common.expert_matmul and match the per-expert dense einsum."""
        from repro.models import common as cm
        v1, v2 = self._quantized(), self._quantized(r=64, c=256)
        stacked = jax.tree.map(lambda *a: jnp.stack(a), v1, v2)
        fvl = vql_mod.prepare_fused(stacked, impl=impl)
        assert isinstance(fvl, vql_mod.FusedVQLinear)
        assert fvl.words.shape[0] == 2
        x = jax.random.normal(jax.random.PRNGKey(5), (2, 4, 256))
        y = cm.expert_matmul(x, fvl)
        W = jnp.stack([vql_mod.dequantize(v, jnp.float32).T
                       for v in (v1, v2)])  # (E, in, out)
        y_ref = jnp.einsum("ecd,edf->ecf", x, W)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_dispatch_counters(self, dispatch_counters):
        """The "vq" dispatch counts pin which path traced: fused_matmul
        bumps its impl; dequant_tree bumps "gather" per densified VQLinear
        leaf. The fixture zeroes the registry, so counts are absolute."""
        vql = self._quantized()
        fvl = vql_mod.prepare_fused(vql)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 256))
        vql_mod.fused_matmul(x, fvl, impl="xla")
        assert dispatch_counters()["vq"]["xla"] == 1
        vql_mod.fused_matmul(x, fvl, impl="pallas", interpret=True,
                             tile_n=64, tile_k=256)
        assert dispatch_counters()["vq"]["pallas"] == 1
        vql_mod.dequant_tree({"w": vql}, jnp.float32)
        assert dispatch_counters()["vq"]["gather"] == 1
        # leaf stamp is the default when no explicit impl is passed
        vql_mod.fused_matmul(x, vql_mod.prepare_fused(vql, impl="xla"))
        assert dispatch_counters()["vq"]["xla"] == 2

    def test_unaligned_rows_stay_gather(self):
        """Rows not packed on uint32 word boundaries (flat-packed leaf):
        prepare_fused must leave the leaf as VQLinear (gather path) rather
        than produce a layout the kernel cannot tile."""
        r, c, d, k = 4, 24, 2, 16  # nspans=12, lanes=8 -> unaligned
        code_bits = 4
        codes = jax.random.randint(jax.random.PRNGKey(1), (r, c // d), 0, k)
        # 48 codes / 8 lanes = 6 words: rows straddle word boundaries, so
        # the pack is flat (1, n_words) rather than per-row
        words = packing.pack(codes.reshape(-1), code_bits).reshape(1, -1)
        vql = vql_mod.VQLinear(
            words=words,
            codebooks=jax.random.randint(
                jax.random.PRNGKey(2), (2, 2, k, d), -127, 128
            ).astype(jnp.int8),
            cb_scale=jnp.full((2, 2), 0.05, jnp.float32),
            scale_sint=jnp.zeros((2, r, 1), jnp.int8),
            scale_a=jnp.zeros((2,), jnp.float32),
            scale_z=jnp.zeros((2,), jnp.float32),
            r=r, c=c, d=d, k=k, group_cols=12, rows_per_band=2)
        out = vql_mod.prepare_fused(vql)
        assert out is vql
        tree = vql_mod.prepare_fused_tree({"w": vql})
        assert isinstance(tree["w"], vql_mod.VQLinear)
        dense = vql_mod.dequant_tree(tree, jnp.float32)
        assert dense["w"].shape == (c, r)


class TestVQAssign:
    @pytest.mark.parametrize("n,d,k", [(256, 2, 16), (1024, 4, 64),
                                       (512, 1, 8), (2048, 2, 256)])
    def test_matches_oracle(self, n, d, k):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(ks[0], (n, d))
        hw = jnp.abs(jax.random.normal(ks[1], (n, d))) + 0.1
        C = jax.random.normal(ks[2], (k, d))
        got = ops.assign(x, hw, C, use_pallas=True, interpret=True, tile_n=256)
        want = ref.vq_assign_ref(x, hw, C)
        # ties are legal but measure-zero for continuous data
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_matches_core_codebook_assign(self):
        """Kernel == the core EM E-step used by Algorithm 1."""
        from repro.core import codebook as cb
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        x = jax.random.normal(ks[0], (512, 2))
        hw = jnp.abs(jax.random.normal(ks[1], (512, 2))) + 0.1
        C = jax.random.normal(ks[2], (16, 2))
        got = ops.assign(x, hw, C, use_pallas=True, interpret=True)
        want = cb.assign(x, hw, C)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestFlashAttentionKernel:
    @pytest.mark.parametrize(
        "B,S,H,KV,hd,bq,bk,causal",
        [
            (2, 256, 8, 4, 64, 64, 64, True),
            (2, 256, 8, 4, 64, 64, 64, False),
            (1, 128, 4, 4, 32, 32, 64, True),   # MHA, uneven blocks
            (2, 128, 8, 2, 64, 128, 32, True),  # G=4 GQA
        ],
    )
    def test_matches_plain_attention(self, B, S, H, KV, hd, bq, bk, causal):
        from repro.kernels.flash_attention import flash_attention_tpu
        from repro.models import attention
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, hd))
        k = jax.random.normal(ks[1], (B, S, KV, hd))
        v = jax.random.normal(ks[2], (B, S, KV, hd))
        o1 = flash_attention_tpu(q, k, v, causal=causal, block_q=bq,
                                 block_k=bk, interpret=True)
        if causal:
            msk = (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])[
                None, None, None]
        else:
            msk = jnp.ones((1, 1, 1, S, S), bool)
        o2 = attention._plain_attention(q, k, v, msk)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        from repro.kernels.flash_attention import flash_attention_tpu
        from repro.models import attention
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 128, 4, 32)).astype(dtype)
        k = jax.random.normal(ks[1], (1, 128, 2, 32)).astype(dtype)
        v = jax.random.normal(ks[2], (1, 128, 2, 32)).astype(dtype)
        o = flash_attention_tpu(q, k, v, causal=True, block_q=64,
                                block_k=64, interpret=True)
        assert o.dtype == dtype
        msk = (jnp.arange(128)[None, :] <= jnp.arange(128)[:, None])[
            None, None, None]
        o2 = attention._plain_attention(q, k, v, msk)
        tol = 2e-4 if dtype == jnp.float32 else 4e-2
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(o2, np.float32),
            rtol=tol, atol=tol)

    @pytest.mark.parametrize("Sq,Sk,off", [(64, 64, 32), (64, 192, 128),
                                           (1, 64, 63)])
    def test_q_offset_matches_xla_scan(self, Sq, Sk, off):
        """Causal masking at a nonzero static row offset: the kernel must
        match the XLA two-level scan's q_offset semantics (q row i is
        absolute position off + i; k spans [0, Sk))."""
        from repro.kernels.flash_attention import flash_attention_tpu
        from repro.models import attention
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (2, Sq, 4, 32))
        k = jax.random.normal(ks[1], (2, Sk, 2, 32))
        v = jax.random.normal(ks[2], (2, Sk, 2, 32))
        o1 = flash_attention_tpu(q, k, v, causal=True, q_offset=off,
                                 block_q=min(64, Sq), block_k=32,
                                 interpret=True)
        msk = (jnp.arange(Sk)[None, :]
               <= off + jnp.arange(Sq)[:, None])[None, None, None]
        o2 = attention._plain_attention(q, k, v, msk)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=2e-4, atol=2e-4)


class TestFlashDispatch:
    """Regression: a nonzero q_offset with an empty cache prefix
    (Sk == Sq, absolute-position masking) used to silently skip the
    Pallas path. The "flash" dispatch counters (obs/dispatch) pin which
    impl dispatched; the fixture zeroes them per test."""

    def test_q_offset_no_longer_skips_pallas(self, dispatch_counters):
        from repro.models import attention
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (1, 64, 4, 32))
        k = jax.random.normal(ks[1], (1, 64, 4, 32))
        v = jax.random.normal(ks[2], (1, 64, 4, 32))
        attention.set_flash_impl("pallas")
        try:
            o_pl = attention.flash_attention(q, k, v, causal=True,
                                             q_offset=16)
            after = dispatch_counters()["flash"]
            assert after["pallas"] == 1, \
                "pallas path was silently skipped"
            assert after["xla"] == 0
            attention.set_flash_impl("xla")
            o_xla = attention.flash_attention(q, k, v, causal=True,
                                              q_offset=16)
            assert dispatch_counters()["flash"]["xla"] == 1
        finally:
            attention.set_flash_impl("xla")
        np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_xla),
                                   rtol=2e-4, atol=2e-4)

    def test_traced_offset_falls_back_to_xla(self, dispatch_counters):
        """A *traced* q_offset can't parameterize the static kernel mask —
        dispatch must take the XLA scan, not crash."""
        from repro.models import attention
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        q = jax.random.normal(ks[0], (1, 64, 4, 32))
        k = jax.random.normal(ks[1], (1, 64, 4, 32))
        v = jax.random.normal(ks[2], (1, 64, 4, 32))
        attention.set_flash_impl("pallas")
        try:
            out = jax.jit(
                lambda off: attention.flash_attention(
                    q, k, v, causal=True, q_offset=off))(jnp.int32(16))
            after = dispatch_counters()["flash"]
            assert after["xla"] == 1
            assert after["pallas"] == 0
        finally:
            attention.set_flash_impl("xla")
        ref_o = attention.flash_attention(q, k, v, causal=True, q_offset=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_o),
                                   rtol=2e-4, atol=2e-4)
