"""Compile the serving kernels for one described TPU v5e chip.

The interpret-mode suites check the kernels' math; only the TPU compiler
checks that their block shapes tile, that their bodies lower to Mosaic and
that they fit the chip's scoped VMEM. Each case lowers one kernel at
qwen3-1.7b's serving widths (H=16, KV=8, hd=128, page_size=16; the paged
kernel at the benchmark cell's 64 slots of 64 pages over a 1792-block
pool; the 2.25bpv_2d layout of its three weight shapes) against a
``v5e:2x2`` topology description and compiles it for the first chip.
Nothing runs, so this needs no TPU; where the topology cannot be described
the fixture skips every case.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import packing
from repro.core.bpv import PAPER_SETTINGS
from repro.kernels import kv_quant as kvq

pytestmark = pytest.mark.kernels

H, KV, HD, PAGE = 16, 8, 128, 16
B, N_PAGES, N_BLOCKS = 64, 64, 1792       # the serving cell's pool


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no chip lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_tpu(one_chip):
    """Lower ``fn`` on shapes placed on the described chip and compile it,
    with the persistent compilation cache off (a described-chip compile
    can be written to it but never read back here)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_(fn, *shapes, **kw_shapes):
        place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,  # noqa: E731
                                               sharding=one_chip)
        compiled = jax.jit(fn).lower(
            *map(place, shapes),
            **{k: place(v) for k, v in kw_shapes.items()}).compile()
        assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel"
        return compiled

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _compile_paged(compile_tpu, bits, n_heads):
    from repro.kernels.paged_attention import paged_attention_tpu

    nb = N_BLOCKS
    if bits == 16:
        pool = _s((nb, PAGE, KV, HD), jnp.float32)
    else:
        cols = HD if bits == 8 else kvq.storage_cols(HD, bits)
        pool = _s((nb, PAGE, KV, cols), jnp.int8)
    kw = {}
    if bits != 16:
        kw["k_scale"] = kw["v_scale"] = _s((nb, PAGE, KV), jnp.float32)
    if bits == kvq.VQ_BITS:
        kw["k_codebook"] = kw["v_codebook"] = _s((KV, kvq.VQ_K, kvq.VQ_D),
                                                 jnp.float32)
    compile_tpu(lambda *a, **k: paged_attention_tpu(*a, **k),
                _s((B, n_heads, HD), jnp.bfloat16), pool, pool,
                _s((B, N_PAGES), jnp.int32), _s((B,), jnp.int32), **kw)


@pytest.mark.parametrize("bits", [16, 8, 4, kvq.VQ_BITS])
def test_paged_attention_compiles(compile_tpu, bits):
    """Decode attention over the engine's pool: bf16 queries; an f32 pool
    (what the engine allocates) or int8 code pages + f32 scales. The
    kernel derives its pages per block from these shapes, and the block
    must fit the chip's scoped VMEM."""
    _compile_paged(compile_tpu, bits, H)


def test_paged_attention_block_of_f32_pool():
    """An f32 pool at qwen3-1.7b's widths: 16 pages per grid step, 1 MiB
    per K block."""
    from repro.kernels.paged_attention import pages_per_block

    assert pages_per_block(H, HD, PAGE, KV, N_PAGES, jnp.float32, 16) == 16


@pytest.mark.parametrize("bits", [16, kvq.VQ_BITS])
def test_paged_attention_compiles_yi34b_heads(compile_tpu, bits):
    """Yi-34B's 56 query heads over 8 kv heads: the widest score rows."""
    _compile_paged(compile_tpu, bits, 56)


@pytest.mark.parametrize("n,k", [(6144, 2048), (2048, 6144), (2048, 2048)])
def test_vq_dequant_matmul_compiles(compile_tpu, n, k):
    """Decode-shaped (M=8) fused VQ matmul on each qwen3-1.7b weight shape
    under the 2.25bpv_2d layout (d=2, 16-entry codebooks, 256 x 4 groups)."""
    from repro.kernels.vq_dequant_matmul import vq_dequant_matmul

    cfg = PAPER_SETTINGS["2.25bpv_2d"]
    cg, rg = cfg.group_cols, cfg.group_size // cfg.group_cols
    code_bits = max(1, (cfg.k - 1).bit_length())
    cbits = packing.container_bits(code_bits)
    words = _s((n, k // cfg.d * cbits // 32), jnp.uint32)
    books = _s((k // cg, n // rg, cfg.k, cfg.d), jnp.float32)
    compile_tpu(
        lambda x, w, c: vq_dequant_matmul(
            x, w, c, d=cfg.d, k_c=cfg.k,
            container_bits=cbits, rows_per_band=rg, group_cols=cg),
        _s((8, k), jnp.float32), words, books)


def test_flash_attention_compiles(compile_tpu):
    """Prefill flash attention, 512 tokens x 16 heads x hd 128, bf16."""
    from repro.kernels.flash_attention import flash_attention_tpu

    compile_tpu(lambda q, k, v: flash_attention_tpu(q, k, v, causal=True),
                _s((1, 512, H, HD), jnp.bfloat16),
                _s((1, 512, KV, HD), jnp.bfloat16),
                _s((1, 512, KV, HD), jnp.bfloat16))
