"""The required-work functions (the kernels' and the decoder's model
FLOPs) against hand counts at qwen3-1.7b's and yi-34b's shapes, the peaks
table, and the traffic generator's fixed work."""
import numpy as np
import pytest

from bench import manifest, spec as bspec, traffic
from bench.arch import decoder
from bench.work import Work, paged_attention, peaks, roofline
from bench.work import vq_dequant_matmul as vq

CONFIGS = manifest.ROOT / "bench" / "configs"


def load(name):
    return decoder.spec(bspec.load(CONFIGS / f"{name}.json"))


def test_vq_matmul_qwen3_wq():
    fmt = load("qwen3-1.7b-vq").vq
    w = vq.work(16, 2048, 2048, fmt)
    assert w.flops == 2 * 16 * 2048 * 2048
    # 4-bit codes per 2 weights; 8 x 512 codebooks of 16x2 int8 + f32
    # scale; bf16 x and y
    assert w.bytes == 2048 * 1024 // 2 + 8 * 512 * 36 + 2 * 16 * 4096


def test_vq_matmul_yi_w_out():
    fmt = load("yi-34b-vq").vq
    w = vq.work(16, 7168, 20480, fmt)
    assert w.flops == 4_697_620_480
    assert w.bytes == 36_700_160 + 80 * 1792 * 36 + 884_736


def test_paged_attention_counts_live_rows_only():
    w = paged_attention.work([100, 300], n_heads=16, n_kv=8, hd=128)
    assert w.flops == 4 * 16 * 128 * 400
    assert w.bytes == 2 * 400 * 8 * 128 * 4 + 2 * 2 * 16 * 128 * 2
    assert paged_attention.work([], 16, 8, 128) == Work(0.0, 0.0)


def test_model_flops_qwen3():
    s = load("qwen3-1.7b-vq")
    assert decoder.layer_flops_per_token(s) == 2 * 28 * 50_331_648
    assert decoder.head_flops(s) == 2 * 2048 * 151_936
    assert decoder.attention_flops(s, 1000) == 4 * 28 * 16 * 128 * 1000
    assert decoder.decode_flops(s, 2, 1000, 1) == (
        2 * 2 * 28 * 50_331_648 + 4 * 28 * 16 * 128 * 1000
        + 2 * 2048 * 151_936)


def test_model_flops_yi():
    s = load("yi-34b-vq")
    assert s.n_layers == 30 and s.hd == 128 and not s.tied
    assert decoder.layer_flops_per_token(s) == 2 * 30 * 557_842_432
    assert decoder.head_flops(s) == 2 * 7168 * 64_000


def test_roofline_bound_and_peaks():
    fmt = load("qwen3-1.7b-vq").vq
    t, bound = roofline(vq.work(16, 2048, 2048, fmt), "TPU v5 lite")
    assert bound == "memory"
    assert t == pytest.approx(1_327_104 / 819e9)
    t, bound = roofline(Work(197e12, 1.0), "TPU v5 lite")
    assert (t, bound) == (1.0, "compute")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks("TPU v9000")
    with pytest.raises(KeyError):
        roofline(Work(1.0, 1.0), "cpu")


def test_every_seed_gets_the_same_work():
    mix = traffic.load("chat")
    a = traffic.schedule(mix, 1, 30.0, 1000)
    b = traffic.schedule(mix, 2**40 + 3, 30.0, 1000)
    assert len(a) == len(b) == int(np.ceil(mix["rate_per_s"] * 30))
    for key in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert sorted(map(key, a)) == sorted(map(key, b))
        assert list(map(key, a)) != list(map(key, b))
    gaps = lambda s: sorted(np.round(np.diff([r.due_s for r in s]), 9))
    assert abs(sum(np.diff([r.due_s for r in a]))
               - sum(np.diff([r.due_s for r in b]))) < 1.0
    p = [len(r.prompt) for r in a]
    assert min(p) >= mix["prompt"]["min"] and max(p) <= mix["prompt"]["max"]
    assert gaps(a)[0] >= 0


def test_gamma_gaps_have_the_asked_mean_and_spread():
    g = traffic._gamma_gaps(4.0, 1.0, 4000)
    assert np.mean(g) == pytest.approx(0.25, rel=0.02)
    assert np.std(g) / np.mean(g) == pytest.approx(1.0, rel=0.05)
    g = traffic._gamma_gaps(4.0, 0.5, 4000)
    assert np.std(g) / np.mean(g) == pytest.approx(0.5, rel=0.05)


def test_gptvq_target_flops_qwen3_w_out():
    from bench.work import gptvq

    # w_out of qwen3-1.7b: r=2048, c=6144; 2.25bpv_2d at 50 / 25 iterations
    f = gptvq.target_flops(2048, 6144, k=16, em_iters=50, update_iters=25)
    assert f["inverse"] == 6144 ** 3
    assert f["em_init"] == 50 * 2048 * 6144 * 50
    assert f["column_sweep"] == 2048 * 6144 ** 2 + 3 * 2048 * 6144 * 16
    assert f["codebook_update"] == 25 * (2 * 2048 * 6144 ** 2 + 2048 * 6144)
    # the paper's calibration set: 128 sequences of 2048 tokens
    assert gptvq.hessian_flops(128 * 2048, 6144) == 2 * 262144 * 6144 ** 2


@pytest.mark.parametrize("pad", [True, False])
def test_chunk_widths_follow_the_scheduler(pad):
    from repro.serve.paged_cache import BlockAllocator
    from repro.serve.scheduler import Scheduler

    from bench import serve

    sched = Scheduler(max_batch=2, max_len=1024, page_size=16,
                      allocator=BlockAllocator(8), prefill_chunk=64,
                      pad_prefill=pad)
    got = serve.chunk_widths(sched, [200, 64, 3, 70, 130])
    if pad:     # whole chunks, each tail padded to its power of two
        assert got == {64: 64, 4: 3, 8: 70, 2: 130}
    else:       # tails cut into powers of two
        assert got == {2: 3, 1: 3, 64: 64, 4: 70, 8: 200}
    mix = traffic.load("chat")
    prompts, _ = traffic.lengths(mix, 51.0)
    widths = serve.chunk_widths(sched, prompts)
    assert all(min(prompts) <= p <= max(prompts) for p in widths.values())


def test_stratified_order_deals_one_value_per_band():
    """Each run of ``max_batch`` requests holds one prompt length and one
    output length from each band of the sorted multiset."""
    mix = dict(traffic.load("chat"), rate_per_s=2.0)
    block = mix["engine"]["max_batch"]
    prompts, outputs = traffic.lengths(mix, 96.0)     # three whole runs
    n_blocks = len(prompts) // block
    assert n_blocks * block == len(prompts) and n_blocks == 3
    for seed in (5, 2**33 + 7):
        arr = traffic.schedule(mix, seed, 96.0, 1000)
        for got, values in (([len(a.prompt) for a in arr], prompts),
                            ([a.max_new_tokens for a in arr], outputs)):
            bands = [sorted(np.sort(values)[j::n_blocks].tolist())
                     for j in range(n_blocks)]
            runs = sorted(sorted(got[i:i + block])
                          for i in range(0, len(got), block))
            assert runs == sorted(bands)
