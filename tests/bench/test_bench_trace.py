"""The trace reduction: busy union, programs, kernels inside programs, idle
gaps labelled by the host span open at them, on a hand-made extract and
on a short trace recorded on the chip (``bench/testdata/``)."""
from pathlib import Path

import pytest

from bench import trace

RECORDED = Path(trace.__file__).resolve().parent / "testdata" / \
    "chat_trace.json.gz"

# times in ns; the harness's host spans open and close the window
HAND = {
    "host": [["bench.step", 0, 100], ["decode_tick/device", 10, 80],
             ["bench.wait_for_arrival", 100, 50], ["bench.step", 150, 50]],
    "modules": [["jit_decode(7)", 10, 40], ["jit_chunk(3)", 60, 20],
                ["jit_decode(7)", 160, 30]],
    "ops": [["fusion.1", 10, 10], ["vq_dequant_matmul", 20, 25],
            ["paged_attention", 45, 5], ["vq_dequant_matmul", 60, 20],
            ["fusion.2", 160, 30], ["late", 195, 20]],
}


def test_hand_made_extract():
    s = trace.Summary(HAND)
    assert (s.t0, s.t1) == (0, 200)
    assert s.window_s == pytest.approx(200e-9)
    # [10, 50) + [60, 80) + [160, 190) + [195, 200) (cut at the close)
    assert s.busy_s == pytest.approx(95e-9)
    assert s.program_count("decode") == 2
    assert s.program_seconds("decode") == pytest.approx(70e-9)
    assert s.program_count("chunk") == 1
    # a kernel's time counts only inside runs of the program asked for
    assert s.kernel_seconds("vq_dequant_matmul", "decode") == \
        pytest.approx(25e-9)
    assert s.kernel_seconds("vq_dequant_matmul") == pytest.approx(45e-9)
    b = s.breakdown()
    assert b["device_ops"][0] == ["vq_dequant_matmul", pytest.approx(45e-9)]
    idle = dict(b["idle_gaps"])
    # gaps [0,10) and [190,195) in a step, [50,60) in the device span,
    # [80,160) labelled at its middle, waiting for an arrival
    assert idle == {"decode_tick/device": pytest.approx(10e-9),
                    "bench.step": pytest.approx(15e-9),
                    "bench.wait_for_arrival": pytest.approx(80e-9)}
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)


def test_empty_trace_reads_nothing():
    s = trace.Summary({"host": [], "modules": [], "ops": []})
    assert s.busy_s == 0 and s.program_count("decode") == 0
    assert s.breakdown() == {"device_ops": [], "idle_gaps": []}


def test_recorded_chip_trace():
    """Four decode steps and a prefill chunk of qwen3-1.7b-vq.chat, recorded
    on one TPU v5e chip (op names cut to their HLO names, host spans
    under 20 us dropped)."""
    s = trace.read(RECORDED)
    # busy by a second computation: a sweep over interval end points
    ends = sorted([(max(a, s.t0), 1) for _, a, d in s.ops if a < s.t1]
                  + [(min(a + d, s.t1), -1) for _, a, d in s.ops
                     if a < s.t1])
    depth, busy, last = 0, 0, None
    for t, step in ends:
        if depth > 0:
            busy += t - last
        depth, last = depth + step, t
    assert s.busy_s == pytest.approx(busy * 1e-9, rel=1e-12)
    assert 0.9 * s.window_s < s.busy_s < s.window_s
    assert (s.program_count("decode"), s.program_count("chunk")) == (4, 1)
    # the kernels of a decode step, inside its programs and inside it
    vq = s.kernel_seconds("vq_dequant_matmul", "decode")
    pa = s.kernel_seconds("paged_attention_tpu", "decode")
    assert 0 < pa < vq < s.program_seconds("decode")
    assert vq < s.kernel_seconds("vq_dequant_matmul")
    b = s.breakdown()
    kinds = [k for k, _ in b["device_ops"]]
    assert kinds[:2] == ["vq_dequant_matmul", "paged_attention_tpu"]
    assert "while" not in kinds          # loops span their bodies
    assert sum(v for _, v in b["device_ops"]) <= s.busy_s + 1e-9
    assert len(b["idle_gaps"]) <= 10
    assert sum(v for _, v in b["idle_gaps"]) <= s.window_s - s.busy_s + 1e-9
