"""What the program labels on the trace (``bench/labels.py``): device time
under a named scope, idle time under the engine's ``serve.*`` spans, and
the ``engine_host_gap_ms`` reader; and the per-layer readers pinned on the
short chip trace recorded before the engine's spans went on the trace's
clock (``bench/testdata/chat_trace.json.gz``)."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import labels, metrics, spec as bspec, trace
from bench.arch import decoder
from bench.serve import Tick

TESTDATA = Path(trace.__file__).resolve().parent / "testdata"
ROOT = TESTDATA.parent.parent

D = "jit(decode)/while/body/closed_call/checkpoint/"
SCOPES = {"fusion.1": D + "layer_cache_read/squeeze",
          "fusion.2": D + "layer_cache_write/dynamic_update_slice",
          "vq_dequant_matmul.3": D + "mlp/vq_dequant_matmul",
          "paged_attention_tpu.1": D + "attention/paged_attention_tpu"}

# times in ns: two ticks of the engine inside the harness's steps, and a
# chunk program whose operation shares a name with one of decode's
HAND = {
    "host": [["bench.step", 0, 100], ["serve.tick", 2, 96],
             ["serve.admit", 2, 6], ["serve.decode_tick", 10, 85],
             ["serve.decode_tick/device", 12, 70],
             ["PjitFunction(decode)", 74, 4],
             ["serve.decode_tick/emit", 82, 12],
             ["bench.wait_for_arrival", 100, 50], ["bench.step", 150, 50],
             ["serve.tick", 152, 40], ["serve.decode_tick", 153, 38],
             ["serve.decode_tick/device", 155, 35]],
    "modules": [["jit_decode(7)", 15, 60], ["jit_chunk(3)", 80, 10],
                ["jit_decode(7)", 158, 30]],
    "ops": [["fusion.1", 15, 10], ["vq_dequant_matmul.3", 25, 20],
            ["fusion.2", 45, 10], ["paged_attention_tpu.1", 55, 20],
            ["fusion.1", 80, 10], ["fusion.1", 158, 10],
            ["fusion.2", 168, 20]],
}


def test_op_scopes_from_hlo_text():
    text = """HloModule jit_decode, entry_computation_layout={()}
  %p = f32[4]{0} parameter(0)
  %dynamic-slice_bitcast_fusion.6 = f32[65,16]{1,0} fusion(f32[2,65,16]{2,1,0} %p, s32[] %i), kind=kLoop, calls=%fc.6, metadata={op_name="jit(decode)/while/body/layer_cache_read/squeeze" source_file="t.py" source_line=3}
  ROOT %bitcast_dynamic-update-slice_fusion = f32[2,65,16]{2,1,0} fusion(%p), kind=kLoop, calls=%fc.7, metadata={op_name="jit(decode)/while/body/layer_cache_write/dynamic_update_slice"}
"""
    assert labels.op_scopes(text) == {
        "dynamic-slice_bitcast_fusion.6":
            "jit(decode)/while/body/layer_cache_read/squeeze",
        "bitcast_dynamic-update-slice_fusion":
            "jit(decode)/while/body/layer_cache_write/dynamic_update_slice"}


def test_scope_seconds_inside_runs_of_a_program():
    s = trace.Summary(HAND)
    # the chunk's fusion.1 at 80 is not decode's, whatever its name
    assert labels.scope_seconds(s, SCOPES, "layer_cache_read", "decode") \
        == pytest.approx(20e-9)
    assert labels.scope_seconds(s, SCOPES, "layer_cache_write", "decode") \
        == pytest.approx(30e-9)
    assert labels.scope_seconds(s, SCOPES, "attention", "decode") \
        == pytest.approx(20e-9)
    # a scope is a whole segment of the path, not a prefix of one
    assert labels.scope_seconds(s, SCOPES, "layer_cache", "decode") == 0
    assert labels.scope_seconds(s, SCOPES, "mlp", "chunk") == 0


def test_idle_in_engine_spans():
    s = trace.Summary(HAND)
    # busy [15,75) [80,90) [158,188); gaps [0,15) [75,80) [90,158)
    # [188,200), each put to the innermost serve.* span open at its middle
    assert labels.idle_gaps(s) == [(0, 15), (75, 5), (90, 68), (188, 12)]
    idle, ticks = labels.idle_in_spans(s, "serve.")
    # 7: admission; 77: the device span (the runtime's own event is
    # innermost there, but it is not the engine's); 124: waiting for an
    # arrival and 194: the harness's step after the tick closed, left out
    assert idle == {"serve.admit": pytest.approx(15e-9),
                    "serve.decode_tick/device": pytest.approx(5e-9)}
    assert ticks == 2
    harness, _ = labels.idle_in_spans(s, "bench.")
    assert harness == {"bench.step": pytest.approx(32e-9),
                       "bench.wait_for_arrival": pytest.approx(68e-9)}
    # the breakdown labels gaps by the innermost span of any kind
    assert dict(s.breakdown()["idle_gaps"])["PjitFunction(decode)"] == \
        pytest.approx(5e-9)


def _ctx(summary, **window):
    spec = decoder.spec(
        bspec.load(ROOT / "bench" / "configs" / "qwen3-1.7b-vq.json"))
    return metrics.Context(arch=decoder, spec=spec,
                           window=SimpleNamespace(**window),
                           trace=summary, device_kind="TPU v5 lite",
                           mix={"engine": {"max_batch": 16}})


def test_engine_host_gap_reader():
    read = metrics.reader("engine_host_gap_ms")
    assert read(_ctx(trace.Summary(HAND))) == pytest.approx(20e-9 * 1e3 / 2)
    # a trace without the engine's ticks (as before they were annotated)
    # reads nothing
    no_ticks = dict(HAND, host=[e for e in HAND["host"]
                                if e[0] != "serve.tick"])
    assert read(_ctx(trace.Summary(no_ticks))) is None
    assert read(_ctx(trace.Summary(
        {"host": [], "modules": [], "ops": []}))) is None


@pytest.mark.parametrize("name,value", [
    ("decode_batch_mean", 15.5),
    ("decode_step_ms", 77.65213299999999),
    ("vq_matmul_roofline", 1.160049852940892),
    ("paged_attn_roofline", 7.276760707493929),
    ("mfu.decode", 0.36412682225299403),
    ("engine_host_gap_ms", None),
])
def test_readers_on_the_older_recorded_trace(name, value):
    """The five older readers on the recorded 16-slot trace of the chat cell,
    with four decode ticks of 16 slots made up here; the engine's ticks
    were not on that trace, so the new reader finds nothing."""
    ticks = [Tick(decode_context=[100 + 10 * j + i for j in range(16)],
                  prefill_context=[], prompts_done=0) for i in range(4)]
    ctx = _ctx(trace.read(TESTDATA / "chat_trace.json.gz"), ticks=ticks,
               registry_delta={"serve.decode_batch": {"count": 4,
                                                      "sum": 62.0}})
    got = metrics.reader(name)(ctx)
    if value is None:
        assert got is None
    else:
        assert got == pytest.approx(value, rel=1e-12)


def _scoped():
    import gzip
    import json

    with gzip.open(TESTDATA / "chat_trace_scoped.json.gz", "rt") as f:
        ex = json.load(f)
    return trace.Summary(ex), ex["op_scopes"]["decode"]


def test_recorded_pool_copy_is_under_its_scopes():
    """A tick with five prefill chunks and four decode steps of the chat
    cell (64 slots), recorded on one TPU v5e chip with the Python tracer
    off; the decode program's op scopes come from its optimized HLO (op
    names cut to their HLO names, runtime host events under 20 us
    dropped). Every slice and write-back fusion of the pool in the decode
    programs is under ``layer_cache_read`` or ``layer_cache_write``, and
    the two scopes hold all of their time and little else."""
    s, scopes = _scoped()
    assert (s.program_count("decode"), s.program_count("chunk")) == (4, 5)
    runs = sorted((a, a + d) for _, a, d in s._programs("decode"))
    kinds = {"dynamic-slice_bitcast_fusion": "layer_cache_read",
             "bitcast_dynamic-update-slice_fusion": "layer_cache_write"}
    seen = {v: 0 for v in kinds.values()}
    for name, a, d in s._leaves():
        scope = kinds.get(trace.op_kind(name))
        if scope and any(x <= a < y for x, y in runs):
            assert scope in scopes[name].split("/"), (name, scopes[name])
            seen[scope] += d
    # the scopes also hold each layer's page-table row: microseconds
    for scope, ns in seen.items():
        got = labels.scope_seconds(s, scopes, scope, "decode")
        assert ns * 1e-9 <= got < ns * 1e-9 * 1.001
    copy_ms = 1e3 * sum(labels.scope_seconds(s, scopes, k, "decode")
                        for k in kinds.values()) / 4
    step_ms = 1e3 * s.program_seconds("decode") / 4
    assert copy_ms == pytest.approx(40.10296975000001, rel=1e-12)
    assert step_ms == pytest.approx(136.67706025, rel=1e-12)


def test_recorded_idle_gaps_are_labelled():
    """On the same trace, the engine's idle time per tick, and every idle
    gap of the device under an engine or harness span or under no host
    event at all (the harness's own loop between its steps); no gap is
    put to a Python frame."""
    s, _ = _scoped()
    got = metrics.reader("engine_host_gap_ms")(_ctx(s))
    assert got == pytest.approx(4.00467475, rel=1e-9)
    idle, ticks = labels.idle_in_spans(s, "serve.")
    assert ticks == 4
    assert set(idle) <= {"serve.tick", "serve.admit", "serve.prefill",
                         "serve.prompt_sample", "serve.decode_tick",
                         "serve.decode_tick/host_prep",
                         "serve.decode_tick/device",
                         "serve.decode_tick/emit"}
    for start, length in labels.idle_gaps(s):
        t = start + length // 2
        open_ = [n for n, a, d in s.host if a <= t <= a + d]
        assert not open_ or any(n.startswith(("serve.", "bench."))
                                for n in open_), open_
    labels_ = [n for n, _ in s.breakdown(top=100)["idle_gaps"]]
    assert labels_ and not [n for n in labels_ if n.startswith("$")]
