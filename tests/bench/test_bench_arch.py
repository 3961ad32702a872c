"""The architecture seam (``bench/arch/``): every step of a cell that
depends on the model's architecture comes from the one module that serves
the configuration's published ``model_type``.

* A throwaway architecture (``toy_relu2.py`` beside this file: the
  program's non-gated relu^2 feed-forward, no ``w_gate``), added to a copy
  of the benchmark as new files and manifest entries, runs correct; the
  same run with a fault planted in its reference alone (relu in place of
  relu^2) comes out not correct. So a cell's payload, program and
  reference all come from its own architecture file, and no harness file
  changes.
* The readers that count the model's work take their counts from the
  architecture module.
* A ``model_type`` that no module serves, or two serve, stops a run before
  any device work, naming it; every configuration of the benchmark
  resolves to the dense decoder.
* The chat configuration's seeded payload and reference read as pinned
  below at test widths, so a change to their draws or operations shows.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import toy_relu2
from bench import arch, metrics, spec as bspec, trace
from bench.arch import decoder
from bench.serve import Tick

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
CONFIGS = ROOT / "bench" / "configs"
CHAT = CONFIGS / "qwen3-1.7b-vq.json"

DRIVE = """
import json, sys
sys.argv = ["run"]
import jax
from bench import run
run.require_tpu = lambda chips: jax.devices()
run.enable_compile_cache = lambda: None
sys.exit(run.main(["--workload", "toy.toy_short", "--seed", str(2**34 + 5),
                   "--seconds", "2.0", "--trace", "1"]))
"""
RELU2 = "jnp.square(jax.nn.relu("


def digest(root: Path) -> dict:
    return {str(p.relative_to(root)):
            hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def copy_benchmark(tmp_path: Path) -> dict:
    """``bench/`` and ``BENCHMARK.json`` under ``tmp_path``; the digest of
    every file copied."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return digest(tmp_path)


def add_toy_cell(tmp_path: Path, source: str):
    """The toy architecture, its configuration, mix and limits as new
    files, and the cell's entries in the manifest."""
    b = tmp_path / "bench"
    (b / "arch" / "toy_relu2.py").write_text(source)
    chat = bspec.load(CHAT)
    (b / "configs" / "toy.json").write_text(json.dumps({
        "name": "toy", "model_type": "toy_relu2", "hidden_size": 256,
        "intermediate_size": 512, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
        "vocab_size": 300, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
        "max_position_embeddings": 512, "reduced": [],
        "assumed": {"vocab_pad_multiple": 256}, "weights": chat["weights"],
        "engine": dict(chat["engine"], vq_matmul_impl="xla",
                       paged_attn_impl="xla", prefill_chunk=16)}))
    (b / "traffic" / "toy_short.json").write_text(json.dumps({
        "kind": "open_loop", "rate_per_s": 12.0, "arrival_cv": 1.0,
        "prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 30},
        "output": {"median": 8, "sigma": 0.3, "min": 5, "max": 12},
        "engine": {"max_batch": 4, "max_len": 64},
        "trace_window": {"start_s": 0.3, "seconds": 1.0}}))
    (b / "limits" / "toy.toy_short.json").write_text(json.dumps({
        "check": {"min_tokens": 60, "max_requests": 8},
        "numbers": {"served_logit_gap": {"limit": LIMIT}}}))
    m = json.loads((tmp_path / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "toy", "source": "test",
                         "file": "bench/configs/toy.json", "reduced": [],
                         "why": "test"})
    m["workloads"].append({"name": "toy.toy_short", "config": "toy",
                           "traffic": "toy_short", "chips": 1,
                           "why": "test"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "workloads" in e:
            e["workloads"].append("toy.toy_short")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    return m


# the chat cell's limit; at this size on the CPU sound runs read 0-0.0069
# and runs with the planted fault 0.954-1.520 (8 seeds each)
LIMIT = 0.2


@pytest.mark.parametrize("fault", [False, True],
                         ids=["sound", "relu_in_reference"])
def test_new_architecture_from_files_only(tmp_path, fault):
    before = copy_benchmark(tmp_path)
    del before["BENCHMARK.json"]
    source = (HERE / "toy_relu2.py").read_text()
    assert source.count(RELU2) == 1
    if fault:
        source = source.replace(RELU2, "(jax.nn.relu(")
    m = add_toy_cell(tmp_path, source)

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", DRIVE], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    gap = res["check"]["served_logit_gap"]
    assert res["correct"] is (not fault)
    assert (gap["value"] > gap["limit"]) is fault
    declared = {e["name"] for e in m["per_layer"]}
    assert "decode_batch_mean" in res["metrics"]
    assert set(res["metrics"]) <= declared
    after = digest(tmp_path)
    assert {k: after[k] for k in before} == before


def _ctx(arch_mod, spec):
    ticks = [Tick(decode_context=[100 + 10 * j + i for j in range(16)],
                  prefill_context=[], prompts_done=0) for i in range(4)]
    return metrics.Context(
        arch=arch_mod, spec=spec,
        window=SimpleNamespace(ticks=ticks, registry_delta={}),
        trace=trace.read(ROOT / "bench" / "testdata" / "chat_trace.json.gz"),
        device_kind="TPU v5 lite", mix={"engine": {"max_batch": 16}})


def test_readers_take_their_counts_from_the_arch():
    """On the recorded chat trace, the readers that count the model's
    work follow the architecture module: the toy at the chat's widths has
    no ``w_gate``, so its VQ matmuls and FLOPs are fewer by that matrix's,
    and its attention is the decoder's; a module whose routing decides
    the calls, and one with no paged attention, make them read nothing."""
    cfg = bspec.load(CHAT)
    dense = decoder.spec(cfg)
    toy = toy_relu2.spec(cfg)
    read = {n: metrics.reader(n) for n in ("vq_matmul_roofline",
                                           "paged_attn_roofline",
                                           "mfu.decode")}
    dense_ctx, toy_ctx = _ctx(decoder, dense), _ctx(toy_relu2, toy)
    F, D, M = dense.d_ff, dense.d, 16
    gate = vq_work(M, F, D, dense)
    calls = decoder.decode_vq_matmuls(dense, M)
    assert len(calls) == 7 * dense.n_layers
    share = 1 - dense.n_layers * gate / sum(vq_work(*c, dense)
                                            for c in calls)
    assert read["vq_matmul_roofline"](toy_ctx) == pytest.approx(
        read["vq_matmul_roofline"](dense_ctx) * share, rel=1e-12)
    assert read["paged_attn_roofline"](toy_ctx) == pytest.approx(
        read["paged_attn_roofline"](dense_ctx), rel=1e-12)
    n, rows = 64, sum(sum(t.decode_context) for t in toy_ctx.traced_ticks)
    ratio = (toy_relu2.decode_flops(toy, n, rows, n)
             / decoder.decode_flops(dense, n, rows, n))
    assert 0 < ratio < 1
    assert read["mfu.decode"](toy_ctx) == pytest.approx(
        read["mfu.decode"](dense_ctx) * ratio, rel=1e-12)
    routed = SimpleNamespace(decode_vq_matmuls=lambda spec, batch: None,
                             paged_attention_layers=lambda spec: (0, 0, 0, 0))
    assert read["vq_matmul_roofline"](_ctx(routed, dense)) is None
    assert read["paged_attn_roofline"](_ctx(routed, dense)) is None


def vq_work(M, r, c, spec):
    from bench.work import roofline
    from bench.work import vq_dequant_matmul as vq

    return roofline(vq.work(M, r, c, spec.vq), "TPU v5 lite")[0]


def test_unknown_model_type_stops_before_any_device_work(tmp_path):
    """``bench/run.py`` on the CPU: a run that looked for its chip first
    would stop with "needs a TPU"; this one stops at the configuration."""
    copy_benchmark(tmp_path)
    cfg = dict(bspec.load(CHAT), name="mystery", model_type="mystery_lm")
    (tmp_path / "bench" / "configs" / "mystery.json").write_text(
        json.dumps(cfg))
    m = json.loads((tmp_path / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "mystery", "source": "test",
                         "file": "bench/configs/mystery.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "mystery.chat", "config": "mystery",
                           "traffic": "chat", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mystery.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "'mystery_lm'" in p.stderr and "'qwen3'" in p.stderr
    assert "needs a TPU" not in p.stderr


@pytest.mark.parametrize("claims", [0, 2])
def test_a_model_type_needs_exactly_one_module(monkeypatch, claims):
    twin = SimpleNamespace(__name__="bench.arch.twin", MODEL_TYPES=("qwen3",))
    mods = [decoder, twin] if claims == 2 else [decoder]
    monkeypatch.setattr(arch, "modules", lambda: mods)
    model_type = "qwen3" if claims else "mystery_lm"
    with pytest.raises(SystemExit, match=repr(model_type)) as e:
        arch.load({"name": "x", "model_type": model_type})
    assert ("bench.arch.twin" in str(e.value)) is (claims == 2)


@pytest.mark.parametrize("config", sorted(p.name for p in
                                          CONFIGS.glob("*.json")))
def test_every_configuration_resolves_to_the_decoder(config):
    cfg = bspec.load(CONFIGS / config)
    assert arch.load(cfg) is decoder
    assert decoder.spec(cfg).name == cfg["name"]


# The chat configuration at the test widths of ``small_spec`` and one
# seed: a digest of every array of the seeded payload (path, type, shape,
# bytes), and the reference's logits at five positions of two sequences
# for four tokens, with each row's norm over the published vocabulary.
PINNED_SEED = 2**33 + 21
PINNED_PAYLOAD = \
    "f5cbd51453070feebd61cef6e4478171e9db7a1760126b21a6dabd38b292be46"
PINNED_TOKENS = [0, 7, 150, 299]
PINNED_LOGITS = {
    "f32": [
        [0.0566771924495697, 0.6665133237838745, -0.5862399339675903,
         -0.5599995255470276, 5.405642348405831],
        [-0.036623064428567886, 0.025843853130936623, -0.06167367473244667,
         -0.3508166968822479, 4.900176943597276],
        [0.05864613130688667, -0.19670315086841583, 0.06651762872934341,
         -0.18856124579906464, 5.394852972568721],
        [0.21916793286800385, -0.38542255759239197, -0.24064207077026367,
         0.25914740562438965, 5.68146824433078],
        [0.35527488589286804, -0.3014717698097229, -0.4752901792526245,
         -0.005318022333085537, 5.44817549327475]],
    "fp8": [
        [0.03872659057378769, 0.6646026372909546, -0.5605283379554749,
         -0.5393695831298828, 5.402315365713403],
        [-0.023572560399770737, 0.010472486726939678, -0.03661404922604561,
         -0.36552155017852783, 4.887028933679159],
        [0.04982340708374977, -0.1913590133190155, -0.039583757519721985,
         -0.1373031586408615, 5.3342710443244385],
        [0.270707368850708, -0.40034881234169006, -0.17923060059547424,
         0.22935646772384644, 5.687647385133096],
        [0.3266541361808777, -0.29647478461265564, -0.478356271982193,
         -0.016756366938352585, 5.415163551755345]],
}


@pytest.mark.parametrize("what", ["payload", "f32", "fp8"])
def test_chat_configuration_reads_as_pinned(small_spec, what):
    spec = small_spec()
    raw = decoder.raw_payload(spec, PINNED_SEED)
    if what == "payload":
        h = hashlib.sha256()
        leaves = jax.tree_util.tree_flatten_with_path(raw)[0]
        for path, leaf in sorted(leaves, key=lambda kv:
                                 jax.tree_util.keystr(kv[0])):
            a = np.asarray(leaf)
            h.update(f"{jax.tree_util.keystr(path)} {a.dtype} "
                     f"{a.shape}".encode())
            h.update(a.tobytes())
        assert h.hexdigest() == PINNED_PAYLOAD
        return
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, spec.vocab, n).astype(np.int32) for n in (40, 23)]
    pos = [np.array([0, 13, 39]), np.array([5, 22])]
    rows = np.concatenate(decoder.logits_at(raw, spec, seqs, pos,
                                            precision=what, pad_to=16))
    got = [[float(r[t]) for t in PINNED_TOKENS]
           + [float(np.sqrt((r[:spec.vocab].astype(np.float64) ** 2).sum()))]
           for r in rows]
    np.testing.assert_allclose(got, PINNED_LOGITS[what], rtol=1e-6, atol=0)
