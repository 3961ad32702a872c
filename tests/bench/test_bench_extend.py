"""A later change adds a configuration, a traffic mix and a per-layer
metric as new files plus manifest entries, and edits no harness file.

In a copy of the benchmark (``bench/`` and ``BENCHMARK.json``) under a
temporary directory, a throwaway configuration, mix, limits file and
metric reader are added, the manifest gains their entries, and a traced
run of the new cell (on the CPU, the look for a chip stepped round)
reports the new metric. Every harness file is unchanged.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

DRIVE = """
import json, sys
sys.argv = ["run"]
import jax
from bench import run
run.require_tpu = lambda chips: jax.devices()
run.enable_compile_cache = lambda: None
sys.exit(run.main(["--workload", "tiny.burst", "--seed", "3",
                   "--seconds", "1.5", "--trace", "1"]))
"""


def digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_cell_from_files_only(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    before = digest(tmp_path)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")

    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "qwen3-1.7b-vq.json").read_text())
    cfg.update(name="tiny", hidden_size=256, intermediate_size=512,
               num_hidden_layers=1, num_attention_heads=2,
               num_key_value_heads=1, head_dim=128, vocab_size=256,
               reduced=["num_hidden_layers"])
    cfg["engine"] = dict(cfg["engine"], vq_matmul_impl="xla",
                         paged_attn_impl="xla", prefill_chunk=16)
    (b / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (b / "traffic" / "burst.json").write_text(json.dumps({
        "kind": "open_loop", "rate_per_s": 10.0, "arrival_cv": 2.0,
        "prompt": {"median": 10, "sigma": 0.5, "min": 4, "max": 30},
        "output": {"median": 4, "sigma": 0.2, "min": 3, "max": 6},
        "engine": {"max_batch": 4, "max_len": 48},
        "trace_window": {"start_s": 0.2, "seconds": 0.8}}))
    (b / "limits" / "tiny.burst.json").write_text(json.dumps({
        "check": {"min_tokens": 20, "max_requests": 4},
        "numbers": {"served_logit_gap": {"limit": 1.0}}}))
    (b / "metrics" / "requests_traced.py").write_text(
        "def read(ctx):\n"
        "    n = sum(t.prompts_done for t in ctx.traced_ticks)\n"
        "    return n or None\n")
    m = json.loads((tmp_path / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json",
                         "reduced": ["num_hidden_layers"], "why": "test"})
    m["workloads"].append({"name": "tiny.burst", "config": "tiny",
                           "traffic": "burst", "chips": 1, "why": "test"})
    m["end_to_end"].append({"name": "ttft_p95_ms", "unit": "ms",
                            "better": "lower", "bound": 0.25,
                            "source": "host_clock",
                            "workloads": ["tiny.burst"]})
    m["per_layer"].append({"name": "requests_traced", "unit": "requests",
                           "better": "higher", "source": "program_counter",
                           "layer": "engine and scheduler",
                           "moves": "ttft_p95_ms",
                           "workloads": ["tiny.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", DRIVE], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["metrics"]["requests_traced"]["unit"] == "requests"
    after = digest(tmp_path)
    assert {k: after[k] for k in before} == before
