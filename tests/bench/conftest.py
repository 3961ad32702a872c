"""The benchmark's tests import ``bench`` from the repository root, and
build small versions of the benchmark's configurations."""
import dataclasses
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture
def small_spec():
    """A decoder spec of a benchmark configuration at test widths."""
    from bench import spec as bspec
    from bench.arch import decoder

    def make(name="qwen3-1.7b-vq", **kw):
        base = decoder.spec(
            bspec.load(ROOT / "bench" / "configs" / f"{name}.json"))
        sizes = dict(d=256, n_layers=2, n_heads=4, n_kv=2, hd=64, d_ff=512,
                     vocab=300, max_positions=512)
        sizes.update(kw)
        return dataclasses.replace(base, **sizes)

    return make


def shrink(cell, impl="xla"):
    """A cell at test widths. ``impl`` "pallas" runs the kernels in Pallas
    interpret mode; "xla" runs their XLA forms, which is quicker."""
    c = cell.config
    c.update(hidden_size=256, intermediate_size=512, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=64,
             vocab_size=300)
    c["engine"] = dict(c["engine"], vq_matmul_impl=impl,
                       paged_attn_impl=impl, prefill_chunk=16)
    cell.mix.update(rate_per_s=12.0,
                    prompt={"median": 14, "sigma": 0.6, "min": 4, "max": 40},
                    output={"median": 5, "sigma": 0.3, "min": 3, "max": 8},
                    engine={"max_batch": 4, "max_len": 64},
                    trace_window={"start_s": 0.3, "seconds": 0.8})
    return cell


@pytest.fixture
def cpu_run(monkeypatch, capsys):
    """Run ``bench/run.py``'s main on the CPU at test widths; returns the
    parsed result line and standard error."""
    import json

    import jax

    from bench import manifest, run

    monkeypatch.setattr(run, "require_tpu", lambda chips: jax.devices())
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    real = manifest.load_cell
    opts = {}

    def load(name, *a):
        cell = shrink(real(name, *a), opts["kernels"])
        cell.mix.update(opts["mix"])
        return cell

    monkeypatch.setattr(manifest, "load_cell", load)

    def go(workload, trace=0, seconds=2.0, kernels="xla", **mix):
        opts.update(kernels=kernels, mix=mix)
        assert run.main(["--workload", workload, "--seed", str(2**33 + 1),
                         "--seconds", str(seconds), "--trace",
                         str(trace)]) == 0
        out, err = capsys.readouterr()
        return json.loads(out.strip().splitlines()[-1]), err

    return go
