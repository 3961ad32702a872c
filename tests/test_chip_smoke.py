"""Rehearsal of chip_smoke.py on the CPU.

The phase functions run at SMOKE widths in the config's own bf16, with the
Pallas kernels in interpret mode (impl="pallas"; "fused" would resolve to
the XLA oracle off-TPU), and every check they make must pass. ``main()``
itself must refuse to run anywhere but on a TPU. The compile-cache helper
is unit-tested here too, since chip_smoke.py calls it first.
"""
import importlib.util
import os
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache
from repro.configs import SMOKE

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def restore_cache_config():
    """Leave JAX's compilation-cache config as the test found it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


CFG = SMOKE["qwen3-1.7b"]


def _traffic(smoke):
    return smoke.Traffic(n_requests=4, prompt_lo=5, prompt_hi=40, max_new=6,
                         max_batch=4, max_len=64)


def test_smoke_config_keeps_bf16():
    assert CFG.dtype == "bfloat16"


# the phases check their own dispatch counters; the fixture only keeps
# their tallies from leaking into other tests


def test_serve_fp_phase(smoke, dispatch_counters):
    smoke.phase_serve_fp(CFG, _traffic(smoke), seed=0, impl="pallas")


def test_quantize_serve_vq_phase(smoke, dispatch_counters):
    smoke.phase_quantize_serve_vq(CFG.scaled(n_layers=2), _traffic(smoke),
                                  seed=0, n_seq=4, seq_len=32, impl="pallas")


def test_hessian_mesh_phase_on_one_device(smoke):
    """The --chips 4 phase on a degenerate one-device mesh: the same code
    path, shard checks and plan comparison."""
    smoke.phase_hessian_mesh(CFG.scaled(n_layers=1), seed=0, n_dev=1,
                             n_seq=8, seq_len=16)


def test_check_finished_rejects_short_or_failed(smoke):
    import numpy as np
    req = smoke.Request(rid=0, prompt=np.zeros(4, np.int32),
                        max_new_tokens=3, out_tokens=[1, 2], done=True)
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_finished([req])
    req.out_tokens.append(3)
    smoke.check_finished([req])
    req.error = "rejected"
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_finished([req])


def test_main_refuses_cpu(smoke, capsys, restore_cache_config):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_cache_dir_from_env(monkeypatch, restore_cache_config, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    # JAX reads the variable itself; the helper set no other directory
    assert jax.config.jax_compilation_cache_dir is None


def test_cache_dir_default_in_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
    assert os.path.isabs(path)
