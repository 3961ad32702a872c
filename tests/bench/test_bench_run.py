"""Rehearsal of a whole benchmark run on the CPU at test widths.

The harness's look for a chip is stepped round from inside the test, the
cell's configuration and mix are shrunk in memory, and the kernels run in
their XLA forms (once, for every cell, in Pallas interpret mode). What is checked is the run's shape: the result line
has the contract's keys, every metric it prints is declared for the cell
in ``BENCHMARK.json`` with a legal name and unit, and the numbers compared
come last with their limits. No timing from here means anything.
"""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import manifest, run

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def declared():
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    return m, {e["name"]: e for e in m["end_to_end"] + m["per_layer"]}


CELLS = [w["name"] for w in declared()[0]["workloads"]]
# interpret mode is slow: once per configuration, on its first cell
FIRST = list({w["config"]: w["name"]
              for w in reversed(declared()[0]["workloads"])}.values())
RUNS = ([(c, t, "xla") for c in CELLS for t in (0, 1)]
        + [(c, 1, "pallas") for c in FIRST])


@pytest.mark.parametrize("workload,trace,kernels", RUNS)
def test_result_line(cpu_run, workload, trace, kernels):
    res, err = cpu_run(workload, trace, kernels=kernels)
    keys = list(res)
    assert keys[:5] == KEYS and keys[-1] == "check"
    assert set(keys) <= set(KEYS) | {"breakdown", "check"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["correct"] is True
    _, metrics = declared()
    cell = manifest.load_cell(workload)
    wanted = {m["name"] for m in
              (cell.per_layer if trace else cell.end_to_end)}
    for name, v in res["metrics"].items():
        assert name in wanted and NAME.match(name)
        assert v["unit"] == metrics[name]["unit"] and UNIT.match(v["unit"])
    if not trace:
        assert set(res["metrics"]) == wanted
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # the numbers compared, beside their limits, end standard error
    tail = err.strip().splitlines()[-len(res["check"]):]
    for line, (name, v) in zip(tail, res["check"].items()):
        assert line.startswith(f"check {name}: ") and "limit" in line
        assert v["value"] <= v["limit"]


def test_refuses_a_cpu_before_any_work(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    """A checkout holding only the benchmark's own files exits non-zero
    and prints no result."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_compile_in_window_fails_the_run(cpu_run, monkeypatch):
    """A shape left out of the warm-up compiles inside the window, and the
    run's check comes out not correct."""
    from bench import serve

    monkeypatch.setattr(serve, "warm_up", lambda *a, **kw: None)
    res, err = cpu_run(CELLS[0])
    assert res["correct"] is False
    assert res["check"]["compiles_in_window"]["value"] > 0
    assert "check compiles_in_window: " in err.strip().splitlines()[-1]


def test_engine_takes_the_mix_pool_size():
    """``engine.num_blocks`` in a mix sizes the pool apart from the slots,
    and the window records how much of it was in use."""
    from bench import serve
    from conftest import shrink

    cell = shrink(manifest.load_cell(CELLS[0]))
    cell.mix["engine"] = dict(cell.mix["engine"], num_blocks=13)
    spec, mix = cell.spec, cell.mix
    engine = serve.build_engine(cell.arch, spec, cell.config, mix, 3)
    assert engine.scheduler.allocator.capacity == 12
    serve.warm_up(engine, spec, mix, 1.0, 3)
    window = serve.drive(engine, serve.traffic.schedule(mix, 3, 1.0,
                                                        spec.vocab),
                         1.0, serve.CompileCounter())
    assert window.pool_blocks == 12
    assert 0 < window.pool_used_max <= 12
    assert 0 < window.pool_used_mean <= window.pool_used_max
    assert window.compiles == 0


def test_sweep_reports_each_rate(monkeypatch, capsys):
    """The knee-finding tool runs each rate on one engine and reports the
    queue left at each close, at test widths on the CPU."""
    import jax

    from bench import sweep
    from conftest import shrink

    monkeypatch.setattr(run, "require_tpu", lambda chips: jax.devices())
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    real = manifest.load_cell
    monkeypatch.setattr(manifest, "load_cell",
                        lambda name, *a: shrink(real(name, *a)))
    assert sweep.main(["--workload", CELLS[0], "--seconds", "1",
                       "--rates", "2,40"]) == 0
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()
            if x.startswith("{")][1:]
    assert [r["rate_per_s"] for r in rows] == [2.0, 40.0]
    assert all(r["compiles"] == 0 and r["_failed"] == 0 for r in rows)
    assert rows[1]["_attempted"] > rows[0]["_attempted"]
