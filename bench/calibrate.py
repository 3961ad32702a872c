"""Readings that the limits of ``correct`` are set from, for one cell.

  python3 bench/calibrate.py --workload <name> --seconds <s> --seeds 1,2,3

In one process on the chip, for each seed: the cell's own set-up and
window, exactly as ``bench/run.py`` drives them, then the reference over a
seeded sample of the finished requests, and the control (the reference in
float8) over the same prompts and served tokens. Prints one JSON line per
seed with the program's reading and the control's; ``bench/limits/``
records what each limit was set from. Not part of a benchmark run.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, manifest, run, serve, traffic  # noqa: E402


def one_seed(cell, seed: int, seconds: float, counter) -> dict:
    spec, mix = cell.spec, cell.mix
    engine = serve.build_engine(cell.arch, spec, cell.config, mix, seed)
    serve.warm_up(engine, spec, mix, seconds, seed)
    arrivals = traffic.schedule(mix, seed, seconds, spec.vocab)
    window = serve.drive(engine, arrivals, seconds, counter)
    serve.first_token_times(engine, window)
    e2e = serve.end_to_end(window)
    del engine
    gc.collect()
    limits = check.load_limits(cell.name)
    ok, shown, info = serve.correctness(cell.arch, spec, window, seed,
                                        limits, control=True)
    return {"seed": seed, "correct": ok, **{k: v["value"]
                                            for k, v in shown.items()},
            **info, **e2e, "compiles_in_window": window.compiles,
            "pool_used_max": window.pool_used_max}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = manifest.load_cell(args.workload)
    run.enable_compile_cache()
    run.require_tpu(cell.chips)
    counter = serve.CompileCounter()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        row = one_seed(cell, seed, args.seconds, counter)
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
