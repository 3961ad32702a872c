"""Run one benchmark cell on the chip this process is started on.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file and
a traffic mix; both, the limits of the correctness check and the per-layer
readers are found by name under ``bench/``. The run refuses any device but
a TPU before it does any work, prints its diagnostics on earlier lines, the
numbers it compared beside their limits as the last lines of standard
error, and one JSON result as the last line of standard output.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a part of the window is recorded on the device trace and the
result carries the cell's per-layer metrics, the device's busy and window
seconds, and a breakdown of where the time went.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import manifest  # noqa: E402


class NoChip(SystemExit):
    pass


def require_tpu(chips: int):
    """Exit non-zero, before any work, unless JAX holds enough TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"bench: cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs


def enable_compile_cache():
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    when set, else ``.jax_cache`` in the checkout), holding every program
    however quickly it compiled, so only a cell's first run compiles."""
    import jax
    from repro import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = manifest.load_cell(args.workload)
    enable_compile_cache()
    devs = require_tpu(cell.chips)
    from bench import runner

    result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                         devs, PROCESS_START)
    for name, v in result["check"].items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
