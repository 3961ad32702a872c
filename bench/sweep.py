"""Find the highest rate a serving cell sustains: its knee.

  python3 bench/sweep.py --workload <name> --seconds <s> --rates 2,4,8

One process on the chip builds the cell's engine once and runs its mix at
each rate in turn (the mix's own rate is ignored), printing one JSON line
per rate: requests sent and finished, tokens per second, time to first
token and gap tails, and the queue left at the close. The knee is the
highest rate whose queue does not grow through the window; a cell's rate
is then fixed in its traffic file. Not part of a benchmark run.
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

from bench import manifest, run, runner, serve, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = manifest.load_cell(args.workload)
    run.enable_compile_cache()
    devs = run.require_tpu(cell.chips)
    spec, mix = cell.spec, cell.mix
    counter = serve.CompileCounter()
    engine = serve.build_engine(cell.arch, spec, cell.config, mix, args.seed)
    rates = [float(r) for r in args.rates.split(",")]
    for rate in rates:       # each rate's lengths may need other widths
        serve.warm_up(engine, spec, dict(mix, rate_per_s=rate),
                      args.seconds, args.seed)
    print(json.dumps({"setup_s": time.perf_counter() - PROCESS_START}),
          flush=True)
    for n, rate in enumerate(rates):
        arrivals = traffic.schedule(dict(mix, rate_per_s=rate), args.seed,
                                    args.seconds, spec.vocab)
        window = serve.drive(engine, arrivals, args.seconds, counter)
        serve.first_token_times(engine, window)
        e2e = serve.end_to_end(window)
        queued = len(engine.scheduler.queue)
        print(json.dumps({"rate_per_s": rate, **e2e, "queued_at_close":
                          queued, "compiles": window.compiles,
                          "pool_used_max": window.pool_used_max,
                          "pool_used_mean": window.pool_used_mean,
                          "pool_blocks": window.pool_blocks,
                          "peak_bytes": runner.device_record(
                              devs, cell.chips)["memory_peak_bytes"]}), flush=True)
        if n + 1 == len(rates):
            break
        # drain what is left before the next rate
        while engine.scheduler.has_work():
            engine.step()
        engine.drain_request_records()
    return 0


if __name__ == "__main__":
    sys.exit(main())
