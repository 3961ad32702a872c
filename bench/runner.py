"""One cell's run, from set-up to the result line, by the mix's ``kind``."""
from __future__ import annotations

import gc
import shutil
import tempfile
import time

import numpy as np

from bench import check, metrics, serve, trace as trace_mod, traffic


def log(msg: str):
    print(msg, flush=True)


def device_record(devs, chips: int) -> dict:
    dev = devs[0]
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips, "memory_peak_bytes": peak}


def run(cell, seed: int, seconds: float, with_trace: bool, devs,
        process_start: float) -> dict:
    kind = cell.mix["kind"]
    if kind != "open_loop":
        raise SystemExit(f"bench: no runner for traffic kind {kind!r}")
    return run_serving(cell, seed, seconds, with_trace, devs, process_start)


def run_serving(cell, seed, seconds, with_trace, devs, process_start):
    spec, mix = cell.spec, cell.mix
    counter = serve.CompileCounter()
    engine = serve.build_engine(cell.arch, spec, cell.config, mix, seed)
    impls = (engine.vq_matmul_impl, engine.paged_attn_impl)
    log(f"engine: {spec.name} layers={spec.n_layers} impls={impls} "
        f"max_batch={engine.max_batch} max_len={engine.max_len}")
    if devs[0].platform == "tpu" and impls != ("pallas", "pallas"):
        raise SystemExit(f"bench: fused paths resolved to {impls}")
    serve.warm_up(engine, spec, mix, seconds, seed)
    arrivals = traffic.schedule(mix, seed, seconds, spec.vocab)
    prof_dir = tempfile.mkdtemp(prefix="bench-trace-") if with_trace else None
    tr = None
    if with_trace:
        tw = mix["trace_window"]
        spans = engine.telemetry.spans
        tr = (tw["start_s"], tw["start_s"] + tw["seconds"],
              lambda: spans.start_trace(prof_dir), spans.stop_trace)
    setup_s = time.perf_counter() - process_start
    log(f"setup: {setup_s:.3f} s, {counter.compiles} compiles, "
        f"{counter.cache_loads} cache loads; window {seconds} s, "
        f"{len(arrivals)} requests due")
    window = serve.drive(engine, arrivals, seconds, counter, tr)
    serve.first_token_times(engine, window)
    e2e = serve.end_to_end(window)
    device = device_record(devs, cell.chips)
    late = np.asarray(window.late_s) if window.late_s else np.zeros(1)
    log(f"window: {e2e['_attempted']} sent, {e2e['_requests_finished']} "
        f"finished, {e2e['_failed']} failed, {e2e['_tokens']} tokens, "
        f"{e2e['_gaps']} gaps; ttft_p95_ms {e2e['ttft_p95_ms']} itl_p95_ms "
        f"{e2e['itl_p95_ms']}; generator late p50 {1e3 * np.median(late):.3f}"
        f" ms max {1e3 * late.max():.3f} ms; compiles in window "
        f"{window.compiles} (cache loads {window.cache_loads}); "
        f"pool blocks in use max {window.pool_used_max} mean "
        f"{window.pool_used_mean:.1f} of {window.pool_blocks}; "
        f"peak bytes {device['memory_peak_bytes']}")

    per_layer = {}
    breakdown = None
    if with_trace:
        summary = trace_mod.load(prof_dir)
        shutil.rmtree(prof_dir, ignore_errors=True)
        ctx = metrics.Context(arch=cell.arch, spec=spec, window=window,
                              trace=summary, device_kind=devs[0].device_kind,
                              mix=mix)
        per_layer = metrics.read_all(cell.per_layer, ctx)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
        log(f"trace: window {summary.window_s:.6f} s busy "
            f"{summary.busy_s:.6f} s; {len(window.ticks)} ticks traced")

    # the program's state goes before the reference runs
    del engine
    gc.collect()
    limits = check.load_limits(cell.name)
    ok, shown, info = serve.correctness(cell.arch, spec, window, seed, limits)
    log(f"check: {info}")
    # nothing may compile inside the measured window
    shown["compiles_in_window"] = {"value": window.compiles, "limit": 0}
    ok = ok and e2e["_failed"] == 0 and window.compiles == 0

    values = {"setup_s": setup_s, **e2e}
    if with_trace:
        out_metrics = {m["name"]: {"value": per_layer[m["name"]],
                                   "unit": m["unit"]}
                       for m in cell.per_layer if m["name"] in per_layer}
    else:
        out_metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in cell.end_to_end
                       if values.get(m["name"]) is not None}
    result = {"correct": bool(ok), "attempted": e2e["_attempted"],
              "failed": e2e["_failed"], "metrics": out_metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = shown
    return result
