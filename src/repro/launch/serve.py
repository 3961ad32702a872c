"""Serving launcher: load (or synthesize) weights, optionally GPTVQ-quantize
them, and serve batched synthetic requests through the engine.

  PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --smoke \
      --vq --requests 8 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import compile_cache
from repro.configs import ARCHS, SMOKE
from repro.core.pipeline import quantize_model
from repro.core.recipe import get_recipe
from repro.data.calibration import calibration_tokens
from repro.models import model_zoo
from repro.obs import Telemetry
from repro.serve.engine import Engine, Request


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--vq", action="store_true",
                    help="GPTVQ-quantize before serving")
    ap.add_argument("--recipe", default="2.25bpv_2d",
                    help="recipe preset name or JSON path (with --vq)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--paged-attn-impl", default="gather",
                    choices=["gather", "fused", "xla", "pallas"],
                    help="decode attention over the paged KV pool: the "
                         "XLA logical-view gather (default), or the fused "
                         "in-kernel page gather ('fused' = Pallas kernel "
                         "on TPU, its XLA oracle elsewhere)")
    ap.add_argument("--vq-matmul-impl", default="gather",
                    choices=["gather", "fused", "xla", "pallas"],
                    help="execution path for VQ-packed weight leaves: "
                         "per-layer dense dequantization (default), or the "
                         "fused VQ-dequant matmul over engine-prepped "
                         "FusedVQLinear leaves ('fused' = Pallas kernel on "
                         "TPU, its XLA oracle elsewhere); with --vq this "
                         "skips the per-tick dense-weight materialization")
    ap.add_argument("--kv-cache-bits", default=16,
                    type=lambda s: s if s == "vq2" else int(s),
                    choices=[16, 8, 4, "vq2"],
                    help="paged KV-cache storage: 16 = passthrough dtype, "
                         "8/4 = int8/packed-int4 pages with per-row "
                         "per-kv-head scales, dequantized on the fly by "
                         "every read path (2-4x more pages per byte); "
                         "vq2 = vector-quantized pages (4-bit codebook "
                         "indices over d=2 head-dim vectors, ~10x pages "
                         "per byte; codebooks EM-calibrated at engine "
                         "load, then frozen)")
    ap.add_argument("--prefix-cache", default="off", choices=["on", "off"],
                    help="radix prefix cache + refcounted copy-on-write "
                         "page tables: admitted prompts whose prefix was "
                         "already prefilled share those KV pages and skip "
                         "their prefill chunks (attention families; inert "
                         "for recurrent-state families)")
    ap.add_argument("--parallel-n", type=int, default=1,
                    help="parallel samples per request: each request forks "
                         "n-1 children sharing the prompt's KV blocks "
                         "(best with --prefix-cache on; temperature 0 "
                         "makes them identical — use --temperature)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--events-out", default=None,
                    help="write the request-lifecycle JSONL event stream "
                         "(enqueue/admit/first_token/preempt/finish) here")
    ap.add_argument("--metrics-out", default=None,
                    help="write the final metrics-registry snapshot "
                         "(gauges/counters/histograms + dispatch counts) "
                         "as JSON here")
    ap.add_argument("--trace-dir", default=None,
                    help="capture a jax profiler trace of the serve loop; "
                         "each tick is the step serve.tick and each phase "
                         "an annotation serve.<phase>")
    args = ap.parse_args()

    cfg = (SMOKE if args.smoke else ARCHS)[args.arch]
    if args.smoke:
        cfg = cfg.scaled(dtype="float32")
    model = model_zoo.build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    print(f"arch={cfg.name} params={model_zoo.count_params(model)/1e6:.1f}M")

    if args.vq:
        t0 = time.time()
        calib = calibration_tokens(cfg.vocab_size, n_sequences=8, seq_len=64)
        recipe = get_recipe(args.recipe)
        if not args.recipe.endswith(".json"):
            # presets get the serving-demo speed knobs; a user-authored
            # JSON recipe's own em/update iteration counts stay as written
            recipe = recipe.with_quantize_overrides(
                em_iters=15, codebook_update_iters=5)
        params, rep = quantize_model(model, params, calib, recipe=recipe,
                                     pack=True)
        print(f"GPTVQ[{recipe.name}]: {rep.achieved_bpv:.3f} bpv "
              f"in {time.time()-t0:.1f}s")

    rng = np.random.RandomState(0)
    prefix_on = args.prefix_cache == "on"
    if prefix_on:
        # shared-prefix traffic (the system-prompt pattern the cache is
        # for): every request opens with the same 2 pages of tokens and
        # diverges in a short private tail
        header = rng.randint(0, cfg.vocab_size, size=32)
        prompts = [np.concatenate([
            header, rng.randint(0, cfg.vocab_size, size=6 + i % 5)])
            for i in range(args.requests)]
    else:
        prompts = [rng.randint(0, cfg.vocab_size, size=6 + i % 5)
                   for i in range(args.requests)]
    reqs = [Request(rid=i, prompt=p, max_new_tokens=args.max_new,
                    temperature=args.temperature, n=args.parallel_n)
            for i, p in enumerate(prompts)]
    telemetry = Telemetry(events_out=args.events_out,
                          trace_dir=args.trace_dir)
    eng = Engine(model, params, max_batch=args.max_batch,
                 max_len=args.max_len,
                 paged_attn_impl=args.paged_attn_impl,
                 kv_cache_bits=args.kv_cache_bits,
                 vq_matmul_impl=args.vq_matmul_impl,
                 prefix_cache=prefix_on,
                 telemetry=telemetry)
    if args.kv_cache_bits != 16:
        import dataclasses as _dc

        import jax.numpy as jnp

        from repro.models.attention import KVQuantSpec
        from repro.serve.paged_cache import pool_bytes_of
        fp_layout = _dc.replace(eng.layout, kv=KVQuantSpec())
        print(f"kv_cache_bits={args.kv_cache_bits}: per-layer pool "
              f"{pool_bytes_of(model.cfg, eng.layout, jnp.float32)} B vs "
              f"{pool_bytes_of(model.cfg, fp_layout, jnp.float32)} B fp32 "
              f"at the same page count")
    eng.run(reqs)
    tok_s = eng.stats["tokens"] / max(eng.stats["wall_s"], 1e-9)
    print(f"served {len(reqs)} requests, {eng.stats['tokens']} tokens in "
          f"{eng.stats['wall_s']:.2f}s ({tok_s:.1f} tok/s host-CPU)")

    records = eng.drain_request_records()
    ttfts = sorted(r.ttft_s for r in records if r.ttft_s is not None)
    itls = sorted(r.itl_mean_s for r in records if r.itl_mean_s is not None)
    if ttfts:
        mid = ttfts[len(ttfts) // 2]
        print(f"TTFT: median {mid*1e3:.1f}ms  worst {ttfts[-1]*1e3:.1f}ms "
              f"(enqueue -> first sampled token; first TTFT pays jit "
              f"compilation on this synthetic run)")
    if itls:
        mid = itls[len(itls) // 2]
        print(f"ITL:  median {mid*1e3:.1f}ms/token  worst "
              f"{itls[-1]*1e3:.1f}ms/token")
    preempted = sum(r.preemptions for r in records)
    if preempted:
        print(f"preemptions: {preempted} (recompute-style; preempted "
              f"tokens were discarded and regenerated)")
    if eng.prefix_cache is not None:
        s = eng.stats
        print(f"prefix cache: {s['prefix_hits']} hits / "
              f"{s['prefix_misses']} misses, "
              f"{s['prefix_hit_tokens']} prompt tokens served from shared "
              f"pages, {s['prefix_cached_blocks']} blocks cached, "
              f"{s['prefix_evictions']} evicted")
    if args.parallel_n > 1:
        kids = sum(len(r.forks) for r in reqs)
        print(f"parallel sampling: {kids} forked sequences "
              f"(n={args.parallel_n}) shared their prompts' KV pages")

    if args.metrics_out:
        telemetry.write_metrics(args.metrics_out)
        print(f"metrics snapshot -> {args.metrics_out}")
    if args.events_out:
        print(f"event stream -> {args.events_out}")
    if args.trace_dir:
        print(f"profiler trace -> {args.trace_dir}")
    eng.close()
    telemetry.close()
    for r in reqs[:2]:
        print(f"  req {r.rid}: {list(r.prompt)[:4]}... -> {r.out_tokens[:8]}")


if __name__ == "__main__":
    main()
