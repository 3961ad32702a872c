"""Quantization launcher: run the GPTVQ pipeline over a model and save the
packed checkpoint.

Any architecture in the zoo quantizes through the same command — the
pipeline resolves the family's ModelAdapter (core/adapters/) from the
config, so `--arch whisper-small` or `--arch zamba2-7b` works exactly like
`--arch llama2-7b`.

Configuration is recipe-first: `--recipe` takes a preset name (any
PAPER_SETTINGS key as a uniform recipe, or `mixed_demo`) or a JSON file of
declarative per-target rules (schema: core/recipe.py / ROADMAP.md
"Recipes"); `--setting` remains as the uniform shorthand. `--budget-bpv`
enables Hessian-budgeted mixed precision on top of whichever recipe is
active: a cheap diagonal-Hessian pre-pass scores every target at each
candidate setting and a greedy allocator spends the budget where it buys
the most reconstruction error. The checkpoint metadata records the
resolved recipe and the full per-target bpv/rule/error map (not just one
global number), so serve/report can reconstruct the mix.

Distribution note (DESIGN.md §3): calibration Hessian accumulation is
data-parallel (each worker processes a shard of the calibration set; a psum
merges per-layer Hessians), and layers are embarrassingly parallel across
workers afterwards. On the single-process container worker_count=1 runs the
identical code path.

  PYTHONPATH=src python -m repro.launch.quantize --arch llama2-7b --smoke \
      --setting 2.25bpv_2d --out /tmp/vq_ckpt
  PYTHONPATH=src python -m repro.launch.quantize --arch zamba2-7b --smoke \
      --recipe mixed_demo --out /tmp/vq_ckpt
  PYTHONPATH=src python -m repro.launch.quantize --arch llama2-7b --smoke \
      --budget-bpv 2.5 --out /tmp/vq_ckpt
"""
from __future__ import annotations

import argparse
import time

import jax

from repro import compile_cache
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import ARCHS, SMOKE
from repro.core import adapters
from repro.core import hessian as hes
from repro.core.bpv import PAPER_SETTINGS
from repro.core.pipeline import quantize_model
from repro.core.recipe import PRESET_RECIPES, QuantRecipe, get_recipe
from repro.data.calibration import calibration_tokens, shard_for_worker
from repro.models import model_zoo
from repro.obs import Telemetry
from repro.train.loss import perplexity


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--setting", default="2.25bpv_2d",
                    choices=sorted(PAPER_SETTINGS))
    ap.add_argument("--recipe", default=None,
                    help="preset name (%s) or recipe JSON path; overrides "
                         "--setting" % ", ".join(sorted(PRESET_RECIPES)))
    ap.add_argument("--budget-bpv", type=float, default=None,
                    help="model-wide bits-per-value budget: per-target "
                         "settings are allocated by Hessian sensitivity")
    ap.add_argument("--budget-scorer", default="closed_form",
                    choices=("closed_form", "refit"),
                    help="budget pre-pass error proxy: the O(r*c) "
                         "rate-distortion closed form (default) or the "
                         "original trimmed-EM refit (validation oracle)")
    ap.add_argument("--solver", default=None,
                    choices=("gptq", "babai", "cd"),
                    help="inner sweep solver on every quantize action: "
                         "gptq (paper default), babai (full conditional "
                         "span metric), cd (+coordinate-descent "
                         "refinement)")
    ap.add_argument("--hessian-mesh", type=int, default=0,
                    help="shard Hessian accumulation data-parallel over "
                         "this many local devices (0 = single-device)")
    ap.add_argument("--sequences", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--em-iters", type=int, default=None,
                    help="override em_iters on every quantize action "
                         "(default: 25 for --setting; recipe values for "
                         "--recipe)")
    ap.add_argument("--update-iters", type=int, default=None,
                    help="override codebook_update_iters likewise "
                         "(default: 10 for --setting)")
    ap.add_argument("--out", default="/tmp/repro_vq_ckpt")
    ap.add_argument("--worker", type=int, default=0)
    ap.add_argument("--n-workers", type=int, default=1)
    ap.add_argument("--events-out", default=None,
                    help="write per-stage/per-target quant_* telemetry "
                         "events as JSONL here")
    ap.add_argument("--metrics-out", default=None,
                    help="write the span.quant/* metrics snapshot as JSON "
                         "here")
    args = ap.parse_args()

    cfg = (SMOKE if args.smoke else ARCHS)[args.arch]
    if args.smoke:
        cfg = cfg.scaled(dtype="float32")
    model = model_zoo.build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    calib = calibration_tokens(cfg.vocab_size, n_sequences=args.sequences,
                               seq_len=args.seq_len)
    calib = shard_for_worker(calib, args.worker, args.n_workers)
    heldout = calibration_tokens(cfg.vocab_size, n_sequences=8,
                                 seq_len=args.seq_len, seed=777)

    em, up = args.em_iters, args.update_iters
    if args.recipe is not None:
        recipe = get_recipe(args.recipe)
    else:
        recipe = QuantRecipe.uniform(PAPER_SETTINGS[args.setting],
                                     name=args.setting)
        em = 25 if em is None else em
        up = 10 if up is None else up
    # only explicitly-requested speed knobs touch the recipe: a JSON
    # recipe's per-rule em_iters/update_iters stay authoritative otherwise
    overrides = {k: v for k, v in (("em_iters", em),
                                   ("codebook_update_iters", up))
                 if v is not None}
    if overrides:
        recipe = recipe.with_quantize_overrides(**overrides)
    if args.solver is not None:
        recipe = recipe.with_solver(args.solver)
    mesh = None
    if args.hessian_mesh > 1:
        mesh = hes.data_mesh(args.hessian_mesh)
    budget = f" budget={args.budget_bpv}bpv" if args.budget_bpv else ""
    solver = f" solver={args.solver}" if args.solver else ""
    print(f"arch={cfg.name} recipe={recipe.name or 'custom'}{budget}"
          f"{solver} calib={calib.shape}")

    # stub-frontend extras (audio frames) for families whose forward needs
    # more than tokens; {} for everyone else
    extras = adapters.calib_extras(cfg, heldout)
    ppl_fp = perplexity(model, params, heldout, batch_extra=extras)
    telemetry = Telemetry(events_out=args.events_out)
    t0 = time.time()
    qparams, rep = quantize_model(
        model, params, calib, recipe=recipe, budget_bpv=args.budget_bpv,
        budget_scorer=args.budget_scorer, hessian_mesh=mesh,
        pack=True, progress=lambda msg: print(f"  {msg}", flush=True),
        telemetry=telemetry)
    dt = time.time() - t0
    ppl_vq = perplexity(model, qparams, heldout, batch_extra=extras)
    print(f"quantized in {dt:.1f}s | ppl fp={ppl_fp:.3f} vq={ppl_vq:.3f} "
          f"| recon err={rep.total_error():.4f} "
          f"| achieved {rep.achieved_bpv:.3f} bpv")
    if rep.stage_seconds:
        total = sum(rep.stage_seconds.values())
        parts = "  ".join(
            f"{k}={v:.1f}s ({100*v/max(total, 1e-9):.0f}%)"
            for k, v in sorted(rep.stage_seconds.items(),
                               key=lambda kv: -kv[1]))
        print(f"  stages: {parts}")
    for w in rep.warnings:
        print(f"  WARNING: {w}")
    if args.metrics_out:
        telemetry.write_metrics(args.metrics_out)
        print(f"  metrics snapshot -> {args.metrics_out}")
    telemetry.close()
    dense = [k for k, v in rep.per_target.items()
             if v["action"] == "keep_dense"]
    if dense:
        print(f"  kept dense ({len(dense)}): {', '.join(dense[:6])}"
              + (" ..." if len(dense) > 6 else ""))

    ck = Checkpointer(args.out, keep=1)
    ck.save(0, qparams, metadata={
        "arch": cfg.name, "recipe": rep.recipe,
        "achieved_bpv": rep.achieved_bpv, "per_target": rep.per_target,
        "budget_bpv": args.budget_bpv, "ppl_fp": float(ppl_fp),
        "ppl_vq": float(ppl_vq), "seconds": dt,
        "stage_seconds": rep.stage_seconds, "warnings": rep.warnings,
    })
    print(f"packed checkpoint written to {args.out}")


if __name__ == "__main__":
    main()
