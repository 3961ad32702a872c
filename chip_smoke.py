"""Chip smoke test: quantize -> serve on one TPU at qwen3-1.7b's widths.

The quickest proof that the system still runs on the chip. One process,
no children; everything is built from this checkout (seeded weights,
``data/calibration`` tokens) through the entry points a user calls:
``model_zoo.build``, ``core.pipeline.quantize_model``,
``serve.engine.Engine`` and ``Request``.

  python chip_smoke.py             # one chip: serve-fp, quantize->serve-vq
  python chip_smoke.py --chips 4   # four chips: sharded-Hessian phase only

Phases (one chip):
  serve-fp        qwen3-1.7b unchanged (28 layers, bf16 weights) served by
                  Engine(max_batch=8, max_len=512, paged_attn_impl="fused")
                  on 8 greedy requests; the fused paged-attention kernel is
                  checked against kernels/ref on the engine's real pool at
                  the first decode tick, then the same requests are served
                  through paged_attn_impl="gather" and compared.
  quantize->serve-vq
                  qwen3-1.7b cut to 4 layers (every width and the whole
                  vocabulary kept), GPTVQ 2.25bpv_2d with pack=True on
                  16 x 256 calibration tokens; every packed leaf's fused
                  kernel is checked against kernels/ref at M=8, then the
                  packed model is served fused and through the gather path.
Phase (--chips 4):
  hessian-mesh    accumulate_sharded over a 4-device "data" mesh against
                  single-device accumulate at c=2048 and c=6144, and one
                  budgeted quantize_model(hessian_mesh=...) whose plan must
                  equal the single-device plan.

Every check raises; no phase's exception is caught, so any failure exits
non-zero. The last stdout line is the JSON device record. Timings printed
on earlier lines are smoke timings, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro import compile_cache  # noqa: E402  (needs the src path above)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import ARCHS  # noqa: E402
from repro.core import hessian as hes  # noqa: E402
from repro.core import vq_linear as vql_mod  # noqa: E402
from repro.core.pipeline import quantize_model  # noqa: E402
from repro.core.recipe import get_recipe  # noqa: E402
from repro.data.calibration import calibration_tokens  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.paged_attention import paged_attention_tpu  # noqa: E402
from repro.models import model_zoo  # noqa: E402
from repro.models.attention import PagedKVCache  # noqa: E402
from repro.obs import (reset_dispatch_counters,  # noqa: E402
                       snapshot_dispatch_counters)
from repro.serve.engine import Engine, Request  # noqa: E402

ARCH = "qwen3-1.7b"
VQ_LAYERS = 4
RECIPE = "2.25bpv_2d"
# GPTVQ iteration counts for the smoke: the launcher's defaults are 25/10.
# The four-chip plan check needs the plan, not a good fit, so it iterates
# least (four chips cost four times as much per second)
QUANT_OVERRIDES = {"em_iters": 10, "codebook_update_iters": 5}
MESH_QUANT_OVERRIDES = {"em_iters": 2, "codebook_update_iters": 0}
# kernel-vs-oracle bound, relative to the oracle's largest magnitude: the
# oracle runs at HIGHEST precision, the kernels keep f32 operands on the
# MXU and round the attention output to bf16 (2^-9 relative)
KERNEL_RTOL = 2e-2
MESH_RTOL = 1e-4


@dataclasses.dataclass(frozen=True)
class Traffic:
    """One phase's request mix: greedy, prompts from a seed."""
    n_requests: int = 8
    prompt_lo: int = 16
    prompt_hi: int = 200
    max_new: int = 32
    max_batch: int = 8
    max_len: int = 512


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def log(msg: str):
    print(msg, flush=True)


class CompileClock:
    """Sums JAX's own trace / lower / backend-compile durations."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.seconds += duration


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else str(peak)


def run_phase(name: str, clock: CompileClock, fn, *args, **kw):
    log(f"== phase {name}")
    c0, t0 = clock.seconds, time.perf_counter()
    out = fn(*args, **kw)
    log(f"[smoke timing] phase={name} wall_s={time.perf_counter() - t0:.3f} "
        f"compile_s={clock.seconds - c0:.3f} "
        f"peak_bytes_in_use={peak_bytes()}")
    return out


# ---------------------------------------------------------------------------
# shared checks
# ---------------------------------------------------------------------------

def make_requests(cfg, traffic: Traffic, seed: int) -> list[Request]:
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rng.randint(0, cfg.vocab_size,
                                       rng.randint(traffic.prompt_lo,
                                                   traffic.prompt_hi + 1)
                                       ).astype(np.int32),
                    max_new_tokens=traffic.max_new)
            for i in range(traffic.n_requests)]


def check_finished(reqs: list[Request], eos_id=None):
    for r in reqs:
        check(r.error is None, f"request {r.rid} failed: {r.error}")
        check(r.done, f"request {r.rid} not finished")
        n = len(r.out_tokens)
        ended = eos_id is not None and n and r.out_tokens[-1] == eos_id
        check(n == r.max_new_tokens or ended,
              f"request {r.rid}: {n} tokens, wanted {r.max_new_tokens}")


def drive(engine: Engine, reqs: list[Request], on_first_decode=None,
          max_ticks: int = 10_000):
    """Submit every request and tick to completion. Unlike Engine.run, a
    rejected request raises here and running out of ticks is a failure."""
    for r in reqs:
        engine.submit(r)
    hooked = on_first_decode is None
    while engine.scheduler.has_work():
        check(engine.ticks < max_ticks, f"not done after {max_ticks} ticks")
        engine.step()
        if not hooked and engine.stats["decode_ticks"] > 0:
            hooked = True
            on_first_decode(engine)


def compare_tokens(name, model, params, fused, gather):
    """Share of identical greedy tokens; at the first divergence, the
    logit margin a plain forward gives between the two picks."""
    same = total = 0
    first = None
    for a, b in zip(fused, gather):
        total += len(a.out_tokens)
        for i, (x, y) in enumerate(zip(a.out_tokens, b.out_tokens)):
            if x != y:
                if first is None:
                    first = (a, i, x, y)
                break
            same += 1
    log(f"{name}: identical greedy tokens fused vs gather "
        f"{same}/{total} = {same / total:.4f}")
    if first is None:
        log(f"{name}: no divergence")
        return
    req, i, x, y = first
    toks = np.concatenate([req.prompt, np.asarray(req.out_tokens[:i],
                                                  np.int32)])
    logits = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0])(
        params, jnp.asarray(toks[None]))[0, -1].astype(jnp.float32)
    log(f"{name}: first divergence request={req.rid} token={i} "
        f"fused={x} gather={y} plain-forward logit margin "
        f"{float(logits[x] - logits[y]):+.6f}")


def check_paged_kernel(engine: Engine, seed: int):
    """The fused paged-attention kernel on the engine's real layer-0 pool,
    page table and positions, against the XLA oracle."""
    cfg = engine.model.cfg
    pkv = next(l for l in jax.tree.leaves(
        engine.cache, is_leaf=lambda x: isinstance(x, PagedKVCache))
        if isinstance(l, PagedKVCache))
    k, v = (pkv.k[0], pkv.v[0]) if pkv.k.ndim == 5 else (pkv.k, pkv.v)
    table = np.zeros((engine.max_batch, engine.n_pages), np.int32)
    pos = np.zeros(engine.max_batch, np.int32)
    live = 0
    for s in engine.scheduler.active():
        if s.phase == "decode":
            table[s.slot, :len(s.pages)] = s.pages
            pos[s.slot] = s.pos - 1      # last row the engine wrote
            live += 1
    check(live > 0, "no decoding slot at the first decode tick")
    q = jax.random.normal(jax.random.PRNGKey(seed),
                          (engine.max_batch, cfg.n_heads, cfg.hd),
                          jnp.dtype(cfg.dtype))
    got = paged_attention_tpu(q, k, v, jnp.asarray(table), jnp.asarray(pos),
                              interpret=jax.default_backend() != "tpu")
    with jax.default_matmul_precision("highest"):
        want = ref.paged_attention_ref(q, k, v, jnp.asarray(table),
                                       jnp.asarray(pos))
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    scale = max(1.0, float(jnp.max(jnp.abs(want.astype(jnp.float32)))))
    log(f"paged kernel vs oracle on the real pool: {live} live slots, "
        f"max abs err {err:.3e}, bound {KERNEL_RTOL * scale:.3e}")
    check(err <= KERNEL_RTOL * scale, "paged kernel disagrees with oracle")


def counters(name: str) -> dict:
    snap = snapshot_dispatch_counters()
    log(f"{name}: dispatch counters paged={snap['paged']} vq={snap['vq']}")
    return snap


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_serve_fp(cfg, traffic: Traffic, seed: int, impl: str = "fused"):
    model = model_zoo.build(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"serve-fp: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"dtype={cfg.dtype} params={n_params}")
    kw = dict(max_batch=traffic.max_batch, max_len=traffic.max_len)

    reset_dispatch_counters()
    eng = Engine(model, params, paged_attn_impl=impl, **kw)
    check(eng.paged_attn_impl == "pallas",
          f"paged_attn_impl resolved to {eng.paged_attn_impl!r}")
    fused = make_requests(cfg, traffic, seed)
    drive(eng, fused, on_first_decode=lambda e: check_paged_kernel(e, seed))
    check(counters("serve-fp fused")["paged"]["pallas"] > 0,
          "paged.pallas never dispatched")
    check_finished(fused)
    log(f"serve-fp fused: {eng.stats['tokens']} tokens in "
        f"{eng.stats['decode_ticks']} decode ticks")

    reset_dispatch_counters()
    gather = make_requests(cfg, traffic, seed)
    drive(Engine(model, params, paged_attn_impl="gather", **kw), gather)
    check_finished(gather)
    compare_tokens("serve-fp", model, params, fused, gather)


def packed_leaves(params):
    return [leaf for leaf in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, vql_mod.VQLinear))
        if isinstance(leaf, vql_mod.VQLinear)]


def check_vq_kernels(params, seed: int, m: int = 8):
    """Every packed leaf (every layer of a stacked leaf): the fused Pallas
    matmul against the oracle at M=m."""
    worst, n = 0.0, 0
    for li, leaf in enumerate(packed_leaves(params)):
        fl = vql_mod.prepare_fused(leaf, impl="pallas")
        check(isinstance(fl, vql_mod.FusedVQLinear),
              f"packed leaf {li} has no fused layout")
        lead = fl.words.shape[:-2]
        for idx in np.ndindex(*lead):
            one = jax.tree.map(lambda a: a[idx], fl)
            x = jax.random.normal(jax.random.fold_in(
                jax.random.PRNGKey(seed), n), (m, one.c), jnp.float32)
            y = vql_mod.fused_matmul(x, one, impl="pallas")
            with jax.default_matmul_precision("highest"):
                y_ref = ref.vq_dequant_matmul_ref(
                    x, one.words, one.codebooks_f, one.scales, d=one.d,
                    code_bits=one.code_bits,
                    rows_per_band=one.rows_per_band,
                    group_cols=one.group_cols, scale_block=one.scale_block)
            rel = float(jnp.max(jnp.abs(y - y_ref))
                        / jnp.maximum(jnp.max(jnp.abs(y_ref)), 1e-30))
            worst = max(worst, rel)
            n += 1
            check(rel <= KERNEL_RTOL,
                  f"vq kernel leaf {li}{idx} ({one.r}x{one.c}) rel err {rel}")
    check(n > 0, "quantize produced no packed leaves")
    log(f"vq kernel vs oracle: {n} packed matrices at M={m}, "
        f"worst rel err {worst:.3e} (bound {KERNEL_RTOL})")


def quantize(cfg, seed: int, n_seq: int, seq_len: int, **kw):
    model = model_zoo.build(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    calib = calibration_tokens(cfg.vocab_size, n_sequences=n_seq,
                               seq_len=seq_len)
    recipe = get_recipe(RECIPE).with_quantize_overrides(**QUANT_OVERRIDES)
    qparams, rep = quantize_model(model, params, calib, recipe=recipe,
                                  pack=True, progress=log, **kw)
    return model, params, calib, recipe, qparams, rep


def phase_quantize_serve_vq(cfg, traffic: Traffic, seed: int, *,
                            n_seq: int = 16, seq_len: int = 256,
                            impl: str = "fused"):
    model, _, _, _, qparams, rep = quantize(cfg, seed, n_seq, seq_len)
    stages = " ".join(f"{k}={v:.3f}" for k, v in
                      sorted(rep.stage_seconds.items()))
    log(f"quantize: {cfg.name} layers={cfg.n_layers} recipe={RECIPE} "
        f"{QUANT_OVERRIDES} calib={n_seq}x{seq_len}")
    log(f"[smoke timing] quantize total_s={rep.total_seconds:.3f} "
        f"s_per_block={rep.total_seconds / len(rep.per_layer):.3f} "
        f"stages: {stages}")
    log(f"quantize: achieved_bpv={rep.achieved_bpv:.4f} "
        f"total_layer_error={rep.total_error():.6f}")
    check_vq_kernels(qparams, seed)

    kw = dict(max_batch=traffic.max_batch, max_len=traffic.max_len)
    reset_dispatch_counters()
    eng = Engine(model, qparams, vq_matmul_impl=impl, paged_attn_impl=impl,
                 **kw)
    check((eng.vq_matmul_impl, eng.paged_attn_impl) == ("pallas", "pallas"),
          f"impls resolved to {eng.vq_matmul_impl!r}/"
          f"{eng.paged_attn_impl!r}")
    check(not packed_leaves(eng.params),
          "a packed leaf stayed on the gather path")
    fused = make_requests(cfg, traffic, seed + 1)
    drive(eng, fused)
    snap = counters("serve-vq fused")
    check(snap["vq"]["pallas"] > 0, "vq.pallas never dispatched")
    check(snap["vq"]["gather"] == 0, "vq.gather dispatched on fused engine")
    check(snap["paged"]["pallas"] > 0, "paged.pallas never dispatched")
    check_finished(fused)
    log(f"serve-vq fused: {eng.stats['tokens']} tokens in "
        f"{eng.stats['decode_ticks']} decode ticks")

    reset_dispatch_counters()
    gather = make_requests(cfg, traffic, seed + 1)
    drive(Engine(model, qparams, vq_matmul_impl="gather",
                 paged_attn_impl="gather", **kw), gather)
    check_finished(gather)
    compare_tokens("serve-vq", model, qparams, fused, gather)


def calib_activations(cfg, params, calib):
    """Real calibration activations of layer 0: the embedded tokens
    (c = d_model) and the gated MLP hidden state (c = d_ff)."""
    x = params["embed"][calib].reshape(-1, cfg.d_model).astype(jnp.float32)
    ffn = jax.tree.map(lambda a: a[0], params["layers"]["ffn"])
    h = (jax.nn.silu(x @ ffn["w_gate"].astype(jnp.float32))
         * (x @ ffn["w_in"].astype(jnp.float32)))
    return x, h


def phase_hessian_mesh(cfg, seed: int, n_dev: int, *, n_seq: int = 16,
                       seq_len: int = 256, budget_bpv: float = 2.5):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import adapters
    from repro.core.pipeline import (_allocate, _budget_prepass,
                                     _collect_targets)

    check(jax.device_count() >= n_dev,
          f"{jax.device_count()} devices, need {n_dev}")
    mesh = hes.data_mesh(n_dev)
    model = model_zoo.build(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    calib = calibration_tokens(cfg.vocab_size, n_sequences=n_seq,
                               seq_len=seq_len)
    for x in calib_activations(cfg, params, calib):
        rows, c = x.shape
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        shards = {s.device: s.data.shape for s in xs.addressable_shards}
        check(len(shards) == n_dev and all(
            sh == (rows // n_dev, c) for sh in shards.values()),
            f"row shards {shards}")
        for init, acc, get in (
                (hes.init_hessian, hes.accumulate, lambda s: s.H),
                (hes.init_diag_hessian, hes.accumulate_diag,
                 lambda s: s.diag)):
            one = acc(init(c), x)
            sharded = hes.accumulate_sharded(init(c), xs, mesh)
            check(int(sharded.n) == int(one.n) == rows, "row counts differ")
            a, b = get(sharded), get(one)
            rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            log(f"hessian-mesh c={c} {init.__name__}: {n_dev} row shards "
                f"of {rows // n_dev}, rel err vs single device {rel:.3e}")
            check(rel <= MESH_RTOL, f"sharded Hessian rel err {rel}")

    # the single-device plan: the same pre-pass and allocator, no mesh
    recipe = get_recipe(RECIPE).with_quantize_overrides(
        **MESH_QUANT_OVERRIDES)
    adapter = adapters.get_adapter(model, params)
    blocks = adapter.blocks()
    plan = recipe.resolve(_collect_targets(blocks))
    chunks = [calib[i:i + 8] for i in range(0, n_seq, 8)]
    diag, missed = _budget_prepass(adapter, chunks, plan, None)
    plan, _ = _allocate(blocks, plan, diag, missed, budget_bpv, None)
    single = {name: res.rule for name, res in plan.items()}

    _, rep = quantize_model(model, params, calib, recipe=recipe,
                            budget_bpv=budget_bpv, hessian_mesh=mesh,
                            pack=True, progress=log)
    meshed = {name: e["rule"] for name, e in rep.per_target.items()}
    log(f"hessian-mesh budgeted quantize: {len(meshed)} targets, "
        f"achieved_bpv={rep.achieved_bpv:.4f} "
        f"[smoke timing] total_s={rep.total_seconds:.3f}")
    check(meshed == single, f"mesh plan {meshed} != single {single}")
    log("hessian-mesh: budget plan identical to the single-device plan")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded-Hessian phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = compile_cache.enable()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    log(f"device {dev.device_kind} x{jax.device_count()}, "
        f"compile cache {cache_dir}")
    clock = CompileClock()
    full = ARCHS[ARCH]
    cut = full.scaled(n_layers=VQ_LAYERS)
    if args.chips == 4:
        run_phase("hessian-mesh", clock, phase_hessian_mesh, cut, args.seed,
                  4)
        count = 4
    else:
        run_phase("serve-fp", clock, phase_serve_fp, full, Traffic(),
                  args.seed)
        run_phase("quantize->serve-vq", clock, phase_quantize_serve_vq, cut,
                  Traffic(), args.seed)
        count = 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
