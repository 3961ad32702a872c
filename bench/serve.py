"""Serving: one cell of a served model under an open-loop mix.

Set-up builds the seeded weights on the device (one jitted call), the
program's ``Engine`` with the configuration's options, and warms up every
shape the mix uses: each prefill chunk width its prompts are fed in (as
the engine's scheduler cuts them), the decode step, and the prompt-sampling
step for every number of prompts that can finish in one tick. The window
then sends each request when it is due (``bench/traffic.py``) and ticks the
engine until ``seconds`` have passed. The program sees only
``Engine.submit`` and ``Engine.step``; a compile inside the window fails
the run's check.

Timing is the host's clock. A token's time is the end of the tick that
produced it (every device result of a tick is on the host by then); the
first token's time is the engine's own request record, taken right after
the prompt's sampled token reaches the host. Time to first token runs
from when the request was due, so a late generator or a queue counts.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from contextlib import nullcontext
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, traffic


class CompileCounter:
    """Counts XLA compilations (and persistent-cache loads) as they
    happen, so the window can show that it compiled nothing."""

    def __init__(self):
        self.compiles = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_loads += 1


@dataclasses.dataclass
class Sent:
    req: object
    due_s: float
    sent_s: float
    token_s: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Tick:
    """What one engine step did, for the per-layer metrics."""
    decode_context: list     # context rows of each decoded token
    prefill_context: list    # context rows of each prompt token fed
    prompts_done: int        # prompts whose first token came this tick


@dataclasses.dataclass
class Window:
    t0: float                # perf_counter at the window's start
    seconds: float
    sent: list
    ticks: list
    late_s: list
    compiles: int
    cache_loads: int
    prefill_tokens: int              # prompt tokens fed in the window
    pool_blocks: int                 # blocks the engine's pool holds
    pool_used_max: int               # most blocks in use after a tick
    pool_used_mean: float            # blocks in use, mean over ticks
    registry_delta: dict | None = None


def build_engine(arch, spec, cfg: dict, mix: dict, seed: int):
    """The program's engine over ``arch``'s seeded payload, with the
    configuration's options and the mix's slots and pool."""
    from repro.models import model_zoo
    from repro.serve.engine import Engine

    raw = arch.raw_payload(spec, seed)
    params = arch.program_params(spec, raw)
    del raw
    eng_cfg = cfg["engine"]
    engine = Engine(
        model_zoo.build(arch.program_config(spec)), params,
        max_batch=mix["engine"]["max_batch"],
        max_len=mix["engine"]["max_len"],
        num_blocks=mix["engine"].get("num_blocks"),
        page_size=eng_cfg["page_size"],
        prefill_chunk=eng_cfg["prefill_chunk"],
        vq_matmul_impl=eng_cfg["vq_matmul_impl"],
        paged_attn_impl=eng_cfg["paged_attn_impl"])
    return engine


def _drain(engine, reqs):
    for r in reqs:
        engine.submit(r)
    while engine.scheduler.has_work():
        engine.step()
    for r in reqs:
        if r.error is not None or not r.done:
            raise RuntimeError(f"warm-up request {r.rid} failed: {r.error}")


def chunk_widths(scheduler, prompt_lengths) -> dict[int, int]:
    """Each prefill chunk width the scheduler feeds these prompts in,
    mapped to the shortest prompt that is fed in it."""
    out = {}
    for p in sorted(set(int(n) for n in prompt_lengths)):
        pos = 0
        while pos < p:
            width, real = scheduler.prefill_chunk_len(
                SimpleNamespace(prompt_len=p, pos=pos))
            out.setdefault(width, p)
            pos += real
    return out


def warm_up(engine, spec, mix: dict, seconds: float, seed: int):
    """Compile every shape the window will use, and no other: a prompt of
    the mix for each prefill chunk width the scheduler feeds its prompts
    in, with the decode step after it; then the prompt sampler as a tick
    calls it when k prompts finish together, for k = 1 .. max_batch."""
    from repro.serve.engine import Request

    rng = np.random.default_rng(int(seed) ^ 0x5EED)
    prompts, _ = traffic.lengths(mix, seconds)
    lengths = sorted(set(chunk_widths(engine.scheduler, prompts).values()))
    sample, seen = engine._sample, []

    def spy(key, logits, temps):
        seen.append(logits[0])
        return sample(key, logits, temps)

    engine._sample = spy
    try:
        _drain(engine, [Request(rid=f"warm{n}", max_new_tokens=2,
                                prompt=rng.integers(0, spec.vocab, n)
                                .astype(np.int32)) for n in lengths])
    finally:
        engine._sample = sample
    for k in range(1, engine.max_batch + 1):
        key, sub = jax.random.split(engine.key)
        np.asarray(sample(sub, jnp.stack([seen[0]] * k),
                          jnp.asarray([0.0] * k, jnp.float32)))
    engine.drain_request_records()


def _contexts(engine, known: dict) -> list:
    """Prompt positions fed since the last call, as context rows."""
    out = []
    for s in engine.scheduler.active():
        now = min(s.pos, s.prompt_len)
        before = known.get(id(s.req), 0)
        out.extend(range(before + 1, now + 1))
        known[id(s.req)] = now
    return out


def _annotate(on: bool, name: str):
    """A host span on the device trace while it records (the trace's
    window and the labels of its idle gaps come from these)."""
    return jax.profiler.TraceAnnotation(name) if on else nullcontext()


def drive(engine, arrivals: list, seconds: float, counter: CompileCounter,
          trace=None) -> Window:
    """Send each arrival when due and tick until ``seconds`` have passed.
    ``trace`` = (start_s, stop_s, start_fn, stop_fn) records that part of
    the window on the device trace."""
    from repro.serve.engine import Request
    from repro.serve.scheduler import CapacityError

    sent, live, ticks, late, used = [], [], [], [], []
    alloc = engine.scheduler.allocator
    prefill_known: dict = {}
    prefilled = 0
    c0, l0 = counter.compiles, counter.cache_loads
    state = "before"                  # the traced part: before, on, done
    snaps = []                        # registry at its start and stop
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if trace is not None and state == "before" and now >= trace[0]:
            snaps.append(engine.telemetry.registry.snapshot())
            trace[2]()
            state = "on"
        if state == "on" and now >= trace[1]:
            trace[3]()
            snaps.append(engine.telemetry.registry.snapshot())
            state = "done"
        while i < len(arrivals) and arrivals[i].due_s <= now:
            a = arrivals[i]
            req = Request(rid=i, prompt=a.prompt,
                          max_new_tokens=a.max_new_tokens)
            s = Sent(req, a.due_s, time.perf_counter() - t0)
            late.append(s.sent_s - a.due_s)
            sent.append(s)
            try:
                engine.submit(req)
                live.append(s)
            except CapacityError:
                pass                      # req.error is set: a failure
            i += 1
        on_trace = state == "on"
        if not engine.scheduler.has_work():
            nxt = arrivals[i].due_s if i < len(arrivals) else seconds
            with _annotate(on_trace, "bench.wait_for_arrival"):
                time.sleep(max(0.0, min(nxt, seconds) - now))
            continue
        with _annotate(on_trace, "bench.step"):
            engine.step()
        end = time.perf_counter() - t0
        used.append(alloc.used_blocks)
        decode_ctx = []
        firsts = 0
        keep = []
        for s in live:
            n, had = len(s.req.out_tokens), len(s.token_s)
            if n < had:                   # preempted: tokens recomputed
                del s.token_s[n:]
                had = n
            for j in range(had + 1, n + 1):
                s.token_s.append(end)
                if j >= 2:
                    decode_ctx.append(len(s.req.prompt) + j - 1)
                else:
                    firsts += 1
            if not s.req.done:
                keep.append(s)
        live = keep
        fed = _contexts(engine, prefill_known)
        prefilled += len(fed)
        if on_trace:
            ticks.append(Tick(decode_ctx, fed, firsts))
    if state == "on":
        trace[3]()
        snaps.append(engine.telemetry.registry.snapshot())
    w = Window(t0=t0, seconds=time.perf_counter() - t0, sent=sent, ticks=ticks,
               late_s=late, compiles=counter.compiles - c0,
               cache_loads=counter.cache_loads - l0,
               prefill_tokens=prefilled, pool_blocks=alloc.capacity,
               pool_used_max=max(used, default=0),
               pool_used_mean=float(np.mean(used)) if used else 0.0)
    if len(snaps) == 2:
        w.registry_delta = _delta(*snaps)
    return w


def _delta(a: dict, b: dict) -> dict:
    """Histogram sums and counts accumulated between two snapshots."""
    out = {}
    for name, h in b.items():
        if isinstance(h, dict) and "count" in h and "sum" in h:
            h0 = a.get(name, {"count": 0, "sum": 0.0})
            out[name] = {"count": h["count"] - h0["count"],
                         "sum": h["sum"] - h0["sum"]}
    return out


def first_token_times(engine, window: Window) -> None:
    """Replace each request's first-token time by the engine's own record
    (taken when the prompt's token reached the host, before the decode
    step of the same tick)."""
    events = engine.telemetry.events
    t0_offset = time.perf_counter() - events.now() - window.t0
    recs = {r.rid: r for r in engine.drain_request_records()}
    recs.update(engine.telemetry.records)
    for s in window.sent:
        rec = recs.get(s.req.rid)
        if rec is not None and rec.first_token_ts is not None and s.token_s:
            s.token_s[0] = min(s.token_s[0], rec.first_token_ts + t0_offset)


def end_to_end(window: Window) -> dict:
    """Host-clock metrics of the window, over all requests and gaps."""
    close = window.seconds
    ttft, gaps, tokens, failed = [], [], 0, 0
    for s in window.sent:
        if s.req.error is not None:
            failed += 1
            ttft.append(float("inf"))
            continue
        times = [t for t in s.token_s if t <= close]
        tokens += len(times)
        # a request still waiting at the close counts with the wait so far
        ttft.append((times[0] if times else close) - s.due_s)
        gaps.extend(np.diff(times).tolist())
    return {
        "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)) if ttft else None,
        "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)) if gaps else None,
        "tokens_per_s": tokens / close,
        "prefill_tokens_per_s": window.prefill_tokens / close,
        "_attempted": len(window.sent), "_failed": failed,
        "_requests_finished": sum(1 for s in window.sent if s.req.done
                                  and s.req.error is None),
        "_tokens": tokens, "_gaps": len(gaps),
    }


def correctness(arch, spec, window: Window, seed: int, limits: dict,
                control: bool = False) -> tuple[bool, dict, dict]:
    """Reference check of a seeded sample of the finished requests, by
    ``arch``'s plain reference over its seeded payload. Call with the
    program's state freed. ``control`` also reads the float8 control over
    the same prompts and served tokens."""
    rng = np.random.default_rng(int(seed) ^ 0xC4EC)
    finished = [s.req for s in window.sent
                if s.req.done and s.req.error is None]
    lim = limits.get("check", {})
    sample = check.sample_requests(finished, rng,
                                   lim.get("min_tokens", 300),
                                   lim.get("max_requests", 6))
    readings = {}
    info = {"sampled_requests": len(sample),
            "sampled_tokens": int(sum(len(r.out_tokens) for r in sample))}
    if sample:
        gc.collect()
        raw = arch.raw_payload(spec, seed)
        seqs, pos, served = check.reference_inputs(sample)
        ref = arch.logits_at(raw, spec, seqs, pos, precision="f32")
        gaps = np.concatenate([check.served_gaps(r, s, spec.vocab)
                               for r, s in zip(ref, served)])
        readings["served_logit_gap"] = float(gaps.max())
        info["gap_median"] = float(np.median(gaps))
        if control:
            ctl = arch.logits_at(raw, spec, seqs, pos, precision="fp8")
            cg = np.concatenate([check.control_gaps(r, c, spec.vocab)
                                 for r, c in zip(ref, ctl)])
            info["control_served_logit_gap"] = float(cg.max())
    ok, shown = check.verdict(readings, limits["numbers"])
    return ok, shown, info
