"""Paged-KV continuous-batching serving engine.

Architecture (PR 2): the KV cache is a pool of fixed-size blocks shared by
all ``max_batch`` slots (models/attention.PagedKVCache — block pools plus
per-slot page tables, threaded through the family assemblies' layer scans
as ordinary cache leaves). Host-side policy lives in serve/scheduler.py
(FCFS admission with capacity-aware rejection, chunked prefill, preempt
youngest on pool exhaustion) and serve/paged_cache.py (block allocator,
slot views, page-table pushes). The engine executes:

* **admit** — the request's prompt pages are allocated and the slot's
  recurrent-state rows are reset from a fresh template. Prompts are fed
  through jitted forwards in power-of-two chunks (O(log max_len) compile
  variants instead of one per distinct prompt length), one chunk per tick,
  as a B=1 slot view: page-table row + recurrent rows sliced, block pools
  shared — no more tiling a full max_batch-wide zero batch per prompt.
* **step** — one tick: admissions, at most one prefill chunk, then a
  single batched decode over every decode-phase slot with a *per-slot
  position vector*. Each slot writes at its own depth through its page
  table; there is no shared max-position write index, so staggered
  admissions leave no gaps and batched greedy decode is token-identical
  to serving each request alone (dense and hybrid families; MoE routing
  couples rows by design). Slots mid-prefill are routed to the scratch
  block for the tick and their recurrent rows restored afterwards.
* **run** — drives a request list to completion. Token throughput is
  counted where tokens are sampled (inside ``step``), so a request's
  final-tick token is never dropped from the stats.

Recurrent/ssm state leaves (mamba h/conv, xLSTM C/n/m, enc-dec cross K/V)
are O(1) per slot and stay slot-resident; only attention KV pages.

Pages may be stored low-bit (``kv_cache_bits`` 8/4 — int8 or packed-int4
codes + per-row per-kv-head scales — or "vq2": packed 4-bit codebook
indices over d=2 head-dim vectors against frozen engine-load-calibrated
codebooks; models/attention.KVQuantSpec): writes quantize in-graph at the
existing scatter sites and every read path dequantizes on the fly, so the
same pool bytes hold 2-4x (scalar) to ~10x (vq2) the pages
(``pool_bytes=`` sizes the allocator by budget instead of block count).

Telemetry: every engine owns an ``obs.Telemetry`` (pass your own
to share a registry, write a JSONL event stream, or disable it). Each
tick feeds the metrics registry — pool occupancy and the decode batch —
and its host-side phases run under spans (``span.admit``,
``span.prefill``, ``span.prompt_sample``, ``span.decode_tick/…``; the
device span closes after the sampled-token download, so it accounts
device time). On a profiler trace the tick is the step ``serve.tick``
and each phase is an annotation ``serve.<phase>`` (obs/spans.py).
Per-request lifecycle records (enqueue -> admit -> first token ->
finish) accumulate TTFT / inter-token latency and drain via
``drain_request_records()``; ``stats`` is a live property now —
counters and wall time accumulate per tick, so callers driving
``step()`` directly always read current numbers.

This is the end-to-end driver used by examples/quantize_and_serve.py to
demonstrate the paper's deployment claim: identical engine code serves
bf16 and GPTVQ-compressed weights.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import pages_per_block
from repro.models.attention import KVQuantSpec, PagedKVCache, PagedLayout
from repro.models.model_zoo import Model
from repro.obs import COUNT_BUCKETS, Telemetry
from repro.serve import paged_cache as pc
from repro.serve import sampling
from repro.serve.scheduler import CapacityError, Scheduler, Sequence
from repro.serve.serve_step import make_paged_decode, make_slot_prefill

SHARE_BUCKETS = tuple(i / 10 for i in range(1, 11))


@dataclasses.dataclass
class Request:
    rid: int | str
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    n: int = 1                   # parallel samples: n-1 forked children
                                 # share the prompt's KV blocks
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    error: str | None = None     # set when rejected (CapacityError)
    forks: list = dataclasses.field(default_factory=list)
                                 # the n-1 child Requests (rid "rid.i")


def calibrate_vq_codebooks(model: Model, params, cache, *,
                           page_size: int = 16, calib_len: int = 64,
                           vq_impl: str | None = "gather",
                           em_iters: int = 25):
    """Fit frozen vq2 KV-page codebooks from a short calibration capture
    and return ``cache`` with its codebook leaves replaced.

    A one-sequence slice of the deterministic calibration corpus
    (data/calibration.calibration_tokens) runs through a small fp32
    passthrough paged cache with an identity page table; the K/V rows
    each layer wrote are read back out of the capture pool, amax-
    normalized per (row, kv-head) — the same normalization the write
    path applies before assignment — split into d=2 vectors along the
    head dim, and EM-fit per (pool, kv-head) with core/codebook
    (Hessian weights 1, i.e. plain k-means; Mahalanobis seeding).

    Everything here is deterministic (fixed corpus, fixed seeding, fixed
    iteration count), so two engines over the same model produce
    bit-identical codebooks — which is what lets frozen-codebook
    assignment preserve the interleaved-vs-solo and preemption-replay
    token-identity invariants. Exposed at module level so tests and
    benches that build caches directly (no Engine) calibrate the exact
    same way."""
    from repro.core.codebook import init_codebook
    from repro.data.calibration import calibration_tokens
    from repro.kernels import kv_quant as kvq

    npc = -(-calib_len // page_size)
    cap = model.init_cache(1, npc * page_size, dtype=jnp.float32,
                           paged=PagedLayout(npc + 1, page_size))
    cap = pc.push_page_table(cap, np.arange(1, npc + 1,
                                            dtype=np.int32)[None])
    toks = calibration_tokens(model.cfg.vocab_size, n_sequences=1,
                              seq_len=calib_len)
    _, cap, _ = model.forward(
        params, {"tokens": toks}, cache=cap,
        pos=jnp.zeros((1,), jnp.int32), paged_impl="gather",
        vq_matmul_impl=vq_impl)

    def fit(pool):
        # pool (*stack, num_blocks, page_size, KV, hd): blocks 1..npc
        # hold the capture's first calib_len rows in logical order
        stack = pool.shape[:-4]
        nb, ps, KV, hd = pool.shape[-4:]
        rows = pool[..., 1:, :, :, :].reshape(*stack, (nb - 1) * ps, KV, hd)
        x = jnp.moveaxis(rows[..., :calib_len, :, :], -2, -3)
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        xn = x / jnp.where(amax > 0, amax, 1.0)
        X = xn.reshape(*stack, KV, -1, kvq.VQ_D)
        flat = X.reshape((-1,) + X.shape[-2:])
        cbs = jax.vmap(lambda Xi: init_codebook(
            Xi, jnp.ones_like(Xi), k=kvq.VQ_K, iters=em_iters))(flat)
        return cbs.reshape(*stack, KV, kvq.VQ_K, kvq.VQ_D).astype(
            jnp.float32)

    def inject(dst, src):
        if isinstance(dst, PagedKVCache):
            return dst._replace(k_codebook=fit(src.k),
                                v_codebook=fit(src.v))
        return dst

    return jax.tree.map(inject, cache, cap,
                        is_leaf=lambda x: isinstance(x, PagedKVCache))


class Engine:
    def __init__(self, model: Model, params, *, max_batch: int = 8,
                 max_len: int = 512, eos_id: int | None = None, seed: int = 0,
                 page_size: int = 16, num_blocks: int | None = None,
                 pool_bytes: int | None = None,
                 prefill_chunk: int = 64, paged_attn_impl: str = "gather",
                 kv_cache_bits: int = 16, vq_matmul_impl: str = "gather",
                 prefix_cache: bool = False,
                 telemetry: Telemetry | None = None):
        """``paged_attn_impl`` selects the decode attention read path over
        the paged KV pool, threaded into the jitted decode closure (see
        models/attention._paged_apply): "gather" (XLA logical-view gather,
        the portable default), "pallas" (fused in-kernel page gather —
        kernels/paged_attention.py; interpret mode off-TPU, tests only),
        "xla" (the kernel's oracle routed through the same fused
        dispatch), or "fused" (resolves to "pallas" on TPU and "xla"
        elsewhere — what production serving should pass). Prefill always
        uses the gather path.

        ``kv_cache_bits`` selects the page storage format (16 =
        passthrough dtype, 8/4 = int8/packed-int4 code pages with per-row
        per-kv-head f32 scales; the string "vq2" = vector-quantized pages
        holding 4-bit codebook indices over d=2 head-dim vectors, 2 bits
        per value; models/attention.KVQuantSpec). It rides on the
        PagedLayout into every family's ``init_cache``, so all read and
        write paths — including the fused kernel — see quantized pages
        with no forward-signature change. For "vq2" the per-(pool,
        kv-head) codebooks are EM-calibrated once here at construction
        (calibrate_vq_codebooks) and frozen before any serving write.

        ``pool_bytes`` sizes the pool by a per-layer byte budget instead
        of a block count: the allocator then exposes however many pages
        fit, which is where a quantized cache converts its 2-4x byte
        saving into concurrent-slot / context-length headroom. Mutually
        exclusive with ``num_blocks``.

        ``vq_matmul_impl`` selects the execution path for VQ-packed
        (GPTVQ) weight leaves: "gather" (per-layer-slice dense
        materialization via core/vq_linear.dequant_tree — the portable
        default), "xla" (fused-boundary reconstruct-per-matmul over
        engine-prepped FusedVQLinear leaves), "pallas" (the fused
        VMEM-decode kernel, kernels/vq_dequant_matmul.py), or "fused"
        (resolves to "pallas" on TPU, "xla" elsewhere). Any non-"gather"
        choice runs the one-time ``prepare_fused_tree`` prep pass at
        construction — cb_scale folding, code unpack+offset folding, and
        blockwise-scale-plane expansion all happen here ONCE, so per-tick
        work is zero (see core/vq_linear's module docstring for the
        contract).

        ``prefix_cache=True`` attaches a serve/prefix_cache.PrefixCache:
        admission looks the prompt up in a radix tree over full pages and
        serves matched prefixes from existing pool blocks — the new
        sequence's page table points at them (refcounted, copy-on-write
        by construction: sharing stops before the first writable page)
        and prefill starts past the shared boundary. Inert for
        recurrent-state families: any cache leaf outside the PagedKVCache
        pools is slot-resident state that integrates every prompt token,
        which a page-table share cannot replay — the engine detects this
        structurally and keeps the flag off rather than serving from
        stale state.

        ``telemetry`` is the obs.Telemetry sink the engine reports into
        (metrics registry + spans + request records + optional JSONL
        event stream). None constructs a private enabled one; pass
        ``Telemetry(enabled=False)`` to measure the instrumentation cost
        itself (the bench's ``obs_overhead`` cell)."""
        from repro.core import vq_linear as vql_mod

        if paged_attn_impl == "fused":
            paged_attn_impl = ("pallas" if jax.default_backend() == "tpu"
                               else "xla")
        assert paged_attn_impl in ("gather", "xla", "pallas"), paged_attn_impl
        self.paged_attn_impl = paged_attn_impl
        if vq_matmul_impl == "fused":
            vq_matmul_impl = ("pallas" if jax.default_backend() == "tpu"
                              else "xla")
        assert vq_matmul_impl in ("gather", "xla", "pallas"), vq_matmul_impl
        self.vq_matmul_impl = vq_matmul_impl
        if vq_matmul_impl != "gather" and vql_mod.tree_has_vq(params):
            params = vql_mod.prepare_fused_tree(params, impl=vq_matmul_impl)
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id
        self.key = jax.random.PRNGKey(seed)
        kv_spec = KVQuantSpec.of(kv_cache_bits)
        self.kv_cache_bits = kv_cache_bits

        dtype = jnp.float32
        n_pages = -(-max_len // page_size)
        if pool_bytes is not None:
            assert num_blocks is None, \
                "pass num_blocks or pool_bytes, not both"
            num_blocks = pc.pool_blocks_for_bytes(
                pool_bytes, model.cfg, page_size, kv_spec.fmt, dtype)
        elif num_blocks is None:
            # default pool holds every slot at full depth (+ scratch);
            # pass a smaller pool to oversubscribe and exercise preemption
            num_blocks = max_batch * n_pages + 1
        self.layout = PagedLayout(num_blocks=num_blocks,
                                  page_size=page_size, kv=kv_spec)
        self.n_pages = n_pages

        self.cache = model.init_cache(max_batch, max_len, dtype=dtype,
                                      paged=self.layout)
        if kv_spec.vq:
            # calibrate-then-freeze: the codebook leaves are replaced
            # exactly once, before any serving write, so every subsequent
            # page write assigns against the same frozen tables
            self.cache = calibrate_vq_codebooks(
                model, params, self.cache, page_size=page_size,
                calib_len=min(64, max_len), vq_impl=self.vq_matmul_impl)
        self.axes = pc.batch_axes(model, max_batch, max_len, dtype,
                                  self.layout)
        # B=1 template for resetting a slot's recurrent rows on admission
        # (tiny pool: slot_merge(shared=False) never reads template pools)
        self._slot_template = model.init_cache(
            1, max_len, dtype=dtype, paged=PagedLayout(2, page_size,
                                                       kv=kv_spec))

        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self._spans = self.telemetry.spans
        reg = self.telemetry.registry
        self._m_used = reg.gauge("serve.pool_used_blocks")
        self._m_free = reg.gauge("serve.pool_free_blocks")
        self._m_occ = reg.gauge("serve.pool_occupancy")
        self._m_dec_batch = reg.histogram("serve.decode_batch",
                                          COUNT_BUCKETS)
        # share of the paged-attention kernel's page blocks a decode tick
        # reads (kernels/paged_attention.py: a slot reads its blocks up to
        # the one holding pos, an idle slot its first)
        self._m_live_blocks = reg.histogram("serve.attn_live_block_share",
                                            SHARE_BUCKETS)
        cfg = model.cfg
        ppb = pages_per_block(cfg.n_heads, cfg.hd, page_size, cfg.n_kv_heads,
                              n_pages, dtype, kv_spec.fmt)
        self._block_rows = ppb * page_size
        self._n_attn_blocks = -(-n_pages // ppb)
        self._m_shared = reg.gauge("serve.shared_blocks")

        allocator = pc.BlockAllocator(num_blocks)
        # structural recurrent-state detection: any cache leaf outside the
        # PagedKVCache pools is per-slot state (mamba h/conv, xLSTM C/n/m,
        # enc-dec cross K/V) that integrates every prompt token — a
        # page-table share can't replay it, so prefix sharing stays inert
        has_slot_state = any(
            not isinstance(l, PagedKVCache)
            for l in jax.tree.leaves(
                self.cache,
                is_leaf=lambda x: isinstance(x, PagedKVCache)))
        self.prefix_cache = None
        if prefix_cache and not has_slot_state:
            from repro.serve.prefix_cache import PrefixCache
            self.prefix_cache = PrefixCache(allocator, page_size)
        self._pending_forks: dict = {}   # parent rid -> child Requests

        self.scheduler = Scheduler(
            max_batch=max_batch, max_len=max_len, page_size=page_size,
            allocator=allocator, prefix_cache=self.prefix_cache,
            prefill_chunk=prefill_chunk,
            # attention-only families pad the final prefill chunk to its
            # power-of-two bucket (masked out exactly); recurrent-state
            # families must feed exact tokens (see scheduler module doc)
            pad_prefill=model.cfg.family not in ("ssm", "hybrid"),
            # direct scheduler.submit callers (bench, fuzz suites) still
            # get enqueue records — the hook is the single entry point
            on_submit=lambda req: self.telemetry.on_enqueue(
                req.rid, len(req.prompt), req.max_new_tokens))
        # fully-compiled tick fns: decode traces once at (max_batch, 1);
        # prefill traces per power-of-two chunk width — O(log) variants.
        # The cache arg is donated: XLA updates the block pools in place
        # instead of copying the whole pool every tick (the engine always
        # replaces self.cache with the returned tree, so the old buffers
        # are never read again).
        self._decode_fn = jax.jit(
            make_paged_decode(model, self.axes,
                              paged_impl=self.paged_attn_impl,
                              vq_impl=self.vq_matmul_impl),
            donate_argnums=(2,))
        self._prefill_fn = jax.jit(
            make_slot_prefill(model, self.axes,
                              vq_impl=self.vq_matmul_impl),
            donate_argnums=(2,))
        self._sample = jax.jit(
            lambda k, logits, t: sampling.sample(k, logits, temperature=t))

        self.last_tok = np.zeros(max_batch, np.int32)
        self.ticks = 0
        self._decode_ticks = 0
        self._tokens = 0
        self._prefill_chunks = 0
        self._preemptions = 0
        self._wall_s = 0.0
        # host->device upload cache for slow-changing tick inputs (page
        # tables, keep masks, temperatures): at steady-state decode these
        # only change when a slot crosses a page boundary or a request
        # enters/leaves, so re-uploading every tick was pure host overhead
        self._dev_cache: dict = {}

    def _dev(self, name: str, arr: np.ndarray):
        """Device copy of ``arr``, re-uploaded only when the host value
        changed since the last tick (cheap array_equal on tiny arrays)."""
        ent = self._dev_cache.get(name)
        if ent is None or not np.array_equal(ent[0], arr):
            ent = (arr.copy(), jnp.asarray(arr))
            self._dev_cache[name] = ent
        return ent[1]

    @property
    def stats(self) -> dict:
        """Live counters — always current, whether the engine is driven
        by ``run()`` or tick-by-tick via ``step()`` (wall time and every
        counter accumulate continuously inside ``step``)."""
        alloc = self.scheduler.allocator
        pfx = self.prefix_cache
        return {"wall_s": self._wall_s, "decode_ticks": self._decode_ticks,
                "tokens": self._tokens, "ticks": self.ticks,
                "prefill_chunks": self._prefill_chunks,
                "preemptions": self._preemptions,
                "queue_depth": len(self.scheduler.queue),
                "pool_used_blocks": alloc.used_blocks,
                "pool_free_blocks": alloc.free_blocks,
                "shared_blocks": alloc.shared_blocks,
                "prefix_hits": pfx.hits if pfx else 0,
                "prefix_misses": pfx.misses if pfx else 0,
                "prefix_hit_tokens": pfx.hit_tokens if pfx else 0,
                "prefix_evictions": pfx.evictions if pfx else 0,
                "prefix_cached_blocks": pfx.cached_blocks if pfx else 0}

    def drain_request_records(self):
        """Return-and-clear finished per-request lifecycle records
        (obs.RequestRecord: TTFT, mean ITL, tokens, preemptions, finish
        reason)."""
        return self.telemetry.drain_finished()

    # -- admission ---------------------------------------------------------

    def submit(self, req: Request):
        """Queue a request (telemetry records the enqueue). Raises
        CapacityError — after emitting the ``reject`` event and marking
        the request — if it can never fit this engine configuration.

        ``req.n > 1`` creates n-1 forked children (rid "rid.i") sampling
        the same prompt; they are held back until the parent's prefill
        completes — by then every full prompt page is registered in the
        prefix cache, so each child admits by sharing the parent's
        blocks and prefills only the final partial page. Without a
        prefix cache forks still run (and stay greedy-identical); they
        just re-prefill the prompt privately."""
        try:
            self.scheduler.submit(req)
        except CapacityError as e:
            req.error = str(e)
            req.done = True
            self.telemetry.on_reject(req.rid, str(e))
            raise
        if req.n > 1:
            children = []
            for i in range(1, req.n):
                child = Request(rid=f"{req.rid}.{i}", prompt=req.prompt,
                                max_new_tokens=req.max_new_tokens,
                                temperature=req.temperature)
                children.append(child)
                self.telemetry.on_enqueue(child.rid, len(child.prompt),
                                          child.max_new_tokens)
            req.forks = children
            self._pending_forks[req.rid] = children

    def admit(self, req: Request) -> bool:
        """Place a request into a free slot (no prefill compute yet —
        the prompt streams in chunk-per-tick during ``step``). Raises
        CapacityError (after emitting the ``reject`` event) if the
        request can never fit; returns False when no slot/blocks are
        free right now."""
        try:
            self.scheduler.validate(req)
        except CapacityError as e:
            req.error = str(e)
            req.done = True
            self.telemetry.on_reject(req.rid, str(e))
            raise
        seq = self.scheduler.try_place(req)
        if seq is None:
            return False
        self._admit_seq(seq)
        return True

    def _admit_seq(self, seq: Sequence):
        """Post-placement bookkeeping shared by ``admit`` and ``step``:
        telemetry + prefix-hit accounting + slot state reset."""
        self.telemetry.on_admit(seq.req.rid, seq.slot)
        if seq.shared_tokens:
            self.telemetry.on_prefix_hit(
                seq.req.rid, seq.shared_tokens // self.scheduler.page_size,
                seq.shared_tokens)
        self._reset_slot(seq)

    def _reset_slot(self, seq: Sequence):
        self.cache = pc.slot_merge(self.cache, self._slot_template,
                                   self.axes, seq.slot, shared=False)

    def _page_table(self, phases: tuple) -> np.ndarray:
        """Host page table with rows populated only for the given phases;
        everything else points at the scratch block."""
        t = np.zeros((self.max_batch, self.n_pages), np.int32)
        for s in self.scheduler.active():
            if s.phase in phases:
                t[s.slot, : len(s.pages)] = s.pages
        return t

    # -- one tick ----------------------------------------------------------

    def step(self):
        t0 = time.perf_counter()
        with self._spans.tick(self.ticks):
            self._tick()
        self._wall_s += time.perf_counter() - t0

    def _tick(self):
        # admission: queue -> free slots (prefix-cache lookup, page
        # allocation) and each admitted slot's state reset
        with self._spans.span("admit"):
            for seq in self.scheduler.admit_from_queue():
                self._admit_seq(seq)
        # one chunk per prefilling slot per tick: a burst of admissions
        # drains its prompts concurrently, while a single long prompt can
        # never stall the decode cohort by more than one chunk
        prefilling = sorted(
            (s for s in self.scheduler.active() if s.phase == "prefill"),
            key=lambda s: s.order)
        done = []
        if prefilling:
            # one table serves every chunk this tick: nothing allocates or
            # finishes between chunks of the same tick
            table = self._page_table(("prefill", "decode"))
            # the host's dispatch of the tick's chunks: nothing in it waits
            # for the device, whose prefill time is on the trace
            with self._spans.span("prefill"):
                for seq in prefilling:
                    last_logits = self._prefill_chunk(seq, table)
                    if last_logits is not None:
                        done.append((seq, last_logits))
        if done:
            # sample every prompt that completed this tick in ONE batched
            # draw: per-completion syncs serialized the prefill pipeline
            with self._spans.span("prompt_sample"):
                self.key, sub = jax.random.split(self.key)
                toks = np.asarray(self._sample(
                    sub, jnp.stack([l for _, l in done]),
                    jnp.asarray([s.req.temperature for s, _ in done],
                                jnp.float32)))
            for (seq, _), t in zip(done, toks):
                seq.phase = "decode"
                self._on_prompt_done(seq)
                self._emit(seq, int(t))
        self._decode_tick()
        self.ticks += 1
        # per-tick registry feed: occupancy gauges mirror the allocator's
        # accounting exactly (fuzz-tested invariant)
        alloc = self.scheduler.allocator
        used = alloc.used_blocks
        self._m_used.set(used)
        self._m_free.set(alloc.free_blocks)
        self._m_occ.set(used / alloc.capacity if alloc.capacity else 0.0)
        self._m_shared.set(alloc.shared_blocks)

    def _on_prompt_done(self, seq: Sequence):
        """Prefill just completed: register the prompt's full pages in
        the prefix cache (they are final — decode writes only ever land
        past prompt_len, in the tail partial page or fresh blocks) and
        release any forked children held for this parent. Insertion
        happens BEFORE the first ``_emit`` so the cache's references are
        taken even if the request finishes on its first sampled token."""
        if self.prefix_cache is not None:
            self.prefix_cache.insert(seq.req.prompt, seq.pages)
        children = self._pending_forks.pop(seq.req.rid, None)
        if children:
            # queue front (reversed keeps child order): they share every
            # full prompt page, so placing them next maximizes the time
            # those blocks stay hot
            for child in reversed(children):
                self.scheduler.queue.appendleft(child)

    def _prefill_chunk(self, seq: Sequence, table: np.ndarray):
        """Feed the next chunk; returns the (V,) next-token logits when the
        prompt is complete, else None."""
        size, real = self.scheduler.prefill_chunk_len(seq)
        start = seq.pos
        chunk = np.zeros(size, np.int32)
        chunk[:real] = np.asarray(seq.req.prompt[start:start + real])
        last_logits, self.cache = self._prefill_fn(
            self.params, jnp.asarray(chunk[None]), self.cache, seq.slot,
            start, real - 1, self._dev("table_pf", table))
        seq.pos += real
        self._prefill_chunks += 1
        return last_logits if seq.pos == seq.prompt_len else None

    def _emit(self, seq: Sequence, tok: int):
        req = seq.req
        req.out_tokens.append(tok)
        self.last_tok[seq.slot] = tok
        self._tokens += 1
        self.telemetry.on_token(req.rid)
        eos = self.eos_id is not None and tok == self.eos_id
        if len(req.out_tokens) >= req.max_new_tokens or eos:
            req.done = True
            self.scheduler.finish(seq)
            self.telemetry.on_finish(req.rid, "eos" if eos else "length")

    def _decode_tick(self):
        decoding = [s for s in self.scheduler.active()
                    if s.phase == "decode"]
        # supply every decoding slot with a block for its write position,
        # preempting youngest-first when the pool runs dry
        for s in sorted(decoding, key=lambda s: s.order):
            if self.scheduler.running[s.slot] is not s:
                continue  # already preempted this tick
            for victim in self.scheduler.ensure_block(s):
                self._on_preempt(victim)
        decoding = [s for s in self.scheduler.active()
                    if s.phase == "decode"]
        if not decoding:
            return
        self._m_dec_batch.observe(len(decoding))
        with self._spans.span("decode_tick"):
            with self._spans.span("host_prep"):
                pos = np.zeros(self.max_batch, np.int32)
                temps = np.zeros(self.max_batch, np.float32)
                # slots mid-prefill decode on garbage this tick (their
                # writes are routed to scratch by the table; their
                # recurrent-state rows are restored inside the compiled
                # step via keep_mask)
                keep = np.zeros(self.max_batch, bool)
                for s in self.scheduler.active():
                    if s.phase == "decode":
                        pos[s.slot] = s.pos
                        temps[s.slot] = s.req.temperature
                    else:
                        keep[s.slot] = True
                self._m_live_blocks.observe(
                    float(np.sum(pos // self._block_rows + 1))
                    / (self.max_batch * self._n_attn_blocks))
                toks = jnp.asarray(self.last_tok[:, None], jnp.int32)
                args = (self.params, toks, self.cache, jnp.asarray(pos),
                        self._dev("table_dec",
                                  self._page_table(("decode",))),
                        self._dev("keep", keep), self.key,
                        self._dev("temps", temps))
            with self._spans.span("device"):
                # closes after the (B,) token download — the one sync
                # point of the tick — so this span accounts device time
                nxt, self.key, self.cache = self._decode_fn(*args)
                nxt = np.asarray(nxt)
            with self._spans.span("emit"):
                for s in decoding:
                    s.pos += 1
                    self._emit(s, int(nxt[s.slot]))
        self._decode_ticks += 1

    def _on_preempt(self, victim: Sequence):
        self._preemptions += 1
        self._tokens -= len(victim.req.out_tokens)
        self.telemetry.on_preempt(victim.req.rid)
        victim.req.out_tokens.clear()
        victim.req.done = False

    # -- teardown ----------------------------------------------------------

    def close(self):
        """Release engine-held pool state. Clearing the prefix cache
        returns its block references to the allocator AND zeroes its
        LRU clock + hit/miss/eviction counters, so a restarted engine
        (or a launcher serving several engines back to back) never
        reports stale prefix stats. Idempotent."""
        if self.prefix_cache is not None:
            self.prefix_cache.clear()
        self._dev_cache.clear()

    # -- driver ------------------------------------------------------------

    def run(self, requests: list[Request], max_ticks: int = 10_000):
        """Drive all requests to completion; returns them. Requests that
        can never fit are rejected gracefully (``req.error`` set)."""
        for req in requests:
            try:
                self.submit(req)
            except CapacityError:
                pass  # submit marked the request + emitted the reject
        self.telemetry.start_trace()
        try:
            while self.scheduler.has_work() and self.ticks < max_ticks:
                self.step()
        finally:
            self.telemetry.stop_trace()
            self.telemetry.events.flush()
        return requests
