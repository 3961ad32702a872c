"""Trace spans: context-manager wall-clock timers with nesting.

``SpanTimer.span("decode_tick")`` times a host-side phase and records it
into the owning registry under ``span.<dotted/path>`` — nested spans
record their full path (``span.decode_tick/device``), so a snapshot reads
as a flame-graph-shaped breakdown: each name carries a fixed-bucket
latency histogram (count, sum, p50/p99) and the parent/child sums expose
how much of a tick went to preparing inputs vs waiting on the device vs
emitting tokens.

Device alignment: while a profiler trace records (``start_trace`` /
``--trace-dir``), each tick of the serving engine is one
``jax.profiler.StepTraceAnnotation`` named ``serve.tick`` (``SpanTimer.tick``,
numbered by the engine's tick counter) and every span is a plain
``TraceAnnotation`` on the trace's clock, named ``serve.<path>`` inside a
tick (``serve.decode_tick/device``) and ``<path>`` outside one (the
quantizer's ``quant/<stage>``). The trace records no Python frames, so the
innermost host event at an idle gap of the device is one of these phases
(or the runtime's own). Annotations are only constructed while a trace
records — with no trace a span costs two ``perf_counter`` calls and one
histogram observe, and a tick nothing but its context manager.

Spans do NOT force device sync: jax dispatch is async, so a span around a
bare dispatch measures host time only. Phases that should account device
time must contain their own sync point (the engine's decode tick does —
it downloads the sampled tokens before the ``device`` span closes).
"""
from __future__ import annotations

import time
from contextlib import contextmanager

from repro.obs.metrics import LATENCY_BUCKETS_S, MetricsRegistry


class SpanTimer:
    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self._stack: list[str] = []
        self._tracing = False
        self._prefix = ""            # "serve." inside a tick

    # -- profiler integration ------------------------------------------------

    def start_trace(self, trace_dir: str):
        """Begin a device profiler trace with the Python tracer off: the
        host side keeps the annotations and the runtime's own events."""
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self._tracing = True

    def stop_trace(self):
        if self._tracing:
            import jax

            jax.profiler.stop_trace()
            self._tracing = False

    # -- spans ---------------------------------------------------------------

    @property
    def current_path(self) -> str:
        return "/".join(self._stack)

    @contextmanager
    def tick(self, num: int):
        """One tick of the serving engine: the step ``serve.tick`` with
        ``step_num=num`` on the trace; spans opened inside it are named
        ``serve.<path>`` there. Records nothing in the registry."""
        self._prefix = "serve."
        ann = None
        if self._tracing:
            import jax

            ann = jax.profiler.StepTraceAnnotation("serve.tick",
                                                   step_num=num)
            ann.__enter__()
        try:
            yield
        finally:
            if ann is not None:
                ann.__exit__(None, None, None)
            self._prefix = ""

    @contextmanager
    def span(self, name: str):
        assert "/" not in name, "span names must be single segments"
        self._stack.append(name)
        path = "/".join(self._stack)
        ann = None
        if self._tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(self._prefix + path)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            popped = self._stack.pop()
            assert popped == name, (popped, name)
            self.registry.histogram(f"span.{path}",
                                    LATENCY_BUCKETS_S).observe(dt)
