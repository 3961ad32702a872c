"""Unit suite for the obs/ telemetry subsystem: histogram bucketing,
registry snapshot schema, span nesting, JSONL event schema round-trips,
request-record lifecycle (incl. the recompute-style preempt reset), and
the dispatch-counter registry. Everything here is host-only — no jax in
the loop — so the suite doubles as the schema contract for the CI
metrics smoke step.
"""
import json
import time

import pytest

from repro.obs import (
    COUNT_BUCKETS,
    LATENCY_BUCKETS_S,
    EventLog,
    Histogram,
    MetricsRegistry,
    RequestRecord,
    SpanTimer,
    Telemetry,
    read_jsonl,
    validate_event,
    validate_metrics_snapshot,
)
from repro.obs.dispatch import (
    register_dispatch,
    reset_dispatch_counters,
    snapshot_dispatch_counters,
)


class TestHistogram:
    def test_bucketing_edges_and_overflow(self):
        h = Histogram(buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 100.0):
            h.observe(v)
        # edges are upper-EXCLUSIVE (bisect_right): bucket 0 holds
        # v < 1.0, a value equal to an edge rolls into the next bucket,
        # and v >= the last edge lands in the overflow slot
        assert h.counts == [1, 2, 2, 2]
        assert h.count == 7
        assert h.min == 0.5 and h.max == 100.0
        assert h.sum == pytest.approx(112.0)
        assert h.mean == pytest.approx(112.0 / 7)

    def test_quantiles_bucket_resolution(self):
        h = Histogram(buckets=(1.0, 2.0, 4.0))
        for _ in range(99):
            h.observe(0.5)
        h.observe(50.0)
        assert h.quantile(0.5) == 1.0     # upper edge of the p50 bucket
        assert h.quantile(1.0) == 50.0    # overflow reports the exact max
        # degenerate rank 0 still reports the first nonempty bucket's edge
        assert h.quantile(0.0) == 1.0

    def test_empty_histogram(self):
        h = Histogram()
        assert h.mean == 0.0 and h.quantile(0.5) == 0.0
        j = h.to_json()
        assert j["count"] == 0 and sum(j["counts"]) == 0

    def test_default_buckets_sorted(self):
        assert list(LATENCY_BUCKETS_S) == sorted(LATENCY_BUCKETS_S)
        assert list(COUNT_BUCKETS) == sorted(COUNT_BUCKETS)


class TestRegistry:
    def test_get_or_create_identity(self):
        reg = MetricsRegistry()
        assert reg.counter("a") is reg.counter("a")
        assert reg.gauge("g") is reg.gauge("g")
        assert reg.histogram("h") is reg.histogram("h")

    def test_snapshot_roundtrip_and_validate(self):
        reg = MetricsRegistry()
        reg.counter("tokens").inc(5)
        reg.gauge("depth").set(3)
        reg.histogram("lat").observe(0.01)
        snap = json.loads(json.dumps(reg.snapshot()))  # JSON round-trip
        validate_metrics_snapshot(snap)
        assert snap["tokens"] == 5 and snap["depth"] == 3
        assert snap["lat"]["count"] == 1

    def test_validate_rejects_malformed(self):
        with pytest.raises(ValueError):
            validate_metrics_snapshot(
                {"h": {"buckets": [1.0], "counts": [1], "sum": 1.0,
                       "count": 1}})  # counts missing the overflow slot
        with pytest.raises(ValueError):
            validate_metrics_snapshot(
                {"h": {"buckets": [1.0], "counts": [1, 1], "sum": 1.0,
                       "count": 3}})  # counts don't sum to count

    def test_disabled_registry_is_null(self):
        reg = MetricsRegistry(enabled=False)
        c = reg.counter("x")
        c.inc(100)
        assert c.value == 0
        reg.histogram("h").observe(1.0)
        assert reg.snapshot() == {}


class TestSpans:
    def test_nesting_paths_and_timing(self):
        reg = MetricsRegistry()
        spans = SpanTimer(reg)
        with spans.span("tick"):
            assert spans.current_path == "tick"
            with spans.span("upload"):
                assert spans.current_path == "tick/upload"
                time.sleep(0.002)
            with spans.span("device"):
                pass
        assert spans.current_path == ""
        snap = reg.snapshot()
        assert set(snap) == {"span.tick", "span.tick/upload",
                             "span.tick/device"}
        assert snap["span.tick/upload"]["sum"] >= 0.002
        # parent covers its children
        assert snap["span.tick"]["sum"] >= snap["span.tick/upload"]["sum"]

    def test_stack_unwinds_on_exception(self):
        spans = SpanTimer(MetricsRegistry())
        with pytest.raises(RuntimeError):
            with spans.span("outer"):
                with spans.span("inner"):
                    raise RuntimeError("boom")
        assert spans.current_path == ""

    def test_single_segment_names_enforced(self):
        spans = SpanTimer(MetricsRegistry())
        with pytest.raises(AssertionError):
            with spans.span("a/b"):
                pass

    def test_tick_is_a_step_and_phases_are_annotations(self, monkeypatch):
        """While a trace records, a tick is one StepTraceAnnotation named
        ``serve.tick`` and each span inside it a plain TraceAnnotation
        named ``serve.<path>``; the registry keeps ``span.<path>`` and
        nothing for the tick; outside a tick a span is annotated by its
        bare path."""
        import jax

        seen = []

        class Ann:
            def __init__(self, kind, name, **kw):
                self.entry = (kind, name, kw)

            def __enter__(self):
                seen.append(("enter",) + self.entry)

            def __exit__(self, *exc):
                seen.append(("exit", self.entry[1]))

        monkeypatch.setattr(jax.profiler, "StepTraceAnnotation",
                            lambda name, **kw: Ann("step", name, **kw))
        monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                            lambda name, **kw: Ann("ann", name, **kw))
        reg = MetricsRegistry()
        spans = SpanTimer(reg)
        with spans.tick(3):
            with spans.span("admit"):
                pass
        assert seen == []                    # no trace: no annotation
        spans._tracing = True
        with spans.tick(4):
            with spans.span("decode_tick"):
                with spans.span("emit"):
                    pass
        with spans.span("quant"):
            pass
        assert seen == [
            ("enter", "step", "serve.tick", {"step_num": 4}),
            ("enter", "ann", "serve.decode_tick", {}),
            ("enter", "ann", "serve.decode_tick/emit", {}),
            ("exit", "serve.decode_tick/emit"),
            ("exit", "serve.decode_tick"),
            ("exit", "serve.tick"),
            ("enter", "ann", "quant", {}),
            ("exit", "quant")]
        assert set(reg.snapshot()) == {"span.admit", "span.decode_tick",
                                       "span.decode_tick/emit",
                                       "span.quant"}


class TestEvents:
    def test_jsonl_schema_roundtrip(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        log = EventLog(path)
        log.emit("enqueue", rid=1, prompt_len=5, max_new_tokens=4)
        log.emit("admit", rid=1, slot=0)
        log.emit("first_token", rid=1, ttft_s=0.01)
        log.emit("finish", rid=1, tokens=4, reason="length", ttft_s=0.01,
                 itl_mean_s=0.002, preemptions=0)
        log.close()
        evs = read_jsonl(path)  # validates every line
        assert [e["event"] for e in evs] == ["enqueue", "admit",
                                             "first_token", "finish"]
        # timestamps are monotonic within one log
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)

    def test_validate_event_rejects_bad(self):
        with pytest.raises(ValueError):
            validate_event({"event": "nope", "ts": 0.0})
        with pytest.raises(ValueError):
            validate_event({"event": "admit", "ts": 0.0})  # missing fields
        with pytest.raises(ValueError):
            validate_event({"event": "finish", "ts": 0.0, "rid": 1,
                            "tokens": 1, "reason": "whatever",
                            "ttft_s": 0, "itl_mean_s": 0,
                            "preemptions": 0})  # unknown finish reason
        with pytest.raises(ValueError):
            validate_event({"event": "admit", "rid": 1, "slot": 0})  # no ts

    def test_ring_buffer_bounds_memory(self):
        log = EventLog(keep=10)
        for i in range(50):
            log.emit("token", rid=i)
        assert len(log.events) == 10
        assert log.events[-1]["rid"] == 49

    def test_disabled_log_is_free(self, tmp_path):
        path = str(tmp_path / "nope.jsonl")
        log = EventLog(path, enabled=False)
        log.emit("token", rid=1)
        assert log.events == []
        import os
        assert not os.path.exists(path)  # disabled never opens the file


class TestRequestRecord:
    def test_lifecycle_and_preempt_reset(self):
        r = RequestRecord(rid=1, prompt_len=5, max_new_tokens=8)
        r.enqueue_ts = 0.0
        r.first_token_ts = 1.0
        r.last_token_ts = 3.0
        r.tokens = 5
        assert r.ttft_s == 1.0
        assert r.itl_mean_s == pytest.approx(0.5)
        r.on_preempt()  # recompute-style: tokens discarded and replayed
        assert r.preemptions == 1
        assert r.tokens == 0 and r.first_token_ts is None
        assert r.ttft_s is None and r.itl_mean_s is None
        j = r.to_json()
        assert j["rid"] == 1 and j["preemptions"] == 1

    def test_itl_undefined_below_two_tokens(self):
        r = RequestRecord(rid=1)
        r.first_token_ts = r.last_token_ts = 1.0
        r.tokens = 1
        assert r.itl_mean_s is None


class TestTelemetryLifecycle:
    def test_token_accounting_through_preempt(self):
        tel = Telemetry()
        tel.on_enqueue(1, 5, 8)
        tel.on_admit(1, 0)
        for _ in range(3):
            tel.on_token(1)
        assert tel.request_token_total() == 3
        tel.on_preempt(1)
        # recompute-style: the counter and the record reset together
        assert tel.request_token_total() == 0
        assert tel.registry.counter("serve.tokens").value == 0
        for _ in range(8):
            tel.on_token(1)
        tel.on_finish(1, "length")
        assert tel.request_token_total() == 8
        recs = tel.drain_finished()
        assert len(recs) == 1 and recs[0].tokens == 8
        assert recs[0].preemptions == 1
        assert tel.drain_finished() == []  # drained

    def test_direct_admit_without_enqueue(self):
        # bench/fuzz drivers used to call scheduler.submit directly;
        # on_admit must synthesize the record
        tel = Telemetry()
        tel.on_admit(7, 0)
        tel.on_token(7)
        tel.on_finish(7, "eos")
        rec = tel.drain_finished()[0]
        assert rec.rid == 7 and rec.ttft_s is not None

    def test_disabled_telemetry_noops(self):
        tel = Telemetry(enabled=False)
        tel.on_enqueue(1, 5, 8)
        tel.on_admit(1, 0)
        tel.on_token(1)
        tel.on_finish(1, "length")
        assert tel.drain_finished() == []
        assert tel.metrics_snapshot()["metrics"] == {}

    def test_snapshot_has_dispatch_section(self):
        snap = Telemetry().metrics_snapshot()
        assert set(snap) == {"metrics", "dispatch"}
        for source, counts in snap["dispatch"].items():
            assert all(isinstance(v, int) for v in counts.values()), source


class TestDispatchRegistry:
    def test_register_idempotent_and_live(self):
        reset_dispatch_counters()
        c1 = register_dispatch("t_obs", ("a", "b"))
        c2 = register_dispatch("t_obs", ("a", "b"))
        assert c1 is c2  # owners keep bumping the same dict
        c1["a"] += 3
        assert snapshot_dispatch_counters()["t_obs"]["a"] == 3

    def test_snapshot_is_a_copy(self):
        register_dispatch("t_obs2", ("x",))["x"] += 1
        snap = snapshot_dispatch_counters()
        snap["t_obs2"]["x"] += 100
        assert snapshot_dispatch_counters()["t_obs2"]["x"] == 1

    def test_reset_zeros_in_place(self):
        counts = register_dispatch("t_obs3", ("x", "y"))
        counts["x"] += 5
        reset_dispatch_counters()
        assert counts == {"x": 0, "y": 0}  # same dict object, zeroed
        counts["y"] += 1  # owners' references stay live after reset
        assert snapshot_dispatch_counters()["t_obs3"]["y"] == 1
