"""What the program labels on the device trace, reduced for the per-layer
metrics: the named scope of each device operation, and the serving
engine's host spans.

Scopes: the program names its layers with ``jax.named_scope``
(``layer_cache_read``, ``attention``, ``mlp`` ...). XLA keeps the path in
each HLO instruction's ``metadata={op_name="jit(decode)/while/body/.../
layer_cache_read/..."}``, and the trace names each device operation by its
HLO instruction (``bench/trace.py`` keeps that name). ``op_scopes`` reads
the map from a program's optimized HLO text (``Compiled.as_text()``); a
device operation inside a run of that program is under a scope when the
scope is a segment of its path. ``scope_seconds`` sums such operations.

Host spans: the engine's tick is the step ``serve.tick`` and each of its
phases an annotation ``serve.<phase>`` (``src/repro/obs/spans.py``).
``idle_in_spans`` puts each idle gap of the device in the traced window to
the innermost span open at the gap's middle whose name has the prefix,
and counts the ticks; idle time under the harness's ``bench.*`` spans or
under no such span is left out.
"""
from __future__ import annotations

import bisect
import re

_INSTR = re.compile(
    r'^\s*(?:ROOT )?%([\w.\-]+) = .*?metadata=\{op_name="([^"]*)"')


def op_scopes(hlo_text: str) -> dict[str, str]:
    """HLO instruction name -> its op_name path, for every instruction of
    an optimized HLO module that carries one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def scope_seconds(summary, scopes: dict[str, str], scope: str,
                  program: str) -> float:
    """Device time of the leaf operations under ``scope`` (one segment of
    their path) inside runs of ``program``; ``scopes`` is that program's
    ``op_scopes``."""
    runs = sorted((s, s + d) for _, s, d in summary._programs(program))
    starts = [a for a, _ in runs]
    total = 0
    for name, s, d in summary._leaves():
        if scope not in scopes.get(name, "").split("/"):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            total += d
    return total * 1e-9


def idle_gaps(summary) -> list:
    """(start, length) in ns of each stretch of the traced window in which
    no device operation runs."""
    gaps, prev = [], summary.t0
    for a, b in summary._busy_intervals() + [[summary.t1, summary.t1]]:
        if a > prev:
            gaps.append((prev, a - prev))
        prev = max(prev, b)
    return gaps


def idle_in_spans(summary, prefix: str = "serve.") -> tuple[dict, int]:
    """Idle seconds of the window by the innermost host span open at each
    gap's middle whose name starts with ``prefix`` (gaps under none are
    left out), and the number of ``<prefix>tick`` steps that start in the
    window."""
    spans = [e for e in summary.host if e[0].startswith(prefix)]
    starts = [s for _, s, _ in spans]
    by_span: dict = {}
    for start, length in idle_gaps(summary):
        t = start + length // 2
        for name, s, d in reversed(spans[:bisect.bisect_right(starts, t)]):
            if s + d >= t:
                by_span[name] = by_span.get(name, 0.0) + length * 1e-9
                break
    ticks = sum(1 for name, s, _ in spans if name == prefix + "tick"
                and summary.t0 <= s < summary.t1)
    return by_span, ticks
