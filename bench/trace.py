"""Reduction of a profiler trace to what the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
three lists, all on the trace's one clock (nanoseconds): the device's
programs (``XLA Modules``), the device's operations (``XLA Ops``) and the
host's annotated spans. ``Summary`` then gives:

* the traced window: from the first to the last of the harness's own host
  spans (``bench.step``, ``bench.wait_for_arrival``);
* busy time: the union of the device operations' intervals in it;
* each program's device time and count, by the jitted function's name;
* a kernel's device time inside one program, by the kernel's name;
* a breakdown: the kinds of device operation that took most time (loops
  and calls, which span their bodies, left out), and the idle
  time between them summed by what the host was doing (the innermost host
  span open at each gap's middle).

The extract is JSON, so a short one recorded on the chip is kept with the
tests (``bench/testdata/``).
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")


def _events(line, short=False):
    for e in line.events:
        yield (op_name(e.name) if short else e.name, int(e.start_ns),
               int(e.duration_ns))


def op_name(hlo: str) -> str:
    """``%vq_dequant_matmul.97 = f32[16,2048] custom-call(...)`` ->
    ``vq_dequant_matmul.97``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """``vq_dequant_matmul.97`` -> ``vq_dequant_matmul``."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def extract(profile_dir: str) -> dict:
    """Programs, operations and host spans of a profiler directory."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        return {"modules": [], "ops": [], "host": []}
    pd = ProfileData.from_file(paths[-1])
    modules, ops, host = [], [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            if int(m.group(1)) != 0:
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules.extend(_events(line))
                elif line.name == "XLA Ops":
                    ops.extend(_events(line, short=True))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    return {"modules": sorted(modules, key=lambda e: e[1]),
            "ops": sorted(ops, key=lambda e: e[1]),
            "host": sorted(host, key=lambda e: e[1])}


def program_name(module: str) -> str:
    """``jit_decode(123)`` -> ``decode``."""
    name = module.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


class Summary:
    def __init__(self, ex: dict, host_prefix: str = "bench."):
        self.modules = [tuple(e) for e in ex["modules"]]
        self.ops = [tuple(e) for e in ex["ops"]]
        self.host = [tuple(e) for e in ex["host"]]
        self._host_starts = [e[1] for e in self.host]
        spans = [e for e in self.host if e[0].startswith(host_prefix)] \
            or self.host
        if spans:
            self.t0 = min(e[1] for e in spans)
            self.t1 = max(e[1] + e[2] for e in spans)
        else:
            self.t0 = self.t1 = 0

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _busy_intervals(self) -> list:
        out = []
        for _, s, d in self.ops:
            a, b = max(s, self.t0), min(s + d, self.t1)
            if b <= a:
                continue
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return out

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in self._busy_intervals()) * 1e-9

    def _programs(self, program: str) -> list:
        return [e for e in self.modules if program_name(e[0]) == program]

    def program_count(self, program: str) -> int:
        return len(self._programs(program))

    def program_seconds(self, program: str) -> float:
        return sum(d for _, _, d in self._programs(program)) * 1e-9

    def _leaves(self) -> list:
        """Operations that hold no other: a loop or call on the ops line
        spans the operations of its body, which are listed after it."""
        out = []
        for i, (name, s, d) in enumerate(self.ops):
            nxt = self.ops[i + 1] if i + 1 < len(self.ops) else None
            if nxt is None or nxt[1] >= s + d:
                out.append((name, s, d))
        return out

    def kernel_seconds(self, kernel: str, program: str | None = None) -> float:
        """Device time of operations of kind ``kernel`` (inside runs of
        ``program`` when given)."""
        spans = sorted((s, s + d) for _, s, d in self._programs(program)) \
            if program else None
        starts = [a for a, _ in spans] if spans else None
        total = 0
        for name, s, d in self.ops:
            if op_kind(name) != kernel:
                continue
            if spans is not None:
                i = bisect.bisect_right(starts, s) - 1
                if i < 0 or s >= spans[i][1]:
                    continue
            total += d
        return total * 1e-9

    def _host_at(self, t: int, look_back: int = 4000) -> str:
        """The innermost host span open at time t: of the spans that
        started before t and have not ended, the one that started last
        (among the ``look_back`` latest starts)."""
        i = bisect.bisect_right(self._host_starts, t)
        for name, s, d in reversed(self.host[max(0, i - look_back):i]):
            if s + d >= t:
                return name
        return "no host span"

    def breakdown(self, top: int = 10) -> dict:
        by_op: dict = {}
        for name, s, d in self._leaves():
            if self.t0 <= s < self.t1:
                by_op[op_kind(name)] = by_op.get(op_kind(name), 0) + d
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        gaps, prev = [], self.t0
        for a, b in self._busy_intervals() + [[self.t1, self.t1]]:
            if a > prev:
                gaps.append((a - prev, prev))
            prev = max(prev, b)
        by_host: dict = {}
        for length, start in gaps:
            label = self._host_at(start + length // 2)
            by_host[label] = by_host.get(label, 0) + length
        idle = sorted(by_host.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[n, d * 1e-9] for n, d in ops],
                "idle_gaps": [[n, d * 1e-9] for n, d in idle[:top]]}


def load(profile_dir: str) -> Summary:
    return Summary(extract(profile_dir))


def save(ex: dict, path: str | Path):
    with gzip.open(path, "wt") as f:
        json.dump(ex, f)


def read(path: str | Path) -> Summary:
    with gzip.open(path, "rt") as f:
        return Summary(json.load(f))
