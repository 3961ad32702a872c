"""Per-layer metrics: one reader per metric, ``bench/metrics/<name>.py``.

Each reader defines ``read(ctx) -> float | None`` and returns None where it
finds nothing to read (the metric is then left out of the result line);
a share of a roofline or of a peak is never reported as 0 for want of
data. ``ctx`` is a ``Context``: the cell's architecture module
(``bench/arch/``, which counts the model's work) and model sizes, the
window's record (ticks, requests, registry deltas of the traced part) and
the reduced device trace.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path

METRICS_DIR = Path(__file__).resolve().parent


@dataclasses.dataclass
class Context:
    arch: object          # the configuration's module of bench/arch/
    spec: object          # that module's spec of the configuration
    window: object        # bench.serve.Window
    trace: object         # bench.trace.Summary
    device_kind: str
    mix: dict

    @property
    def traced_ticks(self) -> list:
        return self.window.ticks


def reader(name: str):
    path = METRICS_DIR / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench.metrics._{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def read_all(entries: list, ctx: Context) -> dict:
    out = {}
    for m in entries:
        v = reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = float(v)
    return out
