"""Required work of the kernels, counted from shapes (a model's own work
is counted by its architecture module, ``bench/arch/``).

Each function counts what the algorithm needs, not what one
implementation happens to do, so a later kernel that does less redundant
work cannot push a share past 100% and one that does more is not credited
for it. ``roofline`` turns a count into the least time the chip's peaks
allow (``bench/peaks.json``, keyed by ``device_kind``).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent.parent / "peaks.json"


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, n: float) -> "Work":
        return Work(self.flops * n, self.bytes * n)


ZERO = Work(0.0, 0.0)


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; an unknown kind is
    an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def roofline(work: Work, device_kind: str) -> tuple[float, str]:
    """(least seconds, which bound sets it: "compute" or "memory")."""
    p = peaks(device_kind)
    t_c = work.flops / p["bf16_flops_per_s"]
    t_m = work.bytes / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
