"""A benchmark configuration file, and the served weight format it names.

Configuration files (``bench/configs/<name>.json``) keep the published
``config.json`` keys at their top level, as the source states them, beside
the benchmark's own groups: ``weights`` (the served format), ``engine``
(the serving engine's options), ``reduced`` and ``assumed``. The sizes a
cell needs are read from them by the configuration's architecture module
(``bench/arch/``).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path


@dataclasses.dataclass(frozen=True)
class VQFormat:
    """GPTVQ packed-weight format: d-dim codes of ``k`` entries, one int8
    codebook per (``group_cols`` columns x ``group_size // group_cols``
    rows) group."""
    d: int
    k: int
    group_size: int
    group_cols: int
    codebook_bits: int

    @property
    def code_bits(self) -> int:
        return (self.k - 1).bit_length()

    def plan(self, r: int, c: int) -> tuple[int, int]:
        """(group_cols, rows_per_band) of an (r=out, c=in) matrix: the
        widest divisor of c up to the group's columns, then the most rows
        up to the group's size."""
        cg = max(x for x in range(self.d, min(self.group_cols,
                                              self.group_size) + 1, self.d)
                 if c % x == 0)
        rg = max(x for x in range(1, max(1, self.group_size // cg) + 1)
                 if r % x == 0)
        return cg, rg


def vq_format(cfg: dict) -> VQFormat | None:
    """The configuration's packed-weight format; None where its weights
    are not GPTVQ-packed."""
    w = cfg["weights"]
    if w["format"] != "gptvq":
        return None
    k = round(2 ** (w["d"] * w["bits_per_dim"]))
    return VQFormat(d=w["d"], k=k, group_size=w["group_size"],
                    group_cols=w["group_cols"],
                    codebook_bits=w["codebook_bits"])


def load(path: str | Path) -> dict:
    return json.loads(Path(path).read_text())
