"""``BENCHMARK.json``: which cells exist, and what each one reports."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from bench import arch, spec, traffic

ROOT = Path(__file__).resolve().parent.parent
MANIFEST = ROOT / "BENCHMARK.json"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    arch: object          # the configuration's module of bench/arch/
    config: dict          # the configuration file's contents
    mix: dict             # the traffic file's contents
    end_to_end: list      # metric entries this cell reports (--trace 0)
    per_layer: list       # metric entries this cell reports (--trace 1)

    @property
    def spec(self):
        return self.arch.spec(self.config)


def _applies(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(name: str, manifest: Path = MANIFEST) -> Cell:
    m = json.loads(Path(manifest).read_text())
    cells = {w["name"]: w for w in m["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in {manifest}")
    w = cells[name]
    conf = {c["name"]: c for c in m["configs"]}[w["config"]]
    e2e = [e for e in m["end_to_end"] if _applies(e, name, set())]
    names = {e["name"] for e in e2e}
    per = [p for p in m["per_layer"] if _applies(p, name, names)]
    config = spec.load(Path(manifest).parent / conf["file"])
    return Cell(name=name, chips=w["chips"], arch=arch.load(config),
                config=config, mix=traffic.load(w["traffic"]),
                end_to_end=e2e, per_layer=per)
