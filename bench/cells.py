"""Find a cell and everything it names, by file name.

`BENCHMARK.json` at the checkout root lists the cells (`workloads`); a cell
names a configuration (`bench/configs/<config>.json`) and a traffic mix
(`bench/traffic/<traffic>.json`), and its correctness limits sit in
`bench/limits/<cell>.json`. Each per-layer metric is read by
`bench/metrics/<metric>.py`, and each configuration names its plain
reference, `bench/references/<reference>.py`. Adding a cell, a mix, a
configuration or a metric adds files and entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


class CellError(RuntimeError):
    pass


def _json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise CellError(f"missing {path}") from None


def load_module(path: Path, name: str):
    if not path.is_file():
        raise CellError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict             # the configuration file
    traffic: dict            # the mix file
    limits: dict             # bench/limits/<cell>.json
    end_to_end: list         # BENCHMARK.json metric entries this cell reports
    per_layer: list
    root: Path

    def reference(self):
        return load_module(self.root / "bench" / "references"
                           / f"{self.config['reference']}.py",
                           f"bench_reference_{self.config['reference']}")

    def reader(self, metric: str):
        """The per-layer metric's reader module (`read(run) -> float|None`)."""
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py",
                           "bench_metric_" + metric.replace(".", "_"))


def _applies(entry: dict, cell: str, e2e_names: set) -> bool:
    """A metric with a `workloads` list applies to those cells; a per-layer
    metric without one to every cell that reports the metric it moves."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    return "moves" not in entry or entry["moves"] in e2e_names


def load(name: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json; known: "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]),
                config=_json(root / cfg_entry["file"]),
                traffic=_json(root / "bench" / "traffic"
                              / f"{w['traffic']}.json"),
                limits=_json(root / "bench" / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer, root=root)
