"""Find a cell's pieces by name.

``BENCHMARK.json`` names every piece; each lives in a file of its own:

* configuration ``<c>``: ``bench/configs/<c>.json`` (the sizes as run)
  beside ``bench/configs/<c>.reference.py`` (its plain reference);
* traffic mix ``<t>``: ``bench/traffic/<t>.json``, parameters that the
  generator named by its ``"driver"`` key reads;
* per-layer metric ``<m>``: ``bench/metrics/<m>.py``, a reader with
  ``read(run) -> float | None``;
* device peaks: ``bench/peaks.json``, keyed by ``device_kind``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType
from typing import List, Optional

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class CellError(LookupError):
    """A name that ``BENCHMARK.json`` or the files under ``bench/`` do
    not resolve."""


def load_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise CellError(f"{path} does not exist") from None


def load_module(path: pathlib.Path, name: str) -> ModuleType:
    """Import the Python file ``path`` (its name may hold dots)."""
    if not path.is_file():
        raise CellError(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its pieces resolved."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]            # the metrics this cell reports
    per_layer: List[dict]
    root: pathlib.Path

    def reference(self) -> ModuleType:
        return load_module(self.root / "bench" / "configs"
                           / f"{self.config_name}.reference.py",
                           f"reference_{self.config_name}")

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py",
                           f"metric_{metric}")


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Optional[pathlib.Path] = None) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``."""
    root = ROOT if root is None else pathlib.Path(root)
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json; "
                        f"known: {sorted(cells)}")
    w = cells[workload]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config_name=w["config"],
        config=load_json(root / "bench" / "configs" / f"{w['config']}.json"),
        traffic_name=w["traffic"],
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        root=root)


def peaks_for(device_kind: str, root: Optional[pathlib.Path] = None) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = load_json((ROOT if root is None else pathlib.Path(root)) / "bench"
                      / "peaks.json")["devices"]
    if device_kind not in table:
        raise CellError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json; known: {sorted(table)}")
    return table[device_kind]
