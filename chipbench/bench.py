"""``BENCHMARK.json`` and the files it names, resolved by name.

Everything that belongs to one configuration, traffic mix, cell or per-layer
metric sits in a file of its own, found by its name:

- ``configs/<name>.json``   (the path is the configuration's ``file``)
- ``traffic/<name>.json``   a mix's parameters, read by ``traffic.py``
- ``cells/<name>.json``     a cell's fleet, ``max_seq``, queue depth and the
                            limits of its check (its configuration and mix
                            are the ``BENCHMARK.json`` entry's)
- ``metrics/<name>.py``     a per-layer metric's reader: ``read(ctx)``
                            returns the number, or None where the run has
                            nothing for it to read

so a later change adds a cell, a mix or a metric by adding files and
entries, with no edit to the harness.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict          # the configuration file's contents
    traffic: dict         # the mix file's contents
    fleet: str
    max_seq: int
    max_queue_depth: int
    check: dict           # limits of the check that decides `correct`
    end_to_end: tuple     # BENCHMARK.json entries this cell reports
    per_layer: tuple


class Bench:
    def __init__(self, root: str, data_dir: str = HERE):
        """``root`` holds BENCHMARK.json; ``data_dir`` the per-name files."""
        self.root = root
        self.data_dir = data_dir
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def _load(self, kind: str, name: str) -> dict:
        with open(os.path.join(self.data_dir, kind, f"{name}.json")) as f:
            return json.load(f)

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        w = cells[name]
        configs = {c["name"]: c for c in self.spec["configs"]}
        with open(os.path.join(self.root, configs[w["config"]]["file"])) as f:
            config = json.load(f)
        body = self._load("cells", name)
        e2e = tuple(m for m in self.spec["end_to_end"]
                    if name in m.get("workloads", [name]))
        moved = {m["name"] for m in e2e}
        per_layer = tuple(
            m for m in self.spec["per_layer"]
            if (name in m["workloads"] if "workloads" in m else m["moves"] in moved))
        return Cell(name=name, config=config, traffic=self._load("traffic", w["traffic"]),
                    fleet=body["fleet"], max_seq=body["max_seq"],
                    max_queue_depth=body["max_queue_depth"], check=body["check"],
                    end_to_end=e2e, per_layer=per_layer)

    def reader(self, metric: str):
        """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
        path = os.path.join(self.data_dir, "metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(f"chipbench_metric_{metric}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
