"""Finds what a cell needs by the names in `BENCHMARK.json`.

Nothing here names a configuration, a cell, a traffic mix or a metric: a
later PR adds `configs/<config>.json`, `traffic/<traffic>.json`,
`workloads/<cell>.json` and `metrics/<metric>.py` with their entries in
`BENCHMARK.json`, and edits no file that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load_json(path: str) -> Dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: str, tag: str):
    """The module in the file at `path`, under a name of its own made from
    `tag`: how a metric's reader, a generator and a tool are found by file."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + tag.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    def __init__(self, root: str = ROOT, here: str = HERE):
        self.root, self.here = root, here
        self.doc = _load_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> Dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.doc["workloads"])
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has: "
                         f"{known}")

    def config(self, name: str) -> Dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return _load_json(os.path.join(self.root, c["file"]))
        raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict:
        return _load_json(os.path.join(self.here, "traffic", f"{name}.json"))

    def workload(self, name: str) -> Dict:
        """The cell's own numbers: seconds a chunk takes (which sets how many
        fit a window) and the limit of each number compared."""
        return _load_json(os.path.join(self.here, "workloads", f"{name}.json"))

    def metrics(self, group: str, cell: str) -> List[Dict]:
        """The cell's metrics of `end_to_end` or `per_layer`: those with no
        `workloads` key, and those whose key lists the cell."""
        return [m for m in self.doc[group]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str) -> Callable:
        """`read(ctx)` of `metrics/<metric>.py`, a reader of its own for
        each metric."""
        path = os.path.join(self.here, "metrics", f"{metric}.py")
        if not os.path.exists(path):
            raise SystemExit(f"metric {metric!r} has no reader at {path}")
        return load_module(path, f"metric_{metric}").read

    def peaks(self, device_kind: str) -> Dict:
        table = _load_json(os.path.join(self.here, "peaks.json"))
        if device_kind not in table["devices"]:
            raise SystemExit(f"device kind {device_kind!r} is not in "
                             f"benchmark/peaks.json; add it with its source")
        return table["devices"][device_kind]
