"""What a cell is made of, found by the names in `BENCHMARK.json`.

The harness holds no list of its own: a cell names its configuration and its
traffic mix; the configuration's entry names its file; a mix is
`cellbench/traffic/<traffic>.json` and names its generator
(`cellbench/generators/<name>.py`); a metric is
`cellbench/metrics/<name>.json` and names its reader
(`cellbench/readers/<name>.py`); a configuration's file names its reference
(`cellbench/reference/<name>.py`) and its counts (`cellbench/counts/`). A
later PR adds files and entries and edits none.
"""

from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(*parts: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def hf_config(config: dict) -> dict:
    """The model's own config.json: every key of the configuration's file
    but the benchmark's group."""
    return {k: v for k, v in config.items() if k != "bench"}


class Cell:
    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.bench = load_json("BENCHMARK.json", root=root)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_file = os.path.join(root, configs[self.entry["config"]]["file"])
        self.config = load_json(self.config_file)
        self.mix = load_json("cellbench", "traffic", self.entry["traffic"] + ".json", root=root)

    def rehearse(self) -> None:
        """Swap in the toy model and the mix's rehearsal overrides: what
        `--cpu-rehearsal` runs, so that the harness cannot rot where there
        is no chip."""
        self.config_file = os.path.join(self.root, "cellbench", "configs", "rehearsal-tiny.json")
        self.config = load_json(self.config_file)
        self.mix.update(self.mix.get("rehearsal", {}))

    def metrics(self, group: str) -> list[dict]:
        """The metric files of this cell's `end_to_end` or `per_layer`
        entries: an entry without `workloads` is every cell's."""
        out = []
        for entry in self.bench[group]:
            if "workloads" in entry and self.name not in entry["workloads"]:
                continue
            out.append(load_json("cellbench", "metrics", entry["name"] + ".json", root=self.root))
        return out

    def generator(self):
        return importlib.import_module(f"cellbench.generators.{self.mix['generator']}")


def reader(name: str):
    return importlib.import_module(f"cellbench.readers.{name}")
