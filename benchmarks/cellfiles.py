"""Find a cell's files by the names in BENCHMARK.json and resolve them.

Nothing here imports JAX: the parent process reads these files, and so do
the children. Everything that belongs to one configuration, one cut, one
traffic mix or one metric sits in a file of its own (README.md), so a later
PR adds files and entries and edits nothing that exists.
"""

import json
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parent
OUT_DIR = BENCH_DIR / "out"


class CellError(Exception):
    """A name that resolves to nothing, or files that contradict each other."""


def load_json(path: Path) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CellError(f"{path.relative_to(REPO)} does not exist")
    except json.JSONDecodeError as e:
        raise CellError(f"{path.relative_to(REPO)} is not JSON: {e}")


def benchmark() -> Dict[str, Any]:
    return load_json(REPO / "BENCHMARK.json")


def metric_file(group: str, name: str) -> Dict[str, Any]:
    folder = {"end_to_end": "e2e_metrics", "per_layer": "layer_metrics"}[group]
    spec = load_json(BENCH_DIR / folder / f"{name}.json")
    if "reader" not in spec:
        raise CellError(f"{folder}/{name}.json names no reader")
    return spec


def resolve_model(model: Dict[str, Any], cut: Dict[str, Any],
                  rehearsal: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Published keys as this cut runs them -> keyword arguments of the
    program's ModelConfig, through the family's `maps_to` table."""
    family = load_json(BENCH_DIR / "families" / f"{model['family']}.json")
    run = dict(model)
    for key, value in cut.get("reduced", {}).items():
        if key not in model["reduced"]:
            raise CellError(
                f"cut changes {key!r}, which the configuration does not list"
                " under `reduced`"
            )
        run[key] = value
    if rehearsal is not None:
        run.update(rehearsal["model"])
        run.update({k: v for k, v in rehearsal["model_if_present"].items()
                    if k in run})
    for key, want in family["must_hold"].items():
        if key == "head_dim":
            want = run["hidden_size"] // run["num_attention_heads"]
        if run.get(key) != want:
            raise CellError(
                f"{key} = {run.get(key)!r}, but the {model['family']} family"
                f" as the program implements it needs {want!r}"
            )
    fields = {dst: run[src] for src, dst in family["maps_to"].items() if src in run}
    for src, dst in family["assumed_maps_to"].items():
        if src in model.get("assumed", {}):
            fields[dst] = model["assumed"][src]
    for key, value in cut.get("assumed_fields", {}).items():
        fields[key] = value
    return {"family": family, "fields": fields}


class Cell:
    """One entry of `workloads`, with every file it names loaded."""

    def __init__(self, name: str, *, rehearsal: bool = False):
        self.bench = benchmark()
        entries = [w for w in self.bench["workloads"] if w["name"] == name]
        if not entries:
            known = ", ".join(w["name"] for w in self.bench["workloads"])
            raise CellError(f"no workload {name!r} in BENCHMARK.json (have: {known})")
        self.entry = entries[0]
        self.name = name
        self.rehearsal = load_json(BENCH_DIR / "rehearsal.json") if rehearsal else None
        configs = [c for c in self.bench["configs"] if c["name"] == self.entry["config"]]
        if not configs:
            raise CellError(f"workload {name!r} names no known configuration")
        self.config_entry = configs[0]
        self.model = load_json(REPO / self.config_entry["file"])
        self.cellfile = load_json(BENCH_DIR / "cells" / f"{name}.json")
        config_dir = (REPO / self.config_entry["file"]).parent
        self.cut = load_json(config_dir / "cuts" / f"{self.cellfile['cut']}.json")
        self.mix = load_json(BENCH_DIR / "traffic" / f"{self.entry['traffic']}.json")
        self.load = dict(self.cellfile["load"])
        self.chips = int(self.entry["chips"])
        if self.chips != int(self.cut["chips"]):
            raise CellError(
                f"workload {name!r} asks for {self.chips} chips, its cut for"
                f" {self.cut['chips']}"
            )
        resolved = resolve_model(self.model, self.cut, self.rehearsal)
        self.family = resolved["family"]
        self.model_fields = resolved["fields"]
        if self.rehearsal is not None:
            self.chips = 1
            for key, scale in self.rehearsal["load_scale"].items():
                if key in self.load:
                    self.load[key] *= scale
            if "callers" in self.load:
                self.load["callers"] = max(1, int(self.load["callers"]))

    @property
    def kind(self) -> str:
        return self.cut["kind"]

    @property
    def generator(self) -> str:
        return self.mix["generator"]

    def server_args(self) -> List[str]:
        if self.rehearsal is not None:
            return list(self.rehearsal["server_args"])
        return list(self.cut["server_args"])

    def server_arg(self, flag: str, default: int) -> int:
        args = self.server_args()
        return int(args[args.index(flag) + 1]) if flag in args else default

    def metrics(self, group: str) -> List[Dict[str, Any]]:
        """The entries of `end_to_end` or `per_layer` that this cell reports:
        an entry without "workloads" belongs to every cell."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def peaks(self, device_kind: str) -> Dict[str, Any]:
        table = load_json(BENCH_DIR / "peaks.json")
        for key, row in table.items():
            if key != "notes" and key.lower() in device_kind.lower():
                return row
        raise CellError(
            f"no published peaks for device_kind {device_kind!r} in"
            " benchmarks/peaks.json: add the row with its source"
        )
