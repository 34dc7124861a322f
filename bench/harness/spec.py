"""Finds a cell and everything that belongs to it by name.

`BENCHMARK.json` at the repo root lists the cells (`workloads`), their
configurations and the metrics. Each piece lives in a file of its own,
found from its name, so a later cell, configuration or metric is added by
adding files:

    bench/configs/<config>.json     model configuration (widths, recipe)
    bench/traffic/<traffic>.json    fleet and round mix
    bench/limits/<cell>.json        limits of the numbers `correct` compares
    bench/metrics/<metric>.py       reader of one per-layer metric
    the file the config's `reference`
    names (a path from the root)    the model family: its plain reference
                                    and the functions of FAMILY_API below

No module of the harness names a model family; a new client family is a
new configuration and a new reference module.

A configuration's optional `reference_device` says where its float32
reference runs: `host` (the default, the host's CPU) or `chip` (the
accelerator at "highest" matmul precision, for a family too large to
replay on the host in a run's time).

The training check replays the first round one client at a time (see
`bench/harness/train_cell.py`): it holds the globals and a fixed number
of client models in host memory, whatever the number of clients, so a
family's functions take and give one client at a time, and its
aggregation reads the trained clients from memory maps, leaf by leaf.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


# What a model family's module exports (bench/references/cnn.py is one):
#   check_config(config)                refuse a program whose model differs
#                                       from the config (ValueError)
#   check_data(config, data)            refuse data of another shape or dtype
#   sim_config(config, traffic, fleet_seed)   the program's FLSimConfig
#   init_params(key, config, size)      one model's initial weights (jittable)
#   train_client(start, xs, ys, config, dtype, keep)   the reference's local
#                                       training of one client: (trained
#                                       model as numpy, per-leaf float64
#                                       norms of its first gradient)
#   entropy(labels, n_classes), accuracy(params, x, y, dtype),
#   aggregate(lite, by_size, sizes, trained, entropies, accs_local,
#             accs_lite)                the reference's Eqs. 36-39 and 5;
#                                       `trained[i]` is client i as memory
#                                       maps: read a leaf at a time, never
#                                       every client whole
#   step_flops(config, size), step_bytes(config, size)   least operations
#                                       and bytes of one client step
FAMILY_API = ("check_config", "check_data", "sim_config", "init_params",
              "train_client", "entropy", "accuracy", "aggregate",
              "step_flops", "step_bytes")


REFERENCE_DEVICES = ("host", "chip")


class SpecError(ValueError):
    """A cell, file or configuration that does not match the benchmark."""


def reference_device(config: Dict) -> str:
    """Where the configuration's float32 reference runs (`host` unless the
    config says otherwise); SpecError for a value not in
    REFERENCE_DEVICES."""
    where = config.get("reference_device", "host")
    if where not in REFERENCE_DEVICES:
        raise SpecError(f"config {config.get('name')!r} states reference_"
                        f"device {where!r}; known: {REFERENCE_DEVICES}")
    return where


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    readers: Dict[str, Callable] = field(default_factory=dict)


def _read_json(path: Path) -> Dict:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    return json.loads(path.read_text())


def load_benchmark(root: Path = ROOT) -> Dict:
    return _read_json(root / "BENCHMARK.json")


def _load_module(path: Path, prefix: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(path: Path) -> Callable:
    """A per-layer metric's reader: the `read(ctx)` of its own file."""
    return _load_module(path, "bench_metric_").read


_FAMILIES: Dict[Path, ModuleType] = {}


def family(config: Dict, root: Path = ROOT) -> ModuleType:
    """The model family of a configuration: the module at its `reference`
    path, loaded once per path. Refused where the file is missing or lacks
    a function of FAMILY_API."""
    path = (root / config["reference"]).resolve()
    if path not in _FAMILIES:
        if not path.is_file():
            raise SpecError(f"config {config.get('name')!r} names reference "
                            f"{config['reference']!r}, which is no file")
        mod = _load_module(path, "bench_family_")
        missing = [f for f in FAMILY_API
                   if not callable(getattr(mod, f, None))]
        if missing:
            raise SpecError(f"{config['reference']} lacks "
                            f"{', '.join(missing)}")
        _FAMILIES[path] = mod
    return _FAMILIES[path]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    entry = configs[w["config"]]
    config = _read_json(root / entry["file"])
    if config.get("name") != entry["name"]:
        raise SpecError(f"{entry['file']} holds config {config.get('name')!r}"
                        f", not {entry['name']!r}")
    reference_device(config)
    traffic = _read_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = _read_json(root / "bench" / "limits" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    readers = {m["name"]: load_reader(root / "bench" / "metrics"
                                      / f"{m['name']}.py")
               for m in per_layer}
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer, readers=readers)
