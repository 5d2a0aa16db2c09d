"""Finds everything by name: ``BENCHMARK.json`` lists cells, configurations
and metrics; each configuration, traffic mix and metric has a data file of
its own under the benchmark's directory, and each metric's file names its
reader, a function in a module found by scanning ``readers/``. Adding a
cell, a configuration, a mix or a metric is adding files and entries; no
file that is there needs an edit. No JAX here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # perfbench/


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list[dict]  # metric files of this cell's end-to-end metrics
    per_layer: list[dict]  # and of its per-layer metrics
    bench_dir: str
    readers: dict = field(default_factory=dict)  # "module:function" -> callable


def _for_cell(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_readers(dirs: list[str]) -> dict:
    """Every public function of every ``*.py`` in the readers directories,
    as ``{"module:function": callable}``: a new reader is a new module that
    a metric file names, found by this scan and by no import list."""
    if HERE not in sys.path:
        sys.path.insert(0, HERE)  # the readers import ``lib``
    found = {}
    for d in dirs:
        if not os.path.isdir(d):
            continue
        for fn in sorted(os.listdir(d)):
            if not fn.endswith(".py") or fn.startswith("_"):
                continue
            mod_name = fn[:-3]
            spec = importlib.util.spec_from_file_location(
                f"perfbench_readers_{mod_name}", os.path.join(d, fn)
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            for attr, obj in vars(mod).items():
                if callable(obj) and not attr.startswith("_") and getattr(
                    obj, "__module__", None
                ) == mod.__name__:
                    found[f"{mod_name}:{attr}"] = obj
    return found


def load_cell(root: str, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files.
    ``root`` is the checkout (or, for the tests, a directory that holds a
    benchmark of toy sizes)."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    bench_dir = os.path.join(root, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"no workload {workload!r} in BENCHMARK.json (has {sorted(cells)})"
        )
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    # the benchmark's own readers, and those a benchmark elsewhere brings
    readers = load_readers(sorted({
        os.path.join(HERE, "readers"),
        os.path.join(os.path.abspath(bench_dir), "readers"),
    }))

    def metric_files(entries: list[dict]) -> list[dict]:
        out = []
        for e in entries:
            if not _for_cell(e, workload):
                continue
            m = _load(os.path.join(bench_dir, "metrics", e["name"] + ".json"))
            if m["name"] != e["name"] or m["unit"] != e["unit"]:
                raise SystemExit(
                    f"metrics/{e['name']}.json disagrees with BENCHMARK.json"
                )
            if m["reader"] not in readers:
                raise SystemExit(
                    f"metric {e['name']}: no reader {m['reader']!r} under "
                    "readers/"
                )
            out.append(m)
        return out

    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], config=config, traffic=traffic,
        end_to_end=metric_files(bench["end_to_end"]),
        per_layer=metric_files(bench["per_layer"]),
        bench_dir=bench_dir, readers=readers,
    )
