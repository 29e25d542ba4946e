"""Find a cell's files by the names in BENCHMARK.json.  Nothing here
knows a cell, a configuration, a mix or a metric by name."""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def _json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> Dict[str, Any]:
    return _json(os.path.join(REPO_ROOT, "BENCHMARK.json"))


def load_cell(name: str) -> Dict[str, Any]:
    """{"workload", "config_entry", "config", "mix"} of one cell.  Where
    benchmark/cells/<cell>.json exists it overlays the mix (the cell's
    own rate: one mix serves several cells) and, under `engine`, the
    configuration's engine sizes (a worker is sized for its traffic)."""
    bench = load_benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                         f"{sorted(by_name)}")
    wl = by_name[name]
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    mix = _json(os.path.join(BENCH_DIR, "traffic", wl["traffic"] + ".json"))
    config = _json(os.path.join(REPO_ROOT, entry["file"]))
    over = os.path.join(BENCH_DIR, "cells", name + ".json")
    if os.path.exists(over):
        cell = _json(over)
        config["engine"].update(cell.pop("engine", {}))
        cell.pop("engine_why", None)
        mix.update(cell)
    return {"workload": wl, "config_entry": entry, "config": config,
            "mix": mix}


def cell_metrics(cell: str, group: str) -> List[Dict[str, Any]]:
    """The entries of `end_to_end` or `per_layer` that this cell reports:
    those without a `workloads` key, and those that list the cell."""
    return [m for m in load_benchmark()[group]
            if "workloads" not in m or cell in m["workloads"]]


def metric_reader(group_dir: str, name: str) -> Callable[[Dict], Any]:
    """benchmark/<group_dir>/<name>.json names `reader` as module.function
    under benchmark/readers/ and may give it `args`.  Returns ctx -> value
    (None where the reader found nothing to read)."""
    d = _json(os.path.join(BENCH_DIR, group_dir, name + ".json"))
    mod, fn = d["reader"].rsplit(".", 1)
    f = getattr(importlib.import_module(f"benchmark.readers.{mod}"), fn)
    args = d.get("args", {})
    return lambda ctx: f(ctx, **args)


def loop_module(mix: Dict[str, Any]):
    """The generator of the mix's loop kind: benchmark/lib/gen_<loop>.py."""
    return importlib.import_module(f"benchmark.lib.gen_{mix['loop']}")


def model_class(config: Dict[str, Any]):
    """benchmark/reference/<class>.py: the class's plain reference, its
    mapping from the source's keys to the program's config, and its
    attention arithmetic."""
    return importlib.import_module(f"benchmark.reference.{config['class']}")
