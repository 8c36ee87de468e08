"""Find a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix and the
metrics it reports.  Everything else is a file found by its name:

* ``bench/configs/<config>.json`` (the path ``configs[].file`` gives): the
  configuration as it is run, with the chunks of its window and the limits
  of its correctness check;
* ``bench/traffic/<traffic>.json``: the mix, read by ``traffic.py``; the
  keys of its ``"config"`` replace the configuration's in its cells;
* ``bench/layer_metrics/<metric>.py``: one reader per per-layer metric, a
  ``read(run)`` that returns a number, or None where it finds nothing.

A later cell, configuration, mix or metric is new files and new entries;
no file here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable

from bench import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    mix: dict
    end_to_end: list[dict]
    per_layer: list[tuple[dict, Callable]]
    run_seconds: float

    def window_chunks(self, seconds: float) -> int:
        """Chunks of a window of ``seconds``: the configuration's
        ``window_chunks`` (for a window of ``run_seconds``) in proportion,
        at least one.  A datum, never a measurement, so that two programs
        compared run the same work."""
        return max(1, round(self.config["window_chunks"] * seconds
                            / self.run_seconds))


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def load_reader(root: str, metric: str) -> Callable:
    path = os.path.join(root, "bench", "layer_metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench.layer_metrics.{metric}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def resolve(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    w = _by_name(bench["workloads"], name, "workload")
    entry = _by_name(bench["configs"], w["config"], "config")
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    mix = traffic.load(w["traffic"], os.path.join(root, "bench", "traffic"))
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config={**config, **mix.get("config", {})},
        traffic_name=w["traffic"], mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if reports(m, name)],
        per_layer=[(m, load_reader(root, m["name"]))
                   for m in bench["per_layer"] if reports(m, name)],
        run_seconds=float(bench["run_seconds"]))
