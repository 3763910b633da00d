"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

Each part is a file of its own, so that a later change adds files and
edits none:

- a configuration is ``configs/<config>.json``;
- a traffic mix is ``traffic/<traffic>.json``, read by the one general
  generator (`bench.harness`): a closed loop of one training stream.  A
  mix sets only the generator's `MIX_KEYS`; any other key is refused,
  since a new kind of traffic needs a new generator;
- a metric, end to end or per layer, is ``metrics/<name>.py`` with
  ``read(run) -> float | None``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: what a mix may set: the proof window T, the ranges the inputs and
#: targets are drawn from, and a plain description
MIX_KEYS = {"about", "steps_per_proof", "x_range", "y_range"}


class SpecError(LookupError):
    """A name in BENCHMARK.json has no file, or a file no entry."""


def _load_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}") from None


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_reader(name: str, metrics_dir: str = os.path.join(HERE, "metrics")
                ) -> Callable:
    path = os.path.join(metrics_dir, f"{name}.py")
    if not os.path.exists(path):
        raise SpecError(f"metric {name!r} has no reader "
                        f"{os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _reported_here(entry: dict, cell: str, e2e_here: set) -> bool:
    """A metric with ``workloads`` is read in those cells; one without,
    end to end in every cell, per layer in every cell that reports the
    end-to-end metric it ``moves``."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    return "moves" not in entry or entry["moves"] in e2e_here


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names unknown config "
                        f"{w['config']!r}")
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "bench", "traffic",
                                      f"{w['traffic']}.json"))
    unknown = set(traffic) - MIX_KEYS
    if unknown:
        raise SpecError(f"mix {w['traffic']!r} sets {sorted(unknown)}, "
                        f"which the generator does not read")
    layouts = config.get("proof_layout_by_steps_per_proof", {})
    if str(traffic["steps_per_proof"]) not in layouts:
        raise SpecError(f"config {w['config']!r} states no proof layout "
                        f"for steps_per_proof "
                        f"{traffic['steps_per_proof']}")
    e2e = [m for m in bench["end_to_end"]
           if _reported_here(m, workload, set())]
    e2e_here = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _reported_here(m, workload, e2e_here)]
    metrics_dir = os.path.join(root, "bench", "metrics")
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[Metric(m["name"], m["unit"],
                           load_reader(m["name"], metrics_dir)) for m in e2e],
        per_layer=[Metric(m["name"], m["unit"],
                          load_reader(m["name"], metrics_dir)) for m in layer])


def read_metrics(metrics: List[Metric], run) -> Dict[str, dict]:
    """Each metric whose reader finds something, with its unit."""
    out: Dict[str, dict] = {}
    for m in metrics:
        value: Optional[float] = m.read(run)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out
