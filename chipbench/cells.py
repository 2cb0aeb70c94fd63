"""Everything about one cell, found by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Each piece lives in files of its own, so a cell, a configuration or a
per-layer metric is added as new files and entries, with no edit here:

* configuration ``<config>``: the file that its ``configs`` entry names
  (JSON; ``arch`` holds the sizes as they are run), and its plain reference
  ``chipbench/reference/<config>.py``;
* traffic ``<traffic>``: ``chipbench/traffic/<traffic>.json``, the trainer
  settings and shapes of the cell;
* limits of the comparison that decides ``correct``:
  ``chipbench/limits/<cell>.json``;
* per-layer metric ``<metric>``: the reader ``chipbench/metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
from typing import Any, Dict, List

BENCH_DIR = "chipbench"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    arch: Dict[str, Any]          # ArchConfig fields, as run
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    reference: Any                # module: init_params, loss, batch_spec, ...
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, Any]       # per-layer metric name -> reader module

    @functools.cached_property
    def _draw(self):
        import jax
        return jax.jit(lambda k: self.reference.init_params(k, self.arch))

    def initial_weights(self, seed: int):
        """The seed's weights, one replica, from one jitted program.  The
        program's state and the reference start from these: the same draw
        compiled into another program can round differently on the
        chip."""
        import jax
        return self._draw(jax.random.PRNGKey(seed))


def import_file(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str):
    with open(path) as f:
        return json.load(f)


def _find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(root: str, name: str) -> Cell:
    """The cell ``name`` of the benchmark whose ``BENCHMARK.json`` is in
    ``root``."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    w = _find(bench["workloads"], name, "workload")
    conf = _find(bench["configs"], w["config"], "config")
    here = os.path.join(root, BENCH_DIR)
    arch = _read_json(os.path.join(root, conf["file"]))["arch"]
    per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"], arch=arch,
        traffic=_read_json(os.path.join(here, "traffic",
                                        w["traffic"] + ".json")),
        limits=_read_json(os.path.join(here, "limits", name + ".json")),
        reference=import_file(
            os.path.join(here, "reference", w["config"] + ".py"),
            f"chipbench_reference_{w['config']}"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=per_layer,
        readers={m["name"]: import_file(
            os.path.join(here, "metrics", m["name"] + ".py"),
            "chipbench_metric_" + m["name"].replace(".", "_"))
            for m in per_layer})
