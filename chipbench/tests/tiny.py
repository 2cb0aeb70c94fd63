"""A benchmark root in a temporary directory with one small cell added as
new files only: the real benchmark's files, plus a configuration, its
reference, a traffic mix and limits of the new cell's own."""
from __future__ import annotations

import dataclasses
import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
REFERENCE = {"xlstm-125m": "xlstm_125m", "whisper-base": "whisper_base"}

TRAFFIC = dict(n_workers=4, topology="ring", theta=0.1, lr=0.01, momentum=0.9,
               weight_decay=5e-4, backend="jnp", comm_path="bucketed",
               chunks=1, trace_steps=3, placement="stacked", wire="moniqua",
               bits=1, seq_len=64, global_batch=8)
LIMITS = {"first_loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-2}


def make_root(tmp: str, arch: str = "xlstm-125m", cell: str = "tiny-cell",
              limits=None, **traffic) -> str:
    """Copy the benchmark into ``tmp`` and add ``cell``: ``arch``'s reduced
    configuration under ``traffic`` (overrides of :data:`TRAFFIC`)."""
    from repro.configs import get_config
    root = os.path.join(tmp, "root")
    shutil.copytree(BENCH, os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)

    def put(rel, obj):
        with open(os.path.join(root, "chipbench", rel), "w") as f:
            json.dump(obj, f)

    name = cell.replace("-", "_")
    put(f"configs/{name}.json",
        {"arch": dataclasses.asdict(get_config(arch).reduced())})
    shutil.copy(os.path.join(BENCH, "reference", REFERENCE[arch] + ".py"),
                os.path.join(root, "chipbench", "reference", name + ".py"))
    put(f"traffic/{name}.json", dict(TRAFFIC, **traffic))
    put(f"limits/{cell}.json", dict(LIMITS, **(limits or {})))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"chipbench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": name,
                               "traffic": name, "chips": 1, "why": "test"})
    with open(path, "w") as f:
        json.dump(bench, f)
    return root
