"""Cells are found by name from their files, and the benchmark file keeps
to its own rules."""
import json
import os
import re

import jax
import pytest

from chipbench import cells, check, program, run
from chipbench.tests import tiny

with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
TRAFFIC_KEYS = set(tiny.TRAFFIC)


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_from_its_files(name):
    cell = cells.load(tiny.ROOT, name)
    assert set(cell.traffic) == TRAFFIC_KEYS
    assert cell.limits and set(cell.limits) <= set(check.NAMES)
    assert all(hasattr(r, "read") for r in cell.readers.values())
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "step_ms"}
    assert cell.per_layer
    # the reference's seeded weights have the program's layout, at the
    # published sizes (shapes only)
    from repro.models.model_factory import build_model
    m = build_model(program.arch_config(cell.arch))
    key = jax.random.PRNGKey(0)
    want = jax.eval_shape(m.init, key)
    got = jax.eval_shape(lambda k: cell.reference.init_params(k, cell.arch),
                         key)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert ([(a.shape, a.dtype) for a in jax.tree.leaves(want)]
            == [(a.shape, a.dtype) for a in jax.tree.leaves(got)])


def test_a_cell_added_as_new_files_only(tmp_path):
    root = tiny.make_root(str(tmp_path), "whisper-base", cell="new-cell",
                          bits=8)
    cell = cells.load(root, "new-cell")
    assert cell.traffic["bits"] == 8 and cell.arch["d_model"] == 256
    assert cell.reference.__name__.endswith("new_cell")
    assert {m["name"] for m in cell.per_layer} >= {"mfu", "idle_share"}
    # a per-layer metric restricted to other cells is not read here
    assert "permute_ms" not in cell.readers


def test_benchmark_file_rules():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    chips = [w["chips"] for w in BENCH["workloads"]]
    assert set(chips) <= {1, 4} and chips.count(4) <= max(1, len(chips) // 2)
    for c in BENCH["configs"]:
        assert os.path.exists(os.path.join(tiny.ROOT, c["file"]))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in BENCH["per_layer"]:
        assert m["moves"] == "step_ms"
        layers.add(m["layer"])
        assert os.path.exists(os.path.join(tiny.BENCH, "metrics",
                                           m["name"] + ".py"))
    assert layers == {"loop", "step", "round", "kernels", "device"}
    cells_ = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        assert set(m.get("workloads", cells_)) <= set(cells_)


def test_no_tpu_exits_nonzero_and_prints_no_result(capsys):
    assert jax.devices()[0].platform != "tpu"
    rc = run.main(["--workload", "xlstm125m-m1-stack4", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "platform cpu" in out.err
