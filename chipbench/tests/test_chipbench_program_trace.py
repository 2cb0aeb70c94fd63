"""The program's own trace points: a traced run of the small cell, stacked
and on four devices, reads every layer the program names, each device op
falls under exactly one layer, and the program's host spans sit inside the
benchmark's on the profiler's clock.  The harness's look for a chip and
the chip's peaks are stood in for; everything else is a traced run."""
import json
import os
import re
import subprocess
import sys
import types

import jax
import pytest

from chipbench import cells, program, run
from chipbench import layers as L
from chipbench import trace as T
from chipbench.tests import tiny

NEW = ("forward_ms", "backward_ms", "optimizer_ms", "stage_ms",
       "unscoped_ms")
LAYERS = {"forward": L.is_forward, "backward": L.is_backward,
          "optimizer": L.is_optimizer, "stage": L.is_stage,
          "round": L.is_round, "unscoped": L.is_unscoped}


def tiny_root(tmp: str, **traffic) -> str:
    """The small cell, with the program's per-layer metrics read there."""
    root = tiny.make_root(tmp, **traffic)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"].append("tiny-cell")
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def host_spans(path: str):
    """The program's (``train.*``) and the benchmark's (``bench.*``) host
    spans of the ``.xplane.pb`` at ``path``, in start order."""
    from jax.profiler import ProfileData
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                # an annotation with arguments is named ``name#k=v#``
                name = e.name.split("#")[0]
                if name.startswith(("train.", "bench.")):
                    spans.append(T.Span(name, float(e.start_ns),
                                        float(e.duration_ns)))
    return sorted(spans, key=lambda s: s.start_ns)


def traced_run(root: str, devices):
    """One traced run of the small cell; returns its result, the window
    the readers read and the host spans of its trace."""
    cell = cells.load(root, "tiny-cell")
    seen = {}
    real = cell.readers["forward_ms"]
    real_load = T.load

    def spy(win):
        seen["win"] = win
        return real.read(win)

    def load(path, hlo_text=None):
        seen["spans"] = host_spans(path)
        return real_load(path, hlo_text)

    cell.readers["forward_ms"] = types.SimpleNamespace(read=spy)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr("chipbench.counts.peaks", lambda kind: {
            "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
        mp.setattr(T, "load", load)
        out = run.run(cell, 2 ** 31 + 17, 0.5, True, devices)
    return out, seen["win"], seen["spans"]


def _inside(inner, outer) -> bool:
    return outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns


def facts(out, win, spans) -> dict:
    """What the tests check of one traced run, as JSON."""
    tr = win.trace
    dev = win.devices()[0]

    def nested(name, parent):
        return [any(_inside(s, o) for o in spans if o.name == parent)
                for s in spans if s.name == name]

    return {
        "correct": out["correct"], "devices": win.devices(),
        "metrics": {k: v["value"] for k, v in out["metrics"].items()},
        "not_one_layer": [[o.name, o.scope, under]
                          for o in tr.ops
                          if len(under := [k for k, pred in LAYERS.items()
                                           if pred(o)]) != 1],
        "busy": win.op_seconds()[dev],
        "in_layers": win.op_seconds(
            lambda o: any(pred(o) for pred in LAYERS.values()))[dev],
        "parts": {k: win.op_seconds(pred)[dev]
                  for k, pred in LAYERS.items()},
        "bench_spans": sorted({s.name for s in tr.spans}),
        "program_spans": sorted({s.name for s in spans
                                 if s.name.startswith("train.")}),
        "batch_in_bench": nested("train.batch", "bench.batch"),
        "dispatch_in_bench": nested("train.dispatch", "bench.dispatch"),
        "place_in_batch": nested("train.place", "train.batch")}


# four forced host devices, in a subprocess, so that ``XLA_FLAGS`` is read
# before JAX starts
FOUR = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
from chipbench.tests import test_chipbench_program_trace as t
out, win, spans = t.traced_run(t.tiny_root(sys.argv[1],
                                           placement="worker_per_chip"),
                               jax.devices()[:4])
print("RESULT " + json.dumps(t.facts(out, win, spans)))
"""


@pytest.fixture(scope="module")
def stacked(tmp_path_factory):
    root = tiny_root(str(tmp_path_factory.mktemp("root")))
    return facts(*traced_run(root, jax.devices()[:1]))


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(tiny.ROOT, "src"), tiny.ROOT,
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, "-c", FOUR, str(tmp_path_factory.mktemp("root"))],
        capture_output=True, text=True, env=env, timeout=900)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("RESULT ")]
    assert proc.returncode == 0 and lines, proc.stderr[-3000:]
    return json.loads(lines[-1][len("RESULT "):])


@pytest.mark.parametrize("placement", ["stacked", "four"])
def test_every_new_reader_reads(request, placement):
    res = request.getfixturevalue(placement)
    assert res["correct"]
    got = res["metrics"]
    assert set(NEW) <= set(got)
    for name in NEW:
        assert got[name] > 0, (name, got[name])


@pytest.mark.parametrize("placement", ["stacked", "four"])
def test_every_op_falls_under_one_layer(request, placement):
    res = request.getfixturevalue(placement)
    assert res["not_one_layer"] == []


def test_layers_cover_the_busy_time(stacked):
    """Every busy instant lies in a layer and no op in two, so on a chip,
    which runs one op at a time, the six add up to its busy time.  XLA's
    CPU runtime runs independent ops of a step on several threads at
    once, so here the layers' times overlap and are not added."""
    parts = stacked["parts"]
    assert all(parts[k] > 0 for k in ("forward", "backward", "optimizer",
                                      "stage", "round")), parts
    assert stacked["in_layers"] == pytest.approx(stacked["busy"], rel=1e-9)


@pytest.mark.parametrize("scope,layer", [
    ("jit(train_step)/train.grad/vmap(jvp())/dot_general", "forward"),
    ("jit(train_step)/train.grad/vmap(transpose(jvp()))/dot_general",
     "backward"),
    ("jit(train_step)/train.grad/vmap(transpose(jvp()))/while/body/"
     "closed_call/checkpoint/rematted_computation/sub", "backward"),
    ("jit(train_step)/train.optimizer/mul", "optimizer"),
    ("jit(train_step)/comm.stage/concatenate", "stage"),
    ("jit(train_step)/comm.scatter/slice", "stage"),
    ("jit(train_step)/comm.encode/pallas_call", "round"),
    ("jit(train_step)/comm.decode_reduce/concatenate", "round"),
    ("jit(train_step)/convert_element_type", "unscoped"),
    ("", "unscoped"),
])
def test_the_layer_of_a_scope_path(scope, layer):
    op = T.Op(0, "fusion.1", scope, 0, 1)
    assert [k for k, pred in LAYERS.items() if pred(op)] == [layer]


def test_program_spans_nest_in_the_benchmarks(stacked):
    steps = tiny.TRAFFIC["trace_steps"]
    assert stacked["bench_spans"] == ["bench.batch", "bench.dispatch",
                                      "bench.fetch"]
    assert stacked["program_spans"] == ["train.batch", "train.dispatch"]
    assert stacked["batch_in_bench"] == [True] * steps
    assert stacked["dispatch_in_bench"] == [True] * steps
    # stacked workers are not placed on a mesh
    assert stacked["place_in_batch"] == []


def test_placement_nests_in_the_batch_on_four_devices(four):
    steps = tiny.TRAFFIC["trace_steps"]
    assert four["devices"] == [0, 1, 2, 3]
    assert four["program_spans"] == ["train.batch", "train.dispatch",
                                     "train.place"]
    assert four["place_in_batch"] == [True] * steps
    assert four["batch_in_bench"] == [True] * steps
    assert four["dispatch_in_bench"] == [True] * steps


def test_readers_with_nothing_to_read():
    """An older program: no ``train.*`` scope or span."""
    ops = [T.Op(0, "fusion.1", "jit(train_step)/comm.encode/add", 0, 50),
           T.Op(0, "fusion.2", "jit(train_step)/mul", 60, 30)]
    win = T.Window(T.Trace(ops, [T.Span("bench.batch", 0, 10)],
                           "HLO op_name"), 0, 100, 1, 1, {}, {})
    for name in NEW:
        reader = cells.import_file(
            os.path.join(tiny.BENCH, "metrics", name + ".py"),
            "test_program_trace_" + name)
        assert reader.read(win) is None, name


EXECUTED_NOT = ("parameter", "constant", "get-tuple-element", "tuple",
                "bitcast")


def executed_op_names(hlo: str):
    """op_name of each instruction of compiled HLO text that can run as a
    device op: not inside a fusion or a reducer, and not a parameter,
    constant or tuple plumbing."""
    comps, cur, called = {}, None, set()
    for line in hlo.splitlines():
        if not line.startswith(" ") and line.rstrip().endswith("{"):
            cur = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)", line).group(1)
            comps[cur] = []
            continue
        called.update(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", line))
        # the opcode: the first word after the shape that opens operands
        m = re.match(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s.*?\s([a-z][\w\-]*)\(",
                     line)
        on = re.search(r'op_name="([^"]*)"', line)
        if m and on and cur is not None and m.group(1) not in EXECUTED_NOT:
            comps[cur].append(on.group(1))
    return [o for c, names in comps.items() if c not in called
            for o in names]


def test_scopes_cover_the_step_program(tmp_path):
    from repro.obs.trace import SCOPES
    cell = cells.load(tiny_root(str(tmp_path)), "tiny-cell")
    trainer = program.build(cell, 5, jax.devices()[:1])
    state = program.init_state(trainer, cell, 5)
    names = executed_op_names(run.lower_text(trainer, state, 0))
    unscoped = [o for o in names if not set(o.split("/")) & set(SCOPES)]
    assert len(names) > 100
    assert len(unscoped) < 0.05 * len(names), unscoped
