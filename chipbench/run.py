#!/usr/bin/env python3
"""Chip benchmark of decentralized Moniqua training: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) and everything
it needs are found by name (``chipbench/cells.py``).  Set-up builds the
cell's ``Trainer``, makes the training state around weights drawn from the
seed, and drives it through the first three steps, which compile the one
step program and feed the comparison that decides ``correct``.  Then:

* ``--trace 0``: steps are dispatched back to back for ``--seconds``, one
  step always queued behind the one running; each step's loss is fetched
  after the next is dispatched.  Prints the end-to-end metrics.
* ``--trace 1``: the profiler traces a few steps of the same loop, and
  the per-layer readers (``chipbench/metrics/<name>.py``) reduce the trace.

Afterwards the program's state is freed and the plain reference repeats
the first three steps; each number compared is printed beside its limit,
as the last lines on standard error and under ``checks`` at the end of the
result.  The last line of standard output is one JSON object.  Without a
TPU, or with fewer chips than the cell asks for, it exits 2 and prints no
result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CHECK_STEPS = 3
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")
BREAKDOWN_ENTRIES = 10


class CompileCounter:
    """Counts the programs JAX lowers (each compile, or each load from the
    persistent cache, lowers one) while the context is entered."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self) -> None:
        self.count = 0

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def step_loop(trainer, state, k0: int, *, steps: int = 0,
              seconds: float = 0.0):
    """Dispatch steps back to back from step ``k0``, fetching step k-1's
    loss after dispatching step k, until ``steps`` steps ran or
    ``seconds`` passed.  Returns the state, the losses and the host clock
    at the start and at each loss's arrival."""
    import jax
    start = time.perf_counter()
    arrivals, losses, pending, k = [], [], None, k0
    while True:
        with jax.profiler.TraceAnnotation("bench.batch"):
            batch = trainer.batch(k)
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            state, metrics = trainer.step(state, batch)
        k += 1
        if pending is not None:
            with jax.profiler.TraceAnnotation("bench.fetch"):
                losses.append(float(pending["loss"]))
            arrivals.append(time.perf_counter())
        pending = metrics
        if steps and k - k0 >= steps:
            break
        if seconds and time.perf_counter() - start >= seconds:
            break
    with jax.profiler.TraceAnnotation("bench.fetch"):
        losses.append(float(pending["loss"]))
    arrivals.append(time.perf_counter())
    return state, losses, start, arrivals


def timed_metrics(start: float, arrivals, setup_s: float,
                  peak: int) -> dict:
    gaps = [b - a for a, b in zip([start] + arrivals[:-1], arrivals)]
    window = arrivals[-1] - start
    return {"step_ms": {"value": window / len(arrivals) * 1e3, "unit": "ms"},
            "step_ms_p90": {"value": statistics.quantiles(
                gaps, n=10, method="inclusive")[8] * 1e3, "unit": "ms"},
            "peak_hbm_gb": {"value": peak / 1e9, "unit": "GB"},
            "setup_s": {"value": setup_s, "unit": "s"}}


def window_counts(cell, trainer, devices) -> dict:
    """What the per-layer readers count with, from shapes and the cell."""
    import jax
    from chipbench import counts as C
    from repro.train import train_step as TS
    t = cell.traffic
    leaves = jax.tree.leaves(jax.eval_shape(trainer.model.init,
                                            jax.random.PRNGKey(0)))
    params = TS.abstract_state(trainer.model, trainer.algo, trainer.hp,
                               t["n_workers"])["params"]
    return {"flops_per_step": cell.reference.train_flops(cell.arch, t),
            "wire_bytes": trainer.algo.bytes_per_step(params, trainer.hp),
            "elems": C.flat_elems([a.shape for a in leaves]),
            "itemsize": leaves[0].dtype.itemsize,
            "bits": t["bits"], "wire": t["wire"],
            "neighbors": len(trainer.hp.comm_topo().neighbor_offsets()),
            "workers_per_chip": t["n_workers"] // len(devices)}


def traced_window(cell, trainer, state, k0: int, devices, kind: str,
                  hlo: str):
    """Trace ``trace_steps`` steps of the loop; returns the state, the
    losses, the per-layer metrics, busy and window seconds and the
    breakdown.  ``hlo``: the step program's compiled text."""
    import jax
    from chipbench import counts as C
    from chipbench import trace as T
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    with jax.profiler.trace(TRACE_DIR):
        state, losses, start, arrivals = step_loop(
            trainer, state, k0, steps=cell.traffic["trace_steps"])
    tr = T.load(T.find_xplane(TRACE_DIR), hlo)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    # from the first loss's arrival to the last: each step in between ran
    # with the next one queued behind it, as in the timed window
    fetches = [s for s in tr.spans if s.name == "bench.fetch"]
    win = T.Window(tr, fetches[0].end_ns, fetches[-1].end_ns,
                   len(fetches) - 1, len(devices), C.peaks(kind),
                   window_counts(cell, trainer, devices))
    metrics = {}
    for m in cell.per_layer:
        v = cell.readers[m["name"]].read(win)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    busy = win.op_seconds()
    print(f"trace: {len(tr.ops)} device ops on devices {win.devices()}, "
          f"scopes from {tr.scope_method}; window {win.seconds:.6f} s, "
          f"{win.steps} steps", file=sys.stderr)
    return (state, losses, metrics,
            sum(busy.values()) / max(len(busy), 1), win.seconds,
            breakdown(win))


def lower_text(trainer, state, k: int) -> str:
    """Compiled HLO text of the step program (from the persistent cache
    after the first run), whose op_name metadata maps ops to scopes."""
    import jax
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        (state, trainer.batch(k)))
    if trainer.mesh is None:
        return trainer.jstep.lower(*abstract).compile().as_text()
    with jax.set_mesh(trainer.mesh):
        return trainer.jstep.lower(*abstract).compile().as_text()


def breakdown(win) -> dict:
    """The device ops that took most time on the first device (self time:
    a loop's own time without the ops of its body), and the longest idle
    gaps labelled by the host span running at their middle."""
    from chipbench import trace as T
    dev = win.devices()[0] if win.devices() else 0
    ops = [o for o in win.trace.ops if o.device == dev
           and win.lo_ns <= o.start_ns < win.hi_ns]
    by_name: dict = {}
    for o, self_ns in zip(ops, T.self_ns(ops)):
        label = o.name + (" " + o.scope[-80:] if o.scope else "")
        by_name[label] = by_name.get(label, 0.0) + self_ns / 1e9
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:BREAKDOWN_ENTRIES]
    gaps = T.gaps_ns([(o.start_ns, o.end_ns) for o in ops], win.lo_ns,
                     win.hi_ns)
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:BREAKDOWN_ENTRIES]:
        mid = (s + e) / 2
        host = [sp.name for sp in win.trace.spans
                if sp.start_ns <= mid < sp.end_ns]
        labelled.append([host[0] if host else "host: between spans",
                         (e - s) / 1e9])
    return {"device_ops": [[k, v] for k, v in top], "idle_gaps": labelled}


def run(cell, seed: int, seconds: float, trace: bool, devices) -> dict:
    """One run of ``cell`` on ``devices``; returns the result object."""
    import gc
    import jax
    from chipbench import algorithm_ref as AR
    from chipbench import check, program
    from repro.launch import compile_cache
    compile_cache.enable()
    seed32 = seed % 2 ** 32
    marks = [("start", T0), ("imports", time.perf_counter())]
    trainer = program.build(cell, seed32, devices)
    marks.append(("trainer", time.perf_counter()))
    state = program.init_state(trainer, cell, seed32)
    marks.append(("state", time.perf_counter()))
    state, prog_numbers = program.first_steps(trainer, state, cell, seed32,
                                              CHECK_STEPS)
    marks.append(("first steps", time.perf_counter()))
    if trace:
        hlo = lower_text(trainer, state, CHECK_STEPS)
        marks.append(("step HLO", time.perf_counter()))
    setup_s = time.perf_counter() - T0
    print("set-up: " + ", ".join(f"{b[0]} {b[1] - a[1]:.2f} s"
                                 for a, b in zip(marks, marks[1:])),
          file=sys.stderr)
    with CompileCounter() as compiles:
        if trace:
            state, losses, metrics, busy_s, window_s, parts = traced_window(
                cell, trainer, state, CHECK_STEPS, devices,
                devices[0].device_kind, hlo)
        else:
            state, losses, start, arrivals = step_loop(
                trainer, state, CHECK_STEPS, seconds=seconds)
    peak = peak_bytes(jax.devices())
    if not trace:
        metrics = timed_metrics(start, arrivals, setup_s, peak)
    print(f"compilations inside the window: {compiles.count}",
          file=sys.stderr)
    del state
    gc.collect()
    ref_numbers = AR.reference_numbers(cell, seed32, CHECK_STEPS,
                                         devices=devices)
    checks = check.judge(check.numbers(prog_numbers, ref_numbers),
                         cell.limits)
    failed = sum(not math.isfinite(v) for v in losses)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": jax.device_count(),
              "memory_peak_bytes": peak}
    if trace:
        device.update(busy_s=busy_s, window_s=window_s)
    out = {"correct": failed == 0 and all(c["ok"] for c in checks.values()),
           "attempted": len(losses), "failed": failed, "metrics": metrics,
           "device": device}
    if trace:
        out["breakdown"] = parts
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import cells
    cell = cells.load(ROOT, args.workload)
    import jax
    devices = jax.devices()
    print(f"device: platform {devices[0].platform}, kind "
          f"{devices[0].device_kind}, count {len(devices)}", file=sys.stderr)
    if devices[0].platform != "tpu":
        print("chipbench: no TPU found; nothing was run", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"chipbench: the cell needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    out = run(cell, args.seed, args.seconds, bool(args.trace),
              devices[:cell.chips])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
