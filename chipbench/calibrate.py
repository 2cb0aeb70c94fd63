#!/usr/bin/env python3
"""Readings that the limits of the ``correct`` comparison are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--look-seed 1] \
        [--out <file.json>]

For each seed, in one process on the chip, at the cell's own sizes:

* ``program``: the program's first three steps (the same set-up as a run)
  against the float32 reference, which gives the lower readings;
* for the ``--control-seeds``, ``control``: the reference with every
  matrix operand in float8 (e4m3), one precision below the
  configuration's bfloat16, against the float32 reference, which gives
  the upper reading;
* for the ``--fault-seeds``: the reference with one fault planted in the
  program's place (``half_batch``: each worker's loss over half its rows;
  ``no_exchange``: the gossip round left out) against the sound reference.
  A state left unchanged reads 1 on ``grad_gap`` and ``change_gap`` by
  construction and needs no run;
* with ``--look-seed``: where, element by element, the program's
  parameters after three steps differ from the reference's.

Prints one line per reading and writes them all as JSON.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

FAULTS = ("half_batch", "no_exchange")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--look-seed", type=int, default=None)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import jax
    from chipbench import algorithm_ref as AR
    from chipbench import cells, check, program
    from chipbench.run import CHECK_STEPS
    from repro.launch import compile_cache
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: no TPU found", file=sys.stderr)
        return 2
    compile_cache.enable()
    cell = cells.load(ROOT, args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    fault_seeds = [int(s) for s in args.fault_seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    trainer = None
    rows = []

    def record(kind, seed, prog, ref, secs):
        nums = check.readings(prog, ref)
        rows.append({"kind": kind, "seed": seed, "readings": nums,
                     "seconds": secs,
                     **{f"{side}_{k}": [float(x) for x in d[k]]
                        for side, d in (("prog", prog), ("ref", ref))
                        for k in ("loss", "grad", "change")}})
        print(f"{kind:12s} seed {seed}: "
              + " ".join(f"{k} {v:.6g}" for k, v in nums.items()
                         if k in check.NAMES)
              + f" ({secs:.1f} s)", flush=True)

    for seed in seeds:
        seed32 = seed % 2 ** 32
        t0 = time.perf_counter()
        if trainer is None:
            trainer = program.build(cell, seed32, devices[:cell.chips])
        trainer.pipeline.seed = seed32
        state = program.init_state(trainer, cell, seed32)
        state, prog = program.first_steps(trainer, state, cell, seed32,
                                          CHECK_STEPS)
        del state
        gc.collect()
        t1 = time.perf_counter()
        ref = AR.reference_numbers(cell, seed32, CHECK_STEPS,
                                   devices=devices[:cell.chips])
        t2 = time.perf_counter()
        record("program", seed, prog, ref, t1 - t0)
        print(f"reference    seed {seed}: {t2 - t1:.1f} s", flush=True)
        if seed in control_seeds:
            ctl = AR.reference_numbers(cell, seed32, CHECK_STEPS, "fp8",
                                       devices=devices[:cell.chips])
            record("control", seed, ctl, ref, time.perf_counter() - t2)
        for fault in FAULTS if seed in fault_seeds else ():
            t3 = time.perf_counter()
            bad = AR.reference_numbers(cell, seed32, CHECK_STEPS,
                                       fault=fault,
                                       devices=devices[:cell.chips])
            record(fault, seed, bad, ref, time.perf_counter() - t3)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                json.dump({"workload": args.workload, "rows": rows}, f,
                          indent=1)
    if args.look_seed is not None:
        look(trainer, cell, args.look_seed, devices[:cell.chips])
    return 0


def look(trainer, cell, seed: int, devices) -> None:
    """Where the program's parameters after three steps differ from the
    reference's: per leaf, the elements apart by more than half a jump of
    the round's finest move (one code step times a neighbour's weight),
    and what they add to the gap of the change norms."""
    import jax
    import numpy as np
    from chipbench import algorithm_ref as AR
    from chipbench import program
    from chipbench.run import CHECK_STEPS
    t = cell.traffic
    levels = 2 ** t["bits"]
    delta = 1 / levels if t["bits"] > 1 else 1 / (2 * levels)
    jump = 2 * t["theta"] / (1 - 2 * delta) / levels / 3
    trainer.pipeline.seed = seed
    state = program.init_state(trainer, cell, seed)
    state, _ = program.first_steps(trainer, state, cell, seed, CHECK_STEPS)
    P = jax.device_get(state["params"])
    del state
    ref = AR.reference_numbers(cell, seed, CHECK_STEPS, devices=devices,
                               keep_params=True)
    x0 = jax.device_get(cell.initial_weights(seed))
    paths = jax.tree_util.tree_flatten_with_path(P)[0]
    for (path, p), r, a in zip(paths, jax.tree.leaves(ref["params"]),
                               jax.tree.leaves(x0)):
        p = np.asarray(p, np.float32)
        r = np.asarray(r, np.float32)
        a = np.asarray(a, np.float32)[None]
        d = np.abs(p - r)
        far = d > jump / 2
        dp, dr = np.linalg.norm(p - a), np.linalg.norm(r - a)
        print(f"look seed {seed} {jax.tree_util.keystr(path)}: change "
              f"{dp:.6g} vs {dr:.6g}; elements apart by > half a jump "
              f"({jump / 2:.3g}) {int(far.sum())} of {d.size}, their "
              f"norm {np.linalg.norm(d[far]):.6g}; the rest differ by at "
              f"most {float(d[~far].max(initial=0)):.3g}", flush=True)
        for i in np.argwhere(far)[:3]:
            col = (slice(None),) + tuple(i[1:])
            print(f"    at {i.tolist()}: program {p[col].tolist()} "
                  f"reference {r[col].tolist()} start {a[0][col[1:]]}",
                  flush=True)


if __name__ == "__main__":
    sys.exit(main())
