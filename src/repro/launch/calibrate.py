import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Depth-probe roofline calibration (see DESIGN.md §Roofline-calibration).

XLA's ``cost_analysis`` counts a ``while`` body exactly ONCE, so any model
whose layer stack is a ``lax.scan`` (dense / moe / vlm / audio here) reports
flops / bytes / collective-bytes for a single layer.  This pass lowers two
UNROLLED shallow probes (1 and 2 layers, ``unroll_layers=True``) per
(arch x shape) on the single-pod mesh and extrapolates

    cost(L) = c1 + (L - 1) * (c2 - c1)

which is exact for homogeneous stacks (embedding/head live in the intercept).
ssm / hybrid stacks are Python loops (fully counted); their residual
undercount is the element-wise inter-chunk scan bodies only — documented,
not corrected.

Usage:
    PYTHONPATH=src python -m repro.launch.calibrate --out calibrated.jsonl
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Dict, Optional

import jax

from repro.analysis import roofline as RL
from repro.configs import assigned_archs, get_config
from repro.configs.base import get_input_shape
from repro.launch import dryrun as DR
from repro.launch.mesh import make_production_mesh, mesh_shape_dict
from repro.models.model_factory import build_model
from repro.models.sharding import ShardingRules
from repro.train import train_step as TS

SCANNED_FAMILIES = ("dense", "moe", "vlm", "audio")


def _probe(arch: str, shape, mesh, ms, depth: int, algo: str, bits: int):
    cfg = get_config(arch)
    ov: Dict = dict(num_layers=depth, unroll_layers=True)
    if cfg.family == "audio":
        ov["encoder_layers"] = depth
    cfg = dataclasses.replace(cfg, **ov)
    rules = ShardingRules(cfg.dist_mode, multi_pod="pod" in ms)
    model = build_model(cfg)
    n_workers = TS.n_workers_for(cfg, rules, ms)
    from repro.models import sharding as SH
    with jax.set_mesh(mesh), SH.constraint_context(rules, ms):
        if shape.kind == "train":
            lowered = DR._lower_train(model, shape, mesh, ms, rules,
                                      n_workers, algo, bits)
        elif shape.kind == "prefill":
            lowered = DR._lower_prefill(model, shape, mesh, ms, rules)
        else:
            lowered = DR._lower_decode(model, shape, mesh, ms, rules)
        compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    stats = RL.parse_collectives(compiled.as_text())
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)), stats)


def _extrapolate(c1: float, c2: float, L: int) -> float:
    return max(c1 + (L - 1) * (c2 - c1), 0.0)


def calibrate_one(arch: str, shape_name: str, mesh, ms, *,
                  algo: str = "moniqua", bits: int = 8) -> dict:
    cfg = get_config(arch)
    shape = get_input_shape(shape_name)
    if DR.skip_reason(cfg, shape):
        return {"arch": arch, "shape": shape_name, "mesh": "16x16",
                "status": "skipped"}
    if cfg.family not in SCANNED_FAMILIES:
        return {"arch": arch, "shape": shape_name, "mesh": "16x16",
                "status": "not-scanned"}
    t0 = time.time()
    try:
        f1, b1, s1 = _probe(arch, shape, mesh, ms, 1, algo, bits)
        f2, b2, s2 = _probe(arch, shape, mesh, ms, 2, algo, bits)
        L = cfg.num_layers
        flops = _extrapolate(f1, f2, L)
        nbytes = _extrapolate(b1, b2, L)
        coll_bytes: Dict[str, float] = {}
        coll_counts: Dict[str, float] = {}
        for op in set(s1.bytes_by_op) | set(s2.bytes_by_op):
            coll_bytes[op] = _extrapolate(s1.bytes_by_op.get(op, 0),
                                          s2.bytes_by_op.get(op, 0), L)
            coll_counts[op] = _extrapolate(s1.counts.get(op, 0),
                                           s2.counts.get(op, 0), L)
        total_coll = sum(coll_bytes.values())
        chips = 1
        for v in ms.values():
            chips *= v
        roof = RL.Roofline(
            flops=flops, bytes_accessed=nbytes, collective_bytes=total_coll,
            compute_s=flops / RL.HW["peak_flops"],
            memory_s=nbytes / RL.HW["hbm_bw"],
            collective_s=total_coll / RL.HW["ici_bw"],
            model_flops=RL.model_flops_for(cfg, shape), chips=chips)
        row = {
            "arch": arch, "shape": shape_name, "mesh": "16x16",
            "status": "ok", "seconds": time.time() - t0,
            "probe": {"L1": {"flops": f1, "bytes": b1},
                      "L2": {"flops": f2, "bytes": b2},
                      "num_layers": L},
            "roofline_calibrated": {
                "flops_per_chip": roof.flops,
                "bytes_per_chip": roof.bytes_accessed,
                "collective_bytes_per_chip": roof.collective_bytes,
                "compute_s": roof.compute_s,
                "memory_s": roof.memory_s,
                "collective_s": roof.collective_s,
                "dominant": roof.dominant,
                "bound_s": roof.bound_s,
                "model_flops": roof.model_flops,
                "useful_ratio": roof.useful_ratio,
                "mfu_upper_bound": roof.mfu_upper_bound,
            },
            "collectives_calibrated": {"counts": coll_counts,
                                       "bytes": coll_bytes},
        }
        r = row["roofline_calibrated"]
        print(f"[{arch} x {shape_name}] calibrated in {row['seconds']:.0f}s "
              f"dominant={r['dominant']} compute={r['compute_s']*1e3:.2f}ms "
              f"memory={r['memory_s']*1e3:.2f}ms "
              f"collective={r['collective_s']*1e3:.2f}ms "
              f"useful={r['useful_ratio']:.3f} mfu<= {r['mfu_upper_bound']:.3f}")
        return row
    except Exception as e:  # noqa: BLE001
        print(f"[{arch} x {shape_name}] calibration FAIL: {e}")
        return {"arch": arch, "shape": shape_name, "mesh": "16x16",
                "status": "error",
                "error": f"{e}\n{traceback.format_exc(limit=10)}"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--algo", default="moniqua")
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    mesh = make_production_mesh(multi_pod=False)
    ms = mesh_shape_dict(mesh)
    archs = [args.arch] if args.arch else assigned_archs()
    shapes = ([args.shape] if args.shape else
              ["train_4k", "prefill_32k", "decode_32k", "long_500k"])
    failures = 0
    for arch in archs:
        for shape in shapes:
            row = calibrate_one(arch, shape, mesh, ms, algo=args.algo,
                                bits=args.bits)
            if row["status"] == "error":
                failures += 1
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
    print(f"calibration complete; failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
