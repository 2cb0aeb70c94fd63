import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: jax locks the device
# count at first init.  Test hook (used by tests/test_dryrun_small.py):
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combination
on the production mesh, with ShapeDtypeStruct inputs (no allocation), and
extract the roofline terms (analysis/roofline.py) from the compiled artifact.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                     # all 40 x 2
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b \
        --shape decode_32k --multi-pod --algo moniqua --bits 8
    ... --out results.json   (incremental append; safe to re-run)

Exit code is non-zero if any requested combination fails to compile.
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import roofline as RL
from repro.configs import assigned_archs, get_config
from repro.configs.base import ArchConfig, InputShape, get_input_shape
from repro.core.algorithms import AlgoHyper, get_algorithm
from repro.core.moniqua import MoniquaCodec
from repro.core.quantizers import QuantSpec
from repro.core.theta import ThetaSchedule
from repro.core.topology import ring
from repro.launch.mesh import (make_host_mesh, make_production_mesh,
                               mesh_shape_dict)
from repro.models.model_factory import build_model
from repro.models.sharding import ShardingRules
from repro.optim.sgd import SGDConfig
from repro.train import serve_step as SS
from repro.train import train_step as TS


def skip_reason(cfg: ArchConfig, shape: InputShape) -> Optional[str]:
    if cfg.name == "whisper-base" and shape.name == "long_500k":
        return ("full-attention encoder-decoder is quadratic; no sub-quadratic "
                "variant implemented (DESIGN.md §5)")
    return None


def input_specs(model, shape: InputShape, n_workers: int, stacked: bool):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    spec = model.batch_spec(shape)
    out = {}
    for name, (shp, dt) in spec.items():
        if stacked:
            assert shp[0] % n_workers == 0, (shp, n_workers)
            shp = (n_workers, shp[0] // n_workers) + tuple(shp[1:])
        out[name] = jax.ShapeDtypeStruct(shp, dt)
    return out


def _named(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


@dataclasses.dataclass
class DryrunResult:
    arch: str
    shape: str
    mesh: str
    status: str
    seconds: float = 0.0
    error: str = ""
    memory: Dict[str, float] = dataclasses.field(default_factory=dict)
    roofline: Dict[str, Any] = dataclasses.field(default_factory=dict)
    collectives: Dict[str, Any] = dataclasses.field(default_factory=dict)
    sim: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def row(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               mesh=None, algo: str = "moniqua", bits: int = 8,
               wire: str = "moniqua", comm_backend: str = "auto",
               comm_path: str = "auto", chunks: int = 1,
               tiers: int = 1, telemetry: bool = False,
               scenario: Optional[str] = None,
               verbose: bool = True, override: Optional[dict] = None,
               rec=None) -> DryrunResult:
    """One (arch x shape x mesh) lower+compile.  ``rec`` (a
    ``repro.obs.trace.SpanRecorder``) gets lower/compile phase spans;
    ``telemetry`` threads the obs flag into the train step being lowered,
    so the compiled artifact is the instrumented one."""
    import contextlib
    cfg = get_config(arch)
    if override:
        cfg = dataclasses.replace(cfg, **override)
    shape = get_input_shape(shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    reason = skip_reason(cfg, shape)
    if reason:
        return DryrunResult(arch, shape_name, mesh_name, "skipped",
                            error=reason)
    t0 = time.time()

    def span(name):
        if rec is None:
            return contextlib.nullcontext()
        return rec.span(name, tid=f"{arch}/{shape_name}", mesh=mesh_name)

    try:
        mesh = mesh or make_production_mesh(multi_pod=multi_pod)
        ms = mesh_shape_dict(mesh)
        mesh_name = "x".join(str(v) for v in mesh.devices.shape)
        chips = 1
        for v in ms.values():
            chips *= v
        rules = ShardingRules(cfg.dist_mode, multi_pod="pod" in ms)
        model = build_model(cfg)
        n_workers = TS.n_workers_for(cfg, rules, ms)

        from repro.models import sharding as SH
        with jax.set_mesh(mesh), SH.constraint_context(rules, ms):
            with span("dryrun.lower"):
                if shape.kind == "train":
                    lowered = _lower_train(model, shape, mesh, ms, rules,
                                           n_workers, algo, bits, wire,
                                           comm_backend, comm_path, chunks,
                                           tiers, telemetry)
                elif shape.kind == "prefill":
                    lowered = _lower_prefill(model, shape, mesh, ms, rules)
                else:
                    lowered = _lower_decode(model, shape, mesh, ms, rules)
            with span("dryrun.compile"):
                compiled = lowered.compile()
        mem = compiled.memory_analysis()
        print(f"[{arch} x {shape_name} x {mesh_name}] memory_analysis:",
              mem)
        ca = compiled.cost_analysis() or {}
        print(f"[{arch} x {shape_name} x {mesh_name}] cost_analysis: "
              f"flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e}")
        roof = RL.roofline_from_compiled(
            compiled, RL.model_flops_for(cfg, shape), chips)
        stats = RL.parse_collectives(compiled.as_text())
        sim_pred: Dict[str, Any] = {}
        if scenario and shape.kind == "train":
            hp = _hyper(cfg, n_workers, algo, bits, wire, comm_backend,
                        comm_path, chunks, tiers, telemetry)
            with span("dryrun.sim"):
                sim_pred = _sim_predict(scenario, model, hp, n_workers,
                                        roof)
            if verbose:
                print(f"[{arch} x {shape_name} x {mesh_name}] sim "
                      f"{scenario}: round="
                      f"{sim_pred['predicted_round_s']*1e3:.3f}ms "
                      f"({sim_pred['network_overhead_x']:.2f}x roofline "
                      f"bound)")
        res = DryrunResult(
            arch, shape_name, mesh_name, "ok", seconds=time.time() - t0,
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_estimate_gb": (mem.argument_size_in_bytes
                                     + mem.output_size_in_bytes
                                     + mem.temp_size_in_bytes
                                     - mem.alias_size_in_bytes) / 1e9,
            },
            roofline={
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
            collectives={"counts": stats.counts,
                         "bytes": stats.bytes_by_op,
                         "summary": stats.summary()},
            sim=sim_pred,
        )
        if verbose:
            r = res.roofline
            print(f"[{arch} x {shape_name} x {mesh_name}] OK in "
                  f"{res.seconds:.1f}s  dominant={r['dominant']} "
                  f"compute={r['compute_s']*1e3:.3f}ms "
                  f"memory={r['memory_s']*1e3:.3f}ms "
                  f"collective={r['collective_s']*1e3:.3f}ms  "
                  f"colls: {res.collectives['summary']}")
        return res
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        tb = traceback.format_exc(limit=20)
        if verbose:
            print(f"[{arch} x {shape_name} x {mesh_name}] FAIL: {e}")
        return DryrunResult(arch, shape_name, mesh_name, "error",
                            seconds=time.time() - t0, error=f"{e}\n{tb}")


def _hyper(cfg, n_workers, algo, bits, wire="moniqua", comm_backend="auto",
           comm_path="auto", chunks=1, tiers=1, telemetry=False,
           worker_axes=()):
    topo = ring(n_workers)
    spec = QuantSpec(bits=bits, stochastic=bits > 1)
    return AlgoHyper(topo=topo, codec=MoniquaCodec(spec), theta=2.0,
                     wire=wire, backend=comm_backend, path=comm_path,
                     chunks=chunks, tiers=tiers, telemetry=telemetry,
                     worker_axes=worker_axes)


def _sim_predict(scenario_name: str, model, hp, n_workers: int, roof):
    """Price one gossip round of this config on a named sim scenario.

    Compute time per round = the roofline bound of the compiled step (the
    best the chips can do); network time = the engine's wire bytes under
    the scenario's link model.  The ratio says how much the scenario's
    network inflates the step beyond the hardware bound.
    """
    from repro.sim import events as SE
    from repro.sim import scenarios as SC

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    X_ab = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((n_workers,) + a.shape, a.dtype),
        params)
    eng = hp.engine()
    bytes_round = eng.bytes_per_round(X_ab)
    compute_s = max(roof.bound_s, 1e-9)
    sc = SC.get_scenario(scenario_name, n=n_workers, compute_s=compute_s)
    trace = SE.simulate_sync_rounds(sc, eng.payload_bytes_per_broadcast(X_ab),
                                    num_rounds=25)
    return {
        "scenario": sc.name,
        "bytes_per_round": bytes_round,
        "predicted_round_s": trace.mean_round_seconds,
        "roofline_bound_s": roof.bound_s,
        "network_overhead_x": trace.mean_round_seconds / compute_s,
    }


def _lower_train(model, shape, mesh, ms, rules, n_workers, algo_name, bits,
                 wire="moniqua", comm_backend="auto", comm_path="auto",
                 chunks=1, tiers=1, telemetry=False):
    algo = get_algorithm(algo_name)
    hp = _hyper(model.cfg, n_workers, algo_name, bits, wire, comm_backend,
                comm_path, chunks, tiers, telemetry,
                rules.worker_axes if tiers <= 1 else ())
    tcfg = TS.TrainStepConfig(algo=algo_name, sgd=SGDConfig(), lr=0.1,
                              theta=ThetaSchedule(mode="constant", value=2.0))
    step = TS.make_train_step(model, hp, tcfg)
    state_ab = TS.abstract_state(model, algo, hp, n_workers)
    batch_ab = input_specs(model, shape, n_workers, stacked=True)
    state_sh = _named(mesh, TS.state_pspecs(model, algo, hp, rules, ms,
                                            n_workers))
    batch_sh = _named(mesh, TS.batch_pspecs(batch_ab, rules, ms, stacked=True))
    jf = jax.jit(step, in_shardings=(state_sh, batch_sh),
                 out_shardings=(state_sh, None), donate_argnums=(0,))
    return jf.lower(state_ab, batch_ab)


def _lower_prefill(model, shape, mesh, ms, rules):
    pstep = SS.make_prefill_step(model)
    params_ab = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch_ab = input_specs(model, shape, 1, stacked=False)
    params_sh = _named(mesh, TS.params_pspecs(model, rules, ms,
                                              stacked=False))
    batch_sh = _named(mesh, TS.batch_pspecs(batch_ab, rules, ms,
                                            stacked=False))
    jf = jax.jit(pstep, in_shardings=(params_sh, batch_sh))
    return jf.lower(params_ab, batch_ab)


def _lower_decode(model, shape, mesh, ms, rules):
    sstep = SS.make_serve_step(model)
    params_ab = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache_ab = SS.abstract_cache(model, shape)
    tok_ab = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    params_sh = _named(mesh, TS.params_pspecs(model, rules, ms,
                                              stacked=False))
    cache_sh = _named(mesh, SS.cache_pspecs(model, shape, rules, ms))
    from repro.models.sharding import safe_pspec
    tok_sh = NamedSharding(mesh, safe_pspec(tok_ab.shape,
                                            rules.pspec("global_batch", None),
                                            ms))
    jf = jax.jit(sstep, in_shardings=(params_sh, cache_sh, tok_sh),
                 out_shardings=(None, cache_sh), donate_argnums=(1,))
    return jf.lower(params_ab, cache_ab, tok_ab)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one arch (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true",
                    help="run single-pod AND multi-pod")
    ap.add_argument("--algo", default="moniqua")
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--wire", default="moniqua",
                    choices=["moniqua", "qsgd", "full"],
                    help="CommEngine wire codec for quantized gossip")
    ap.add_argument("--comm-backend", default="auto",
                    choices=["auto", "jnp", "pallas"],
                    help="CommEngine backend")
    ap.add_argument("--comm-path", default="auto",
                    choices=["bucketed", "per_leaf", "auto"],
                    help="CommEngine gossip path: bucketed flat buffer, "
                         "per-leaf mixing, or the memoized auto crossover")
    ap.add_argument("--chunks", type=int, default=1,
                    help="staged-round chunk count for the pipelined "
                         "gossip round (1 = barrier round)")
    ap.add_argument("--tiers", type=int, default=1,
                    help="two-tier hierarchical gossip: workers per node "
                         "(1 = flat single-tier; k>1 puts the named "
                         "topology across n/k nodes with a full-precision "
                         "reduce inside each)")
    ap.add_argument("--scenario", default=None,
                    help="repro.sim scenario name (incl. contended fabrics "
                         "like oversubscribed-tor / shared-uplink-ring and "
                         "calibrated-from-bench): price one gossip round "
                         "of each train config on this simulated network "
                         "(see repro/sim/scenarios.py)")
    ap.add_argument("--out", default=None, help="append JSONL results here")
    ap.add_argument("--telemetry", action="store_true",
                    help="thread AlgoHyper.telemetry into the lowered train "
                         "step (obs_* round-health metrics compile in)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace JSON of the lower/compile "
                         "phase spans (open in Perfetto)")
    ap.add_argument("--log-jsonl", default=None, metavar="PATH",
                    help="write a repro.obs.runlog JSONL: one event per "
                         "combination + phase spans + final result")
    ap.add_argument("--host-mesh", default=None, metavar="DxM[:pod=P]",
                    help="use a small host mesh 'DATAxMODEL' (optionally "
                         "'PODxDATAxMODEL') instead of the 256-chip "
                         "production mesh; pair with REPRO_DRYRUN_DEVICES "
                         "so enough forced host devices exist (CI smoke)")
    ap.add_argument("--reduced", action="store_true",
                    help="shrink every arch to a tiny layer stack before "
                         "lowering (CI-scale smoke; same mesh/sharding "
                         "logic, minutes instead of hours)")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else assigned_archs()
    shapes = [args.shape] if args.shape else list(
        ["train_4k", "prefill_32k", "decode_32k", "long_500k"])
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    override = None
    if args.reduced:
        override = dict(num_layers=2, d_model=256, num_heads=4,
                        num_kv_heads=2, head_dim=64, d_ff=512,
                        vocab_size=512, remat=False)

    rec = writer = None
    if args.trace or args.log_jsonl:
        from repro.obs.trace import SpanRecorder
        rec = SpanRecorder()
    if args.log_jsonl:
        from repro.obs.runlog import RunLogWriter
        writer = RunLogWriter(args.log_jsonl, run=vars(args), tool="dryrun")

    failures = 0
    try:
        for mp in meshes:
            if args.host_mesh:
                dims = [int(x) for x in args.host_mesh.lower().split("x")]
                if len(dims) == 3:
                    mesh = make_host_mesh(data=dims[1], model=dims[2],
                                          pod=dims[0])
                    mp = True
                else:
                    mesh = make_host_mesh(data=dims[0], model=dims[1])
                    mp = False
            else:
                mesh = make_production_mesh(multi_pod=mp)
            for arch in archs:
                for shape in shapes:
                    res = dryrun_one(arch, shape, multi_pod=mp, mesh=mesh,
                                     algo=args.algo, bits=args.bits,
                                     wire=args.wire,
                                     comm_backend=args.comm_backend,
                                     comm_path=args.comm_path,
                                     chunks=args.chunks, tiers=args.tiers,
                                     telemetry=args.telemetry,
                                     scenario=args.scenario,
                                     override=override, rec=rec)
                    if res.status == "error":
                        failures += 1
                    if args.out:
                        with open(args.out, "a") as f:
                            f.write(json.dumps(res.row()) + "\n")
                    if writer is not None:
                        writer.event("dryrun", {
                            "arch": res.arch, "shape": res.shape,
                            "mesh": res.mesh, "status": res.status,
                            "seconds": res.seconds,
                            "peak_estimate_gb":
                                res.memory.get("peak_estimate_gb")})
        if writer is not None:
            writer.spans_from(rec)
            writer.result(failures=failures,
                          combinations=len(meshes) * len(archs) * len(shapes))
        if rec is not None and args.trace:
            rec.save(args.trace, process_name="dryrun")
    finally:
        if writer is not None:
            writer.close()
    print(f"dry-run complete; failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
