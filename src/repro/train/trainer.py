"""Host-side training loop tying pipeline, step function, and checkpoints.

Works at two scales with the same code path:
  * experiment scale: 1 device, worker dim is a plain array axis;
  * mesh provided (e.g. ``launch.mesh.make_worker_mesh``: one worker per
    chip): state and batch are placed with NamedShardings from
    train_step.state_pspecs / batch_pspecs, the step is jitted with those
    in/out shardings and traced under ``jax.set_mesh``, and the gossip
    engine runs its codec kernels per device on the worker axes.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.comm import gossip
from repro.core.algorithms import AlgoHyper, get_algorithm
from repro.core.moniqua import MoniquaCodec
from repro.core.topology import get_topology
from repro.data.pipeline import SyntheticLMPipeline
from repro.models.model_factory import Model
from repro.models.sharding import ShardingRules
from repro.obs import trace as obs_trace
from repro.train import train_step as TS

PyTree = Any


@dataclasses.dataclass
class TrainerConfig:
    algo: str = "moniqua"
    topology: str = "ring"
    n_workers: int = 8
    bits: int = 8
    theta: float = 2.0
    gamma: float = 1.0          # Choco/DeepSqueeze consensus step size
    slack: float = 1.0          # Theorem 3 slack matrix W_bar = s W + (1-s) I
    lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 5e-4
    steps: int = 100
    log_every: int = 10
    seed: int = 0
    checkpoint_path: Optional[str] = None
    checkpoint_every: int = 0
    wire: str = "moniqua"       # CommEngine wire codec (moniqua | qsgd |
                                #   ef_qsgd | onebit | full)
    backend: str = "auto"       # CommEngine backend (jnp | pallas | auto)
    comm_path: str = "auto"     # gossip path: bucketed | per_leaf | auto
    chunks: int = 1             # staged-round chunk count (1 = barrier)
    overlap: str = "none"       # step-level overlap: none | stale (moniqua)
    warmup: int = 16            # onebit wire: fp32 rounds before 1-bit+EF
    tiers: int = 1              # 1 = flat gossip; k>1 = two-tier hierarchy
                                #   (nodes of k workers, tc.topology across
                                #   nodes, full-precision reduce inside)
    telemetry: bool = False     # round-health obs_* metrics (repro.obs);
                                #   static flag — off costs nothing under jit
    log_jsonl: Optional[str] = None   # schema-versioned run log (repro.obs.
                                #   runlog); drained metrics + spans + result
    trace_path: Optional[str] = None  # Chrome-trace JSON of the host-side
                                #   phase spans (Perfetto / chrome://tracing)
    presence: Optional[tuple] = None  # elastic 0/1 worker mask for every
                                #   round (AlgoHyper.presence); None = all up
    deadline: Optional[float] = None  # sim round deadline in seconds
                                #   (recorded; enforced by sim/faults.py)


def build_hyper(tc: TrainerConfig, worker_axes: tuple = ()) -> AlgoHyper:
    from repro.core.quantizers import QuantSpec
    topo = get_topology(tc.topology, tc.n_workers)
    if tc.slack < 1.0:
        topo = topo.slack(tc.slack)
    spec = QuantSpec(bits=tc.bits, stochastic=tc.bits > 1)
    presence = None if tc.presence is None else tuple(tc.presence)
    return AlgoHyper(topo=topo, codec=MoniquaCodec(spec), theta=tc.theta,
                     gamma=tc.gamma, wire=tc.wire, backend=tc.backend,
                     path=tc.comm_path, chunks=tc.chunks, overlap=tc.overlap,
                     warmup=tc.warmup, telemetry=tc.telemetry,
                     tiers=tc.tiers, presence=presence,
                     deadline=tc.deadline, worker_axes=tuple(worker_axes))


class Trainer:
    def __init__(self, model: Model, shape, tc: TrainerConfig,
                 mesh: Optional[Mesh] = None,
                 rules: Optional[ShardingRules] = None):
        self.model, self.tc = model, tc
        if mesh is not None and rules is None:
            raise ValueError("a mesh needs its ShardingRules")
        # the tiered round stages its own mesh-axis collectives; only the
        # single-tier round runs the codec kernels per device
        self.hp = build_hyper(tc, rules.worker_axes
                              if mesh is not None and tc.tiers <= 1 else ())
        self.algo = get_algorithm(tc.algo)
        from repro.core.theta import ThetaSchedule
        from repro.optim.sgd import SGDConfig
        self.tcfg = TS.TrainStepConfig(
            algo=tc.algo,
            sgd=SGDConfig(momentum=tc.momentum, weight_decay=tc.weight_decay),
            lr=tc.lr,
            theta=ThetaSchedule(mode="constant", value=tc.theta,
                                n=tc.n_workers,
                                rho=self.hp.comm_topo().rho))
        self.pipeline = SyntheticLMPipeline(model, shape, tc.n_workers,
                                            seed=tc.seed)
        # warm the bucket-layout cache from the abstract state so the flat
        # gossip buffer's static layout (and the auto-path crossover) is
        # built exactly once, outside jit; every traced round then hits the
        # memoized BucketLayout
        abstract = TS.abstract_state(model, self.algo, self.hp,
                                     tc.n_workers)
        self.hp.exact_engine().layout(abstract["params"])
        eng = self.hp.engine()
        eng.layout(abstract["params"])
        if tc.algo in ("moniqua", "moniqua_d2"):
            # the buffer the quantized round stages in (CommEngine.staging);
            # the one-round-stale round keeps the [n, D] buffer
            staging = ("flat" if tc.algo == "moniqua"
                       and tc.overlap == "stale" and not eng.stateful
                       else eng.staging(abstract["params"], self.hp.presence))
            print(f"trainer: gossip round staging {staging}",
                  file=sys.stderr)
        self.step_fn = TS.make_train_step(model, self.hp, self.tcfg)
        self.mesh = mesh
        if mesh is None:
            self.jstep = jax.jit(self.step_fn, donate_argnums=(0,))
            return
        mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        sp = TS.state_pspecs(model, self.algo, self.hp, rules, mesh_shape,
                             tc.n_workers)
        self._state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), sp)
        bp = TS.batch_pspecs(jax.eval_shape(self.pipeline.worker_batch, 0),
                             rules, mesh_shape)
        self._batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), bp)
        self.jstep = jax.jit(
            self.step_fn, donate_argnums=(0,),
            in_shardings=(self._state_sh, self._batch_sh),
            out_shardings=(self._state_sh, NamedSharding(mesh, P())))

    def batch(self, k: int, rec=None) -> PyTree:
        """The stacked batch of global step ``k``, placed on the mesh.
        The host spans go to ``rec`` (a ``SpanRecorder``) when given."""
        with obs_trace.span("train.batch", rec, tid="train", step=k):
            b = self.pipeline.worker_batch(k)
            if self.mesh is None:
                return b
            with obs_trace.span("train.place", rec, tid="train"):
                return jax.device_put(b, self._batch_sh)

    def step(self, state: PyTree, batch: PyTree, rec=None):
        """One jitted train step (under the worker mesh, if any)."""
        with obs_trace.span("train.dispatch", rec, tid="train"):
            if self.mesh is None:
                return self.jstep(state, batch)
            with jax.set_mesh(self.mesh):
                return self.jstep(state, batch)

    def init_state(self) -> PyTree:
        key = jax.random.PRNGKey(self.tc.seed)
        state = TS.init_state(self.model, self.algo, self.hp,
                              self.tc.n_workers, key)
        if self.mesh is not None:
            state = jax.device_put(state, self._state_sh)
        return state

    def bytes_per_step(self, state) -> int:
        return self.algo.bytes_per_step(state["params"], self.hp)

    def restore_state(self, path: Optional[str] = None) -> PyTree:
        """Rebuild FULL trainer state (params, momentum, algorithm extras
        including any ``WireState``, step, g_inf, PRNG key) from the
        ``<checkpoint_path>.state`` file ``run()`` writes.  Passing the
        result back into ``run()`` resumes bit-identically — the contract
        ``tests/test_ckpt_state.py`` pins down."""
        from repro.checkpoint import ckpt
        path = path or self.tc.checkpoint_path
        if not path:
            raise ValueError("restore_state needs a checkpoint path "
                             "(argument or TrainerConfig.checkpoint_path)")
        state = ckpt.restore(path + ".state", self.init_state())
        if self.mesh is not None:
            state = jax.device_put(state, self._state_sh)
        return state

    def run(self, state: Optional[PyTree] = None,
            callback: Optional[Callable[[int, Dict], None]] = None
            ) -> Dict[str, Any]:
        from repro.checkpoint import ckpt
        tc = self.tc
        state = state if state is not None else self.init_state()
        # resume-aware: a restored state carries its own step counter, and
        # the data pipeline is indexed by the global step, so a resumed run
        # replays exactly the batches the uninterrupted run would have seen
        k0 = int(jax.device_get(state["step"]))
        history: List[Dict] = []
        rec = writer = None
        if tc.trace_path or tc.log_jsonl:
            rec = obs_trace.SpanRecorder()
        if tc.log_jsonl:
            from repro.obs.runlog import RunLogWriter
            run_meta = dataclasses.asdict(tc)
            run_meta["theta_mode"] = self.tcfg.theta.mode
            writer = RunLogWriter(tc.log_jsonl, run=run_meta, tool="trainer")
        t0 = time.time()
        try:
            for k in range(k0, k0 + tc.steps):
                state, metrics = self.step(state, self.batch(k, rec), rec)
                if (k - k0) % tc.log_every == 0 or k == k0 + tc.steps - 1:
                    # drain the whole metrics dict in ONE host transfer —
                    # per-scalar float() round-trips device-synced once per
                    # metric per log point
                    with obs_trace.span("train.fetch", rec, tid="train",
                                        step=k):
                        got = jax.device_get(metrics)
                    m = {kk: float(v) for kk, v in got.items()}
                    m["step"] = k
                    m["wall"] = time.time() - t0
                    history.append(m)
                    if writer is not None:
                        writer.step(k, {kk: v for kk, v in m.items()
                                        if kk not in ("step", "wall")},
                                    wall_s=m["wall"])
                    if callback:
                        callback(k, m)
                if (tc.checkpoint_path and tc.checkpoint_every
                        and (k + 1) % tc.checkpoint_every == 0):
                    meta = {"step": k + 1, "algo": tc.algo, "wire": tc.wire}
                    with obs_trace.span("train.checkpoint", rec, tid="train",
                                        step=k + 1):
                        # params-only artifact (the eval/restore surface)
                        ckpt.save(tc.checkpoint_path, state["params"], meta)
                        # ... plus the FULL state (momentum, WireState,
                        # counters, PRNG key) so training resumes
                        # bit-identically
                        ckpt.save(tc.checkpoint_path + ".state", state, meta)
            bps = self.bytes_per_step(state)
            if writer is not None:
                writer.spans_from(rec)
                writer.result(bytes_per_step=bps,
                              steps=tc.steps, wall_s=time.time() - t0)
            if rec is not None and tc.trace_path:
                rec.save(tc.trace_path, process_name="trainer")
        finally:
            if writer is not None:
                writer.close()
        return {"state": state, "history": history,
                "bytes_per_step": bps}
