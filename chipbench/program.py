"""The system under test, driven through its own entry points.

The cell's ``Trainer`` is built from the configuration and traffic files;
its state is the program's own (``train_step.init_state``) around weights
the benchmark makes from the seed with the reference's ``init_params``, in
one jitted call on the device.  Every step goes through ``Trainer.batch``
and ``Trainer.step``, the calls ``Trainer.run`` makes.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from chipbench import algorithm_ref as AR


def arch_config(arch: dict):
    from repro.configs.base import ArchConfig, MoEConfig, SSMConfig
    fields = dict(arch)
    ssm, moe = fields.pop("ssm", None), fields.pop("moe", None)
    return ArchConfig(**fields, ssm=SSMConfig(**ssm) if ssm else None,
                      moe=MoEConfig(**moe) if moe else None)


def build(cell, seed: int, devices):
    """The cell's ``Trainer``: workers stacked on one chip, or one worker
    per chip on a mesh of ``devices``."""
    from repro.configs.base import InputShape
    from repro.models.model_factory import build_model
    from repro.train.trainer import Trainer, TrainerConfig
    t = cell.traffic
    model = build_model(arch_config(cell.arch))
    shape = InputShape(cell.name, seq_len=t["seq_len"],
                       global_batch=t["global_batch"], kind="train")
    tc = TrainerConfig(algo="moniqua", topology=t["topology"],
                       n_workers=t["n_workers"], bits=t["bits"],
                       theta=t["theta"], lr=t["lr"], momentum=t["momentum"],
                       weight_decay=t["weight_decay"], wire=t["wire"],
                       backend=t["backend"], comm_path=t["comm_path"],
                       chunks=t["chunks"], seed=seed)
    if t["placement"] == "stacked":
        return Trainer(model, shape, tc)
    if t["placement"] != "worker_per_chip":
        raise ValueError(f"unknown placement {t['placement']!r}")
    from repro.launch.mesh import make_worker_mesh
    from repro.models.sharding import ShardingRules
    return Trainer(model, shape, tc,
                   mesh=make_worker_mesh(devices[:t["n_workers"]]),
                   rules=ShardingRules("decentralized"))


@dataclasses.dataclass(frozen=True)
class _Given:
    """Stands in for the model where ``init_state`` asks for its weights."""
    weights: object

    def init(self, _key):
        return self.weights


def init_state(trainer, cell, seed: int):
    """The program's training state around the benchmark's seeded weights
    (``Cell.initial_weights``), made on the device in one jitted
    call and placed as the trainer places it."""
    key = jax.random.PRNGKey(seed)
    want = jax.eval_shape(trainer.model.init, key)
    x0 = cell.initial_weights(seed)
    if (jax.tree.structure(want) != jax.tree.structure(x0)
            or [(a.shape, a.dtype) for a in jax.tree.leaves(want)]
            != [(a.shape, a.dtype) for a in jax.tree.leaves(x0)]):
        raise ValueError("the reference's parameter layout differs from "
                         "the program's")
    from repro.train import train_step as TS

    def make(x, k):
        return TS.init_state(_Given(x), trainer.algo, trainer.hp,
                             trainer.tc.n_workers, k)

    if trainer.mesh is None:
        return jax.jit(make)(x0, key)
    return jax.jit(make, out_shardings=trainer._state_sh)(x0, key)


def first_steps(trainer, state, cell, seed: int, steps: int):
    """Drive the state through its first ``steps`` steps and read what the
    comparison needs: each loss, the per-leaf momentum norms after the
    first step, the per-leaf parameter change after the last."""
    losses, grad = [], None
    for k in range(steps):
        state, metrics = trainer.step(state, trainer.batch(k))
        losses.append(float(metrics["loss"]))
        if k == 0:
            grad = np.asarray(jax.jit(AR.leaf_norms)(state["mom"]))
    x0 = cell.initial_weights(seed)
    if trainer.mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        x0 = jax.device_put(x0, NamedSharding(trainer.mesh, PartitionSpec()))
    change = jax.jit(AR.change_norms)(state["params"], x0)
    return state, {"loss": losses, "grad": grad, "change": np.asarray(change)}
