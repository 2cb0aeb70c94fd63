"""Decentralized training step: local grads -> optimizer -> gossip rule.

Layout: every training-state leaf carries a leading worker dim ``[n, ...]``
(n = 16 decentralized single-pod, 32 multi-pod; 1/2 hierarchical), sharded
over the worker mesh axes.  Per-worker gradients are ``vmap(grad(loss))`` —
XLA keeps them communication-free along the worker axis; the only cross-worker
traffic is the algorithm's gossip, which every algorithm routes through
``repro.comm.engine.CommEngine`` (quantized collective-permutes for Moniqua;
``AlgoHyper.wire`` / ``AlgoHyper.backend`` / ``AlgoHyper.path`` /
``AlgoHyper.chunks`` select codec, backend, gossip path, and the staged
round's chunk count, and the per-step wire bytes are reported in the step
metrics).  On the bucketed path the gossip inside the jitted step flattens
the whole param tree through a memoized ``comm/bucket.py`` layout — the
trainer warms that cache from the abstract state before jit, so tracing
never rebuilds it.

Stateful wires (``ef_qsgd`` / ``onebit``) need no special-casing here: their
per-worker ``WireState`` (EF residual + warmup counter) lives inside the
algorithm's ``extra`` carry, so it flows through the jitted step, the
``extra_spec`` sharding resolution (residual rows shard on the worker axis,
the counter replicates), and full-state checkpointing like any other
algorithm buffer.  The same holds for ``AlgoHyper.overlap == "stale"``:
the one-round-stale gossip carry (previous packed payload + reference)
rides under ``extra["gossip"]``.

``state_pspecs`` / ``batch_pspecs`` resolve the logical-axis annotations into
PartitionSpecs for jit shardings (trainer and launch/dryrun share them).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.algorithms import AlgoHyper, Algorithm, get_algorithm
from repro.core.theta import ThetaSchedule
from repro.models.model_factory import Model
from repro.models.sharding import ShardingRules, safe_pspec
from repro.obs import trace as obs_trace
from repro.optim import sgd as optim

PyTree = Any


def n_workers_for(cfg, rules: ShardingRules, mesh_shape: Dict[str, int]) -> int:
    axes = rules.worker_axes
    n = 1
    for a in axes:
        n *= mesh_shape.get(a, 1)
    return max(n, 1)


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------

def init_state(model: Model, algo: Algorithm, hp: AlgoHyper, n_workers: int,
               key) -> Dict[str, PyTree]:
    """All workers start from identical weights (assumption A4)."""
    params = model.init(key)
    X = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n_workers,) + a.shape),
                     params)
    return {
        "params": X,
        "mom": optim.init_momentum(X),
        "extra": algo.init(X, hp),
        "step": jnp.zeros((), jnp.int32),
        "g_inf": jnp.ones((), jnp.float32),   # running ||g||_inf for theta
        "key": jax.random.PRNGKey(0),
    }


def abstract_state(model: Model, algo: Algorithm, hp: AlgoHyper,
                   n_workers: int):
    return jax.eval_shape(
        lambda k: init_state(model, algo, hp, n_workers, k),
        jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Logical -> PartitionSpec resolution
# ---------------------------------------------------------------------------

def _lookup_logical(logical, path):
    node = logical
    for part in path:
        if isinstance(part, jax.tree_util.DictKey):
            node = node[part.key]
        elif isinstance(part, jax.tree_util.SequenceKey):
            node = node[part.idx]
        elif isinstance(part, jax.tree_util.GetAttrKey):
            node = getattr(node, part.name)
        else:
            raise TypeError(part)
    return node


def params_pspecs(model: Model, rules: ShardingRules, mesh_shape,
                  stacked: bool = True) -> PyTree:
    logical = model.param_logical()
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def resolve(path, leaf):
        names = tuple(_lookup_logical(logical, path))
        sizes = list(leaf.shape)
        if stacked:
            names = ("worker",) + names
            wn = 1
            for a in (rules.worker_axes or ()):
                wn *= mesh_shape.get(a, 1)
            sizes = [wn] + sizes       # worker dim == product of worker axes
        return safe_pspec(sizes, rules.pspec(*names), mesh_shape)

    return jax.tree_util.tree_map_with_path(resolve, abstract)


def batch_pspecs(batch: PyTree, rules: ShardingRules, mesh_shape,
                 stacked: bool = True) -> PyTree:
    def resolve(leaf):
        if stacked:
            names = ("worker", "batch") + (None,) * (leaf.ndim - 2)
        else:
            names = ("batch",) + (None,) * (leaf.ndim - 1)
        return safe_pspec(leaf.shape, rules.pspec(*names), mesh_shape)
    return jax.tree.map(resolve, batch)


def state_pspecs(model: Model, algo: Algorithm, hp: AlgoHyper,
                 rules: ShardingRules, mesh_shape, n_workers: int) -> PyTree:
    pp = params_pspecs(model, rules, mesh_shape, stacked=True)
    ab = abstract_state(model, algo, hp, n_workers)

    def extra_spec(leaf):
        # algorithm extras mirror param shapes (replicas/error buffers) or are
        # scalars; shard like params when ranks match a leading worker dim
        if leaf.ndim >= 1 and leaf.shape[0] == n_workers:
            names = ("worker",) + (None,) * (leaf.ndim - 1)
            return safe_pspec(leaf.shape, rules.pspec(*names), mesh_shape)
        return P()

    return {
        "params": pp,
        "mom": pp,
        "extra": jax.tree.map(extra_spec, ab["extra"]),
        "step": P(),
        "g_inf": P(),
        "key": P(),
    }


# ---------------------------------------------------------------------------
# The step
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    algo: str = "moniqua"
    sgd: optim.SGDConfig = dataclasses.field(default_factory=optim.SGDConfig)
    lr: float = 0.1
    lr_schedule: Optional[Callable[[jax.Array], jax.Array]] = None
    theta: ThetaSchedule = dataclasses.field(default_factory=ThetaSchedule)


def make_train_step(model: Model, hp: AlgoHyper, tcfg: TrainStepConfig
                    ) -> Callable[[PyTree, PyTree], Tuple[PyTree, PyTree]]:
    algo = get_algorithm(tcfg.algo)
    sched = tcfg.lr_schedule or optim.constant(tcfg.lr)

    def train_step(state, batch):
        X, mom, extra = state["params"], state["mom"], state["extra"]
        step, key = state["step"], state["key"]
        key, k_algo = jax.random.split(key)

        # forward and backward: JAX names the backward's ops under
        # ``train.grad/.../transpose(jvp(...))``
        with obs_trace.named_phase("train.grad"):
            losses, grads = jax.vmap(jax.value_and_grad(model.loss))(X,
                                                                     batch)
        with obs_trace.named_phase("train.optimizer"):
            dirs, mom, g_inf_now = optim.direction(tcfg.sgd, grads, X, mom)
            g_inf = jnp.maximum(0.9 * state["g_inf"], g_inf_now)

        alpha = sched(step)
        theta = tcfg.theta(alpha, g_inf)
        hp_k = dataclasses.replace(hp, theta=theta)
        X, extra = algo.step(X, extra, dirs, alpha, step, k_algo, hp_k)

        new_state = {"params": X, "mom": mom, "extra": extra,
                     "step": step + 1, "g_inf": g_inf, "key": key}
        # bytes_per_step is shape-only bookkeeping: a trace-time constant
        metrics = {"loss": jnp.mean(losses), "alpha": alpha,
                   "theta": jnp.asarray(theta, jnp.float32), "g_inf": g_inf,
                   "wire_bytes": jnp.asarray(
                       algo.bytes_per_step(X, hp), jnp.float32)}
        if isinstance(extra, dict) and "health" in extra:
            # hp.telemetry: the algorithm's accumulated round-health carry
            # (repro.obs.metrics) surfaces as obs_* step metrics
            metrics.update({f"obs_{k}": v
                            for k, v in extra["health"].items()})
        return new_state, metrics

    return train_step
