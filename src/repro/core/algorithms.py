"""Decentralized training update rules (the paper's algorithm zoo).

Every algorithm operates on *stacked* worker pytrees (leaves ``[n, ...]``) and
is a pure function, so the same code runs (a) on one CPU device for the paper's
convergence experiments and (b) sharded over the production mesh where the
worker axis is a mesh axis and every neighbor exchange is a collective-permute.

Implemented rules (Table 1 of the paper + the baselines of Sec. 6):

  allreduce    exact centralized SGD (MPI AllReduce analog)
  dpsgd        Lian et al. 2017, full-precision gossip
  naive        direct quantization of exchanged models (Theorem 1: diverges)
  moniqua      Algorithm 1 (modulo-quantized gossip, zero extra memory)
  choco        ChocoSGD (Koloskova et al. 2019): local estimators x_hat, Θ(md)
  deepsqueeze  Tang et al. 2019: error-compensated compression, Θ(nd)
  dcd          DCD-PSGD (Tang et al. 2018): difference compression + replicas
  ecd          ECD-PSGD: extrapolated difference compression + replicas
  d2 / moniqua_d2   D^2 (Tang et al. 2018) variance reduction, Sec. 5

Gradient input ``g`` is the (optionally momentum-processed) local direction;
``alpha`` the current step size.  ``AlgoHyper`` carries the per-algorithm knobs.

Notes on baseline fidelity: DCD/ECD replica updates follow the difference /
extrapolated-difference schemes of Tang et al. 2018; ECD's extrapolation
weights are simplified to (1/2, 1/2) — the qualitative property the paper
tests (divergence under <= 2-bit budgets) is preserved and reproduced.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.engine import CommEngine, FullPrecisionWire, make_wire
from repro.core.moniqua import MoniquaCodec
from repro.core.quantizers import QuantSpec
from repro.core import topology
from repro.core.topology import Topology
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

PyTree = Any


@dataclasses.dataclass(frozen=True)
class AlgoHyper:
    """Static hyper-parameters shared by the update rules.

    All communication routes through :class:`~repro.comm.engine.CommEngine`:
    ``engine()`` builds the configured wire codec (``wire`` x ``codec.spec``
    x ``backend``) for the quantized-gossip algorithms, ``exact_engine()``
    the full-precision engine the baselines (and replica mixing) use.
    Swapping codec, topology, or backend is a one-field change here.

    ``telemetry`` turns on the engine's round-health observability
    (``repro.obs.metrics``): the instrumented algorithms (Moniqua family,
    DPSGD, D2) then carry the accumulated health dict under
    ``extra["health"]`` and the trainer surfaces it as ``obs_*`` metrics.
    Purely observational — params / payloads / WireState are bit-exact
    with the flag on or off.

    **Elastic rounds** (``docs/elasticity.md``): ``presence`` hands the
    instrumented algorithms a static 0/1 worker mask to pass into the
    engine's ``mix(presence=...)`` — absent workers take the identity
    mix, the rest renormalize; ``None`` / all-ones is bit-exact with
    today's gossip.  Distinct masks retrace the jitted step (the mask is
    static), so per-round time-varying masks belong in an eager loop
    (``bench_elastic``) or a schedule of pre-traced steps.  ``deadline``
    is the round deadline in seconds the *simulator* enforces when the
    run's wall clock is priced (``sim.faults.FaultSpec.deadline_s``); the
    in-step math never reads it — it rides here so one hyper object
    carries the full elastic configuration into run logs and benches.
    """
    topo: Topology
    codec: MoniquaCodec = MoniquaCodec()
    theta: float = 2.0            # Moniqua a-priori bound (paper used 2.0)
    gamma: float = 1.0            # consensus step size (Choco/DeepSqueeze/Thm 3 slack)
    naive_delta: float = 0.05     # absolute lattice pitch for the naive baseline
    wire: str = "moniqua"         # wire codec for quantized gossip (engine())
    backend: str = "auto"         # comm backend: jnp | pallas | auto
    path: str = "auto"            # gossip path: bucketed | per_leaf | auto
    chunks: int = 1               # staged-round chunk count (1 = barrier)
    overlap: str = "none"         # step-level overlap: none | stale (Moniqua)
    warmup: int = 16              # onebit wire: fp32 rounds before 1-bit+EF
    telemetry: bool = False       # round-health observability (repro.obs)
    tiers: int = 1                # 1 = flat gossip; k>1 = two-tier, nodes of k
    presence: Optional[Tuple[int, ...]] = None   # elastic 0/1 worker mask
    deadline: Optional[float] = None             # sim round deadline (s)
    worker_axes: Tuple[str, ...] = ()  # mesh axes of the worker dim (engine)

    def comm_topo(self):
        """The topology the engines gossip on: ``topo`` itself for flat
        (``tiers=1``) runs, or the two-tier hierarchy with ``topo`` as the
        *inter* graph over ``n // tiers`` nodes and a fully-connected intra
        tier of ``tiers`` workers.  A ``HierarchicalTopology`` passed
        directly as ``topo`` wins over ``tiers``.
        """
        if isinstance(self.topo, topology.HierarchicalTopology):
            return self.topo
        if self.tiers <= 1:
            return self.topo
        # rebuild from the base family, replaying any slack factors the
        # flat name carries ("ring-slack0.9") onto the inter tier — the
        # only quantized tier, hence the only one Theorem 3 damps
        parts = self.topo.name.split("-slack")
        hier = topology.two_tier(self.topo.n, self.tiers,
                                 inter_name=parts[0])
        for g in parts[1:]:
            hier = hier.slack(float(g))
        return hier

    def engine(self) -> CommEngine:
        return CommEngine(self.comm_topo(),
                          make_wire(self.wire, self.codec.spec,
                                    warmup=self.warmup),
                          self.backend, path=self.path, chunks=self.chunks,
                          telemetry=self.telemetry,
                          worker_axes=self.worker_axes)

    def exact_engine(self, telemetry: bool = False) -> CommEngine:
        """Full-precision engine.  ``telemetry`` is opt-in per call site:
        the instrumented baselines (DPSGD, D2) pass ``self.telemetry``;
        internal replica/estimator mixing (Choco, DCD, ...) leaves it off."""
        return CommEngine(self.comm_topo(), FullPrecisionWire(),
                          self.backend, path=self.path, chunks=self.chunks,
                          telemetry=telemetry, worker_axes=self.worker_axes)


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------

def _sgd(X: PyTree, g: PyTree, alpha) -> PyTree:
    with obs_trace.named_phase("train.optimizer"):
        return jax.tree.map(lambda x, d: (x - alpha * d).astype(x.dtype),
                            X, g)


def _norm_quantize(v: jax.Array, bits: int, key: Optional[jax.Array],
                   unbiased: bool = False) -> jax.Array:
    """Per-worker norm-scaled linear quantizer (used by Choco/DeepSqueeze/DCD/ECD).

    bits >= 2: scale_i = max_j |v_ij| per worker row; codes cover
    [-scale, scale] with 2**bits levels, stochastic rounding.  Payload =
    codes + one f32 scale per worker per tensor.

    bits == 1 and not unbiased: scaled sign ``sign(v) * mean|v|`` — the
    standard *biased* 1-bit compressor the contraction-based methods
    (Choco/DeepSqueeze) admit (paper Table 1 "supports biased quantizers").
    DCD/ECD's theory REQUIRES unbiased quantizers, so they must use
    1-bit stochastic rounding — whose variance at 1 bit is what makes them
    diverge there (Table 2 "diverge").
    """
    red_axes = tuple(range(1, v.ndim))
    if bits == 1 and not unbiased:
        scale = jnp.mean(jnp.abs(v), axis=red_axes, keepdims=True)
        return jnp.sign(v) * scale
    scale = jnp.max(jnp.abs(v), axis=red_axes, keepdims=True) + 1e-12
    levels = 2 ** bits
    lat = (v / (2.0 * scale) + 0.5) * (levels - 1)
    if key is None:
        codes = jnp.floor(lat + 0.5)
    else:
        codes = jnp.floor(lat + jax.random.uniform(key, v.shape))
    codes = jnp.clip(codes, 0, levels - 1)
    return (codes / (levels - 1) - 0.5) * 2.0 * scale


def _nq_tree(V: PyTree, bits: int, key: Optional[jax.Array],
             unbiased: bool = False) -> PyTree:
    leaves, td = jax.tree.flatten(V)
    keys = [None] * len(leaves) if key is None else list(jax.random.split(key, len(leaves)))
    return jax.tree.unflatten(td, [_norm_quantize(l, bits, k, unbiased)
                                   for l, k in zip(leaves, keys)])


def _zeros_like(X: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, X)


def _tree_bytes(X: PyTree) -> int:
    return sum(int(np.prod(l.shape, dtype=np.int64)) * l.dtype.itemsize
               for l in jax.tree.leaves(X))


# ---------------------------------------------------------------------------
# Algorithm definitions
# ---------------------------------------------------------------------------

class Algorithm:
    """Base: subclasses override init/step and the two accounting methods."""
    name: str = "base"
    quantized: bool = False

    def init(self, X: PyTree, hp: AlgoHyper) -> PyTree:
        return {}

    def step(self, X: PyTree, extra: PyTree, g: PyTree, alpha, k,
             key: Optional[jax.Array], hp: AlgoHyper) -> Tuple[PyTree, PyTree]:
        raise NotImplementedError

    def bytes_per_step(self, X: PyTree, hp: AlgoHyper) -> int:
        """Payload bytes *sent* per worker per iteration."""
        raise NotImplementedError

    def extra_memory_bytes(self, X: PyTree, hp: AlgoHyper) -> int:
        """Per-worker additional state vs full-precision D-PSGD (Table 1).

        Reported per the paper's accounting (conceptual replicas for the
        replica-based schemes, regardless of implementation sharing).
        """
        return 0

    # -- common accounting pieces ------------------------------------------
    @staticmethod
    def _model_bytes(X: PyTree) -> int:
        """Per-worker full-precision model bytes (d * itemsize)."""
        n = jax.tree.leaves(X)[0].shape[0]
        return _tree_bytes(X) // n


class AllReduce(Algorithm):
    name = "allreduce"

    def step(self, X, extra, g, alpha, k, key, hp):
        Xh = _sgd(X, g, alpha)
        Xm = jax.tree.map(lambda x: jnp.broadcast_to(
            jnp.mean(x.astype(jnp.float32), axis=0, keepdims=True), x.shape
        ).astype(x.dtype), Xh)
        return Xm, extra

    def bytes_per_step(self, X, hp):
        return 2 * self._model_bytes(X)  # ring allreduce ~2x model bytes/worker


class DPSGD(Algorithm):
    name = "dpsgd"

    def init(self, X, hp):
        return ({"health": obs_metrics.init_health()} if hp.telemetry
                else {})

    def step(self, X, extra, g, alpha, k, key, hp):
        eng = hp.exact_engine(telemetry=hp.telemetry)
        # theta rides along as a pure diagnostic: "what bound would a
        # Moniqua wire need here" — the full wire itself ignores it
        res = eng.mix(X, theta=hp.theta, presence=hp.presence)
        if hp.telemetry:
            extra = dict(extra)
            extra["health"] = obs_metrics.accumulate_health(
                extra["health"], res.health)
        return _sgd(res.x, g, alpha), extra

    def bytes_per_step(self, X, hp):
        return hp.exact_engine().bytes_per_round(X)


class NaiveQuant(Algorithm):
    """Direct quantization of exchanged models (Eq. 4) — the Theorem 1 failure."""
    name = "naive"
    quantized = True

    def step(self, X, extra, g, alpha, k, key, hp):
        d = hp.naive_delta

        def q(v, kk):
            lat = v / d
            u = 0.5 if kk is None else jax.random.uniform(kk, v.shape)
            return d * jnp.floor(lat + u)

        leaves, td = jax.tree.flatten(X)
        keys = [None] * len(leaves) if key is None else list(jax.random.split(key, len(leaves)))
        Q = jax.tree.unflatten(td, [q(l, kk) for l, kk in zip(leaves, keys)])
        eng = hp.exact_engine()
        mixed = jax.tree.map(
            lambda x, nb: x * eng.self_weight() + nb,
            X, eng.neighbor_sum(Q, lambda v, o: v))
        return _sgd(mixed, g, alpha), extra

    def bytes_per_step(self, X, hp):
        # same code width as an 8-bit budget for comparison purposes
        return self._model_bytes(X) // 4 * len(hp.topo.neighbor_offsets())


class Moniqua(Algorithm):
    """Algorithm 1 (gossip through the engine's configured wire codec).

    With a stateful wire (``hp.wire`` in ``ef_qsgd``/``onebit``) this is the
    error-feedback gossip family: the per-worker ``WireState`` (residual +
    warmup counter) lives under ``extra["wire"]`` and is threaded through
    the engine's ``mix`` carry — which is exactly what puts EF's Θ(nd)
    buffers on the Table 1/2 memory axis while Moniqua's own wire stays at
    zero (``extra_memory_bytes``).

    ``hp.overlap == "stale"`` (stateless Moniqua wire only) switches the
    round to the engine's one-round-stale ``mix_stale``: step k applies
    the consensus delta decoded from round k-1's payloads, and the gossip
    carry (previous packed residue + its reference/B) lives under
    ``extra["gossip"]`` — the step-level overlap that lets the decode
    hide behind the next forward pass."""
    name = "moniqua"
    quantized = True

    def init(self, X, hp):
        eng = hp.engine()
        extra = {}
        if eng.stateful:
            extra["wire"] = eng.init_wire_state(X)
        elif hp.overlap == "stale":
            extra["gossip"] = eng.init_gossip_carry(X)
        if hp.telemetry:
            extra["health"] = obs_metrics.init_health()
        return extra

    def step(self, X, extra, g, alpha, k, key, hp):
        eng = hp.engine()
        new_extra = dict(extra)
        if eng.stateful:
            res = eng.mix(X, theta=hp.theta, key=key, state=extra["wire"],
                          presence=hp.presence)
            new_extra["wire"] = res.state
        elif hp.overlap == "stale":
            res = eng.mix_stale(X, extra["gossip"], theta=hp.theta, key=key,
                                presence=hp.presence)
            new_extra["gossip"] = res.state
        else:
            res = eng.mix(X, theta=hp.theta, key=key, presence=hp.presence)
        if hp.telemetry:
            new_extra["health"] = obs_metrics.accumulate_health(
                extra["health"], res.health)
        return _sgd(res.x, g, alpha), new_extra

    def bytes_per_step(self, X, hp):
        return hp.engine().bytes_per_round(X)

    def extra_memory_bytes(self, X, hp):
        # 0 for the moniqua wire (the headline claim); residual + counter
        # for the EF wires (Θ(nd) graph-wide)
        return hp.engine().wire_state_bytes(X)


class ChocoSGD(Algorithm):
    """Koloskova et al. 2019: gossip on quantized estimators x_hat."""
    name = "choco"
    quantized = True

    def init(self, X, hp):
        return {"x_hat": _zeros_like(X)}

    def step(self, X, extra, g, alpha, k, key, hp):
        x_hat = extra["x_hat"]
        Xh = _sgd(X, g, alpha)
        q = _nq_tree(jax.tree.map(lambda a, b: a - b, Xh, x_hat),
                     hp.codec.spec.bits, key)
        x_hat = jax.tree.map(lambda a, b: a + b, x_hat, q)
        mixed_hat = hp.exact_engine().mix(x_hat).x
        Xn = jax.tree.map(
            lambda x, mh, h: (x + hp.gamma * (mh - h)).astype(x.dtype),
            Xh, mixed_hat, x_hat)
        return Xn, {"x_hat": x_hat}

    def bytes_per_step(self, X, hp):
        return (self._model_bytes(X) * hp.codec.spec.bits // 32
                * len(hp.topo.neighbor_offsets()))

    def extra_memory_bytes(self, X, hp):
        # replicas of every neighbor's estimator + own: Θ(m d) graph-wide
        return self._model_bytes(X) * (len(hp.topo.neighbor_offsets()) + 1)


class DeepSqueeze(Algorithm):
    """Tang et al. 2019: error-compensated compressed gossip."""
    name = "deepsqueeze"
    quantized = True

    def init(self, X, hp):
        return {"err": _zeros_like(X)}

    def step(self, X, extra, g, alpha, k, key, hp):
        e = extra["err"]
        Xh = _sgd(X, g, alpha)
        v = jax.tree.map(lambda a, b: a + b, Xh, e)
        c = _nq_tree(v, hp.codec.spec.bits, key)
        e = jax.tree.map(lambda a, b: a - b, v, c)
        mixed_c = hp.exact_engine().mix(c).x
        Xn = jax.tree.map(
            lambda x, mc, ci: (x + hp.gamma * (mc - ci)).astype(x.dtype),
            Xh, mixed_c, c)
        return Xn, {"err": e}

    def bytes_per_step(self, X, hp):
        return (self._model_bytes(X) * hp.codec.spec.bits // 32
                * len(hp.topo.neighbor_offsets()))

    def extra_memory_bytes(self, X, hp):
        return self._model_bytes(X)  # Θ(n d) graph-wide = one buffer per worker


class DCD(Algorithm):
    """DCD-PSGD: replicas x_hat updated with quantized model differences."""
    name = "dcd"
    quantized = True

    def init(self, X, hp):
        # copy=True: an f32 astype would alias X's buffers and break donation
        return {"x_hat": jax.tree.map(
            lambda x: jnp.array(x, dtype=jnp.float32, copy=True), X)}

    def step(self, X, extra, g, alpha, k, key, hp):
        x_hat = extra["x_hat"]
        mixed_hat = hp.exact_engine().mix(x_hat).x
        Xn = _sgd(jax.tree.map(lambda x, mh, h: x + (mh - h), X, mixed_hat, x_hat),
                  g, alpha)
        z = jax.tree.map(lambda a, b: a - b, Xn, x_hat)
        q = _nq_tree(z, hp.codec.spec.bits, key, unbiased=True)
        x_hat = jax.tree.map(lambda a, b: a + b, x_hat, q)
        return Xn, {"x_hat": x_hat}

    def bytes_per_step(self, X, hp):
        return (self._model_bytes(X) * hp.codec.spec.bits // 32
                * len(hp.topo.neighbor_offsets()))

    def extra_memory_bytes(self, X, hp):
        return self._model_bytes(X) * (len(hp.topo.neighbor_offsets()) + 1)


class ECD(DCD):
    """ECD-PSGD: extrapolated difference compression."""
    name = "ecd"

    def step(self, X, extra, g, alpha, k, key, hp):
        x_hat = extra["x_hat"]
        mixed_hat = hp.exact_engine().mix(x_hat).x
        Xn = _sgd(jax.tree.map(lambda x, mh, h: x + (mh - h), X, mixed_hat, x_hat),
                  g, alpha)
        z = jax.tree.map(lambda a, b: 2.0 * a - b, Xn, x_hat)  # extrapolation
        q = _nq_tree(z, hp.codec.spec.bits, key, unbiased=True)
        x_hat = jax.tree.map(lambda a, b: 0.5 * (a + b), x_hat, q)
        return Xn, {"x_hat": x_hat}


class D2(Algorithm):
    """D^2 (Tang et al. 2018): variance-reduced decentralized SGD, Sec. 5."""
    name = "d2"

    def init(self, X, hp):
        extra = {"x_prev": jax.tree.map(
                     lambda x: jnp.array(x, dtype=jnp.float32, copy=True), X),
                 "g_prev": _zeros_like(X),
                 "alpha_prev": jnp.zeros((), jnp.float32)}
        if hp.telemetry:
            extra["health"] = obs_metrics.init_health()
        return extra

    def _half_step(self, X, extra, g, alpha):
        x_prev, g_prev, a_prev = extra["x_prev"], extra["g_prev"], extra["alpha_prev"]
        return jax.tree.map(
            lambda x, xp, gi, gp: 2.0 * x.astype(jnp.float32) - xp
            - alpha * gi + a_prev * gp,
            X, x_prev, g, g_prev)

    def step(self, X, extra, g, alpha, k, key, hp):
        Xh = self._half_step(X, extra, g, alpha)
        eng = hp.exact_engine(telemetry=hp.telemetry)
        res = eng.mix(Xh, theta=hp.theta, presence=hp.presence)
        Xn = jax.tree.map(lambda a, x: a.astype(x.dtype), res.x, X)
        new_extra = {"x_prev": jax.tree.map(lambda x: x.astype(jnp.float32),
                                            X),
                     "g_prev": g,
                     "alpha_prev": jnp.asarray(alpha, jnp.float32)}
        if hp.telemetry:
            new_extra["health"] = obs_metrics.accumulate_health(
                extra["health"], res.health)
        return Xn, new_extra

    def bytes_per_step(self, X, hp):
        return hp.exact_engine().bytes_per_round(X)

    def extra_memory_bytes(self, X, hp):
        return 2 * self._model_bytes(X)  # x_prev + g_prev (inherent to D^2)


class MoniquaD2(D2):
    """Moniqua on D^2 (Algorithm 2): quantized gossip of the half-step.

    Stateful wires ride along like in :class:`Moniqua`: the ``WireState``
    sits under ``extra["wire"]`` next to D^2's own x_prev/g_prev carry."""
    name = "moniqua_d2"
    quantized = True

    def init(self, X, hp):
        extra = super().init(X, hp)
        eng = hp.engine()
        if eng.stateful:
            extra["wire"] = eng.init_wire_state(X)
        return extra

    def step(self, X, extra, g, alpha, k, key, hp):
        Xh = self._half_step(X, extra, g, alpha)
        eng = hp.engine()
        res = eng.mix(Xh, theta=hp.theta, key=key,
                      state=extra["wire"] if eng.stateful else None,
                      presence=hp.presence)
        Xn = jax.tree.map(lambda a, x: a.astype(x.dtype), res.x, X)
        new_extra = {"x_prev": jax.tree.map(lambda x: x.astype(jnp.float32),
                                            X),
                     "g_prev": g,
                     "alpha_prev": jnp.asarray(alpha, jnp.float32)}
        if eng.stateful:
            new_extra["wire"] = res.state
        if hp.telemetry:
            new_extra["health"] = obs_metrics.accumulate_health(
                extra["health"], res.health)
        return Xn, new_extra

    def bytes_per_step(self, X, hp):
        return hp.engine().bytes_per_round(X)

    def extra_memory_bytes(self, X, hp):
        # D^2's inherent x_prev + g_prev, plus any EF wire state
        return (super().extra_memory_bytes(X, hp)
                + hp.engine().wire_state_bytes(X))


ALGORITHMS: Dict[str, Algorithm] = {a.name: a for a in [
    AllReduce(), DPSGD(), NaiveQuant(), Moniqua(), ChocoSGD(), DeepSqueeze(),
    DCD(), ECD(), D2(), MoniquaD2(),
]}


def get_algorithm(name: str) -> Algorithm:
    try:
        return ALGORITHMS[name]
    except KeyError:
        raise ValueError(f"unknown algorithm {name!r}; "
                         f"available: {sorted(ALGORITHMS)}") from None
