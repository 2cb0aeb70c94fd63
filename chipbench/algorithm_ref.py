"""Plain reference of decentralized training with Moniqua gossip.

The paper's Algorithm 1 with heavy-ball momentum, written from the paper
and independent of the program.  Each of ``n`` workers holds its own model
(leaves stacked ``[n, ...]``) and, in every step:

1. takes the gradient of its own batch: ``g_i``;
2. updates its momentum: ``m_i <- mu m_i + g_i + wd x_i``;
3. gossips (one round on the ring, ``W_ii = W_i,i+-1 = 1/3``):

   * moniqua wire, with ``B = 2 theta / (1 - 2 delta)`` and ``q_i =
     Q_delta((x_i / B) mod 1)`` on the midpoint lattice of ``2**bits``
     points (nearest rounding, or stochastic rounding with one uniform per
     element shared by all workers)::

         x_hat_ii = q_i B - (x_i mod B) + x_i
         x_hat_ij = (q_j B - x_i) mod B + x_i
         x_i     <- x_i + sum_j W_ij (x_hat_ij - x_hat_ii)

   * full wire: ``x_i <- sum_j W_ij x_j``;
4. steps: ``x_i <- x_i - lr m_i``.

``mod`` is the centred modulo into ``[-a/2, a/2)``.  Arithmetic is float32;
the parameters are stored in the configuration's dtype after the gossip and
after the step, as the configuration states.  The stochastic-rounding
uniforms are this reference's own, so at 8 bits an element whose workers
differ may land one level apart from the program's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

RING_OFFSETS = (-1, 1)


def cmod(z, a):
    return z - a * jnp.floor(z / a + 0.5)


def fp8_operand(x):
    """A matrix operand rounded to float8 (e4m3) in the forward pass, with
    the gradient passed straight through: the control's precision."""
    x = x.astype(jnp.float32)
    q = jnp.clip(x, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(
        jnp.float32)
    return x + jax.lax.stop_gradient(q - x)


def f32_operand(x):
    return x.astype(jnp.float32)


PRECISIONS = {"f32": f32_operand, "fp8": fp8_operand}


def ring_weights(n: int):
    """(offset, weight) pairs of the ring's neighbours; self weight 1/3."""
    if n < 3:
        raise ValueError(f"a ring needs at least 3 workers, got {n}")
    return [(o, 1.0 / 3.0) for o in RING_OFFSETS]


def moniqua_mix(x, theta: float, bits: int, stochastic: bool, u=None):
    """One Moniqua round on one stacked leaf ``x`` ([n, ...], float32);
    ``u`` holds one rounding uniform per element of a worker's leaf."""
    levels = 2 ** bits
    delta = 1.0 / levels if stochastic else 1.0 / (2.0 * levels)
    B = 2.0 * theta / (1.0 - 2.0 * delta)
    lattice = (cmod(x / B, 1.0) + 0.5) * levels - 0.5
    codes = jnp.floor(lattice + (u[None] if stochastic else 0.5))
    q = ((jnp.clip(codes, 0, levels - 1) + 0.5) / levels - 0.5) * B
    own = q - cmod(x, B) + x
    out = x
    for o, w in ring_weights(x.shape[0]):
        q_j = jnp.roll(q, o, axis=0)
        out = out + w * ((cmod(q_j - x, B) + x) - own)
    return out


def full_mix(x):
    out = x / 3.0
    for o, w in ring_weights(x.shape[0]):
        out = out + w * jnp.roll(x, o, axis=0)
    return out


def make_step(ref, cfg, traffic, precision: str = "f32",
              fault: str | None = None, parallel: bool = False):
    """A jitted reference step ``(X, M, batch, step, key) -> (X, M, loss)``.

    ``X`` holds the stacked parameters in the configuration's dtype, ``M``
    the float32 momentum.  ``precision`` puts every matrix operand of the
    model in that precision (``fp8``: the control).  ``fault`` plants one
    of the faults the comparison must catch: ``half_batch`` (each worker's
    loss over the first half of its rows only) or ``no_exchange`` (the
    gossip round left out).  The workers' gradients are taken one after
    the other, or with ``parallel`` side by side (for a worker axis
    sharded over chips).
    """
    mm = PRECISIONS[precision]
    mu, wd, lr = traffic["momentum"], traffic["weight_decay"], traffic["lr"]
    wire, bits, theta = traffic["wire"], traffic["bits"], traffic["theta"]
    stochastic = bits > 1
    dt = jnp.dtype(cfg["dtype"])

    def worker_loss(params, batch):
        if fault == "half_batch":
            batch = jax.tree.map(lambda a: a[: a.shape[0] // 2], batch)
        return ref.loss(params, batch, cfg, mm)

    grad_fn = jax.value_and_grad(worker_loss)

    def step(X, M, batch, k, key):
        with jax.default_matmul_precision("highest"):
            if parallel:
                losses, G = jax.vmap(grad_fn)(X, batch)
            else:
                losses, G = jax.lax.map(lambda a: grad_fn(*a), (X, batch))
        M = jax.tree.map(lambda m, g, x: mu * m + g + wd * x.astype(
            jnp.float32), M, G, X)
        leaves, treedef = jax.tree.flatten(X)
        mixed = []
        for i, x in enumerate(leaves):
            xf = x.astype(jnp.float32)
            if fault == "no_exchange":
                out = xf
            elif wire == "moniqua":
                u = jax.random.uniform(
                    jax.random.fold_in(jax.random.fold_in(key, k), i),
                    x.shape[1:], jnp.float32)
                out = moniqua_mix(xf, theta, bits, stochastic, u)
            elif wire == "full":
                out = full_mix(xf)
            else:
                raise ValueError(f"no reference for wire {wire!r}")
            mixed.append(out.astype(dt))
        X = jax.tree.unflatten(treedef, mixed)
        X = jax.tree.map(lambda x, m: (x.astype(jnp.float32) - lr * m)
                         .astype(dt), X, M)
        return X, M, jnp.mean(losses)

    return jax.jit(step)


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree.leaves(tree)])


def change_norms(X, x0):
    """Per-leaf norm of the stacked parameters' change from ``x0`` (one
    replica, which every worker starts from)."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                    - b.astype(jnp.float32)[None])))
        for a, b in zip(jax.tree.leaves(X), jax.tree.leaves(x0))])


def reference_numbers(cell, seed: int, steps: int, precision: str = "f32",
                      fault: str | None = None, devices=None,
                      keep_params: bool = False) -> dict:
    """The reference's ``steps`` steps from the seed's weights on the seed's
    rows: each loss, the per-leaf momentum norms after the first step, the
    per-leaf parameter change after the last (and, with ``keep_params``,
    the parameters themselves on the host).  With several ``devices`` the
    worker axis is sharded over them."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from chipbench import feed
    ref, arch, t = cell.reference, cell.arch, cell.traffic
    n = t["n_workers"]
    spec = ref.batch_spec(arch, t)
    key = jax.random.PRNGKey(seed)
    x0 = cell.initial_weights(seed)
    X = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), x0)
    M = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), X)
    parallel = devices is not None and len(devices) > 1

    def place(tree, spec=PartitionSpec("w")):
        if not parallel:
            return tree
        return jax.device_put(
            tree, NamedSharding(Mesh(list(devices), ("w",)), spec))

    X, M = place(X), place(M)
    step = make_step(ref, arch, t, precision, fault, parallel)
    round_key = jax.random.fold_in(key, 0x5EED)
    losses, grad = [], None
    for k in range(steps):
        batch = place(feed.worker_batch(seed, k, spec, arch["vocab_size"],
                                        n))
        X, M, loss = step(X, M, batch, k, round_key)
        losses.append(float(loss))
        if k == 0:
            grad = np.asarray(jax.jit(leaf_norms)(M))
    out = {"loss": losses, "grad": grad,
           "change": np.asarray(jax.jit(change_norms)(
               X, place(x0, PartitionSpec())))}
    if keep_params:
        out["params"] = jax.device_get(X)
    return out
