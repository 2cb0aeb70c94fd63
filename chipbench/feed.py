"""The training rows of every step, made from the seed.

One general generator for every cell: the configuration's reference names
the batch's fields (``batch_spec``) and the traffic file their sizes.  Row
``k`` of step ``s`` is drawn from ``fold_in(PRNGKey(seed), s)``: token ids
uniform over the real vocabulary, float fields standard normal in the
configuration's dtype.  This is the program's own synthetic feed
(``repro.data.pipeline.SyntheticLMPipeline``), copied so that the reference
reads its rows from the benchmark and not from the program: a step fed
other rows than these reads as a loss and gradient gap.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def global_batch(seed: int, step: int, spec, vocab_size: int):
    """``{name: [global_batch, ...]}`` of step ``step``."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    out = {}
    for name, shape, dtype in spec:
        key, k = jax.random.split(key)
        dt = jnp.dtype(dtype)
        if jnp.issubdtype(dt, jnp.integer):
            out[name] = jax.random.randint(k, shape, 0, vocab_size,
                                           dtype=jnp.int32)
        else:
            out[name] = jax.random.normal(k, shape, jnp.float32).astype(dt)
    return out


def worker_batch(seed: int, step: int, spec, vocab_size: int, n: int):
    """The same rows split over ``n`` workers: ``[n, global_batch / n, ...]``."""
    return {k: v.reshape(n, v.shape[0] // n, *v.shape[1:])
            for k, v in global_batch(seed, step, spec, vocab_size).items()}
