"""Pure-jnp oracles for the Moniqua codec kernels.

These define the *exact* semantics the Pallas kernels must reproduce
(bitwise, including the in-kernel hash RNG), and are what the tests
``assert_allclose`` against.  The packing here is written independently
of ``core/packing.py`` (plain shifts, test-only: the lane-splitting
reshape does not suit the TPU), so a kernel test never compares the
packing step against itself.  The jnp backend shares only
:func:`codes_ref` and :func:`cmod`.

RNG: stochastic rounding uses a counter-based murmur3-finalizer hash of
``(seed, flat_element_index)`` so that (a) the same element gets the same
uniform draw on every worker (the paper's *shared randomness*, Supp. C) and
(b) kernel and oracle agree bit-for-bit with no PRNG-state threading.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# one source of truth for the bit-exactness-critical hash (every backend
# must draw identical uniforms per (seed, element) pair)
from repro.core.quantizers import _counter_uniform as hash_uniform


def cmod(z: jax.Array, a) -> jax.Array:
    zf = z.astype(jnp.float32)
    a = jnp.float32(a) if not isinstance(a, jax.Array) else a.astype(jnp.float32)
    return zf - a * jnp.floor(zf / a + 0.5)


def codes_ref(x: jax.Array, B, bits: int, stochastic: bool,
              seed: jax.Array, idx: jax.Array) -> jax.Array:
    """Quantization codes of ``Q_delta((x/B) mod 1)`` (Algorithm 1 line 3)."""
    levels = 2 ** bits
    r = cmod(x.astype(jnp.float32) / B, 1.0)           # [-1/2, 1/2)
    lat = (r + 0.5) * levels - 0.5                      # midpoint lattice
    if stochastic:
        u = hash_uniform(seed, idx)
        c = jnp.floor(lat + u)
    else:
        c = jnp.floor(lat + 0.5)
    return jnp.clip(c, 0, levels - 1).astype(jnp.uint8)


def pack_ref(codes: jax.Array, bits: int) -> jax.Array:
    """Pack codes into uint8 along the last axis (must be divisible):
    code ``b*vpb + j`` in byte ``b``, bits ``[j*bits, (j+1)*bits)``."""
    if bits == 8:
        return codes.astype(jnp.uint8)
    vpb = 8 // bits
    g = codes.reshape(*codes.shape[:-1], -1, vpb).astype(jnp.uint8)
    out = jnp.zeros(g.shape[:-1], jnp.uint8)
    for j in range(vpb):
        out = out | (g[..., j] << jnp.uint8(j * bits))
    return out


def unpack_ref(packed: jax.Array, bits: int) -> jax.Array:
    """Inverse of :func:`pack_ref`."""
    if bits == 8:
        return packed
    vpb = 8 // bits
    mask = jnp.uint8(2 ** bits - 1)
    parts = [(packed >> jnp.uint8(j * bits)) & mask for j in range(vpb)]
    return jnp.stack(parts, axis=-1).reshape(*packed.shape[:-1], -1)


def encode_ref(x: jax.Array, B, bits: int, stochastic: bool, seed,
               idx_base=0) -> jax.Array:
    """Full encode: x -> packed uint8.  Last dim must divide values-per-byte.

    ``idx_base`` offsets the counter index: element ``e`` hashes
    ``(seed, idx_base + e)``, matching the kernel's global indexing when
    this array is one segment of a bucketed flat buffer.
    """
    seed = jnp.asarray(seed, jnp.uint32)
    idx = (jnp.asarray(idx_base, jnp.uint32)
           + jnp.arange(x.size, dtype=jnp.uint32).reshape(x.shape))
    codes = codes_ref(x, B, bits, stochastic, seed, idx)
    return pack_ref(codes, bits)


def value_ref(packed: jax.Array, B, bits: int) -> jax.Array:
    """Unpack + dequantize + rescale: the transmitted value ``q * B``."""
    levels = 2 ** bits
    c = unpack_ref(packed, bits).astype(jnp.float32)
    return ((c + 0.5) / levels - 0.5) * jnp.float32(B)


def decode_ref(packed: jax.Array, y: jax.Array, B, bits: int) -> jax.Array:
    """Lemma 1 recovery against local reference ``y``."""
    qb = value_ref(packed, B, bits)
    yf = y.astype(jnp.float32)
    return cmod(qb - yf, B) + yf


def recovered_diff_ref(packed: jax.Array, y: jax.Array, B,
                       bits: int) -> jax.Array:
    """The Lemma-1 recovered neighbor difference ``cmod(q*B - y, B)``
    (``decode_ref`` minus the reference) — what the alias sentinel
    (``moniqua_decode_reduce.alias_band_mask``) thresholds at ``theta``."""
    return cmod(value_ref(packed, B, bits) - y.astype(jnp.float32), B)


def decode_self_ref(packed: jax.Array, x: jax.Array, B, bits: int) -> jax.Array:
    """Algorithm 1 line 4: sender-side biased reconstruction."""
    qb = value_ref(packed, B, bits)
    xf = x.astype(jnp.float32)
    return qb - cmod(xf, B) + xf
