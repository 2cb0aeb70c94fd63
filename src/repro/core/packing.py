"""Sub-byte code packing, computed on the matrix unit.

The wire layout is interleaved: along the last axis, code ``b*vpb + j``
lands in byte ``b``, bit slot ``j`` (``vpb = 8 / bits`` values per byte).
It is local — a run of codes packs to the same bytes wherever it sits in a
buffer — which is what makes concatenated per-leaf payloads equal the
bucketed payload bit for bit, at exactly ``bits/8`` bytes per parameter.

Written with shifts and a ``(..., n/vpb, vpb)`` reshape, that layout does
not suit the TPU: Mosaic refuses the lane-splitting shape cast inside a
kernel, and XLA pads the minor ``vpb`` axis to 128 lanes (16x the memory
at 1 bit).  So the interleave is a matrix product instead, on chunks of
``LANES * vpb`` codes against ``LANES`` bytes:

* pack:   ``bytes = codes @ P`` with ``P[b*vpb + j, b] = 2**(j*bits)``;
* unpack: ``r = bytes @ U`` with ``U[b, b*vpb + j] = 2**-(j*bits)``, then
  ``code = floor(r) mod 2**bits``.

Every operand is a small integer or a power of two, exact in bfloat16, and
each output sums at most ``vpb`` exact products below 256, so both products
are exact with float32 accumulation — on the chip, in interpret mode and
on the CPU.  The same :func:`pack_chunk` / :func:`unpack_chunk` run inside
the Pallas kernels and in the jnp twins (:func:`pack` / :func:`unpack`),
so the two backends produce the same bytes by construction.  The matrices
are at most ``1024 x 128`` bf16 (256 KiB), and the work is ``2 * LANES``
flops per code whatever the width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128
# one bf16 pass is already exact here; pinned so that a process-wide
# ``jax_default_matmul_precision`` cannot ask for more
_EXACT = jax.lax.Precision.DEFAULT


def chunk_elems(bits: int) -> int:
    """Codes per packing chunk: one 128-lane row of packed bytes."""
    return LANES * (8 // bits)


@functools.lru_cache(maxsize=None)
def _pack_np(bits: int) -> np.ndarray:
    vpb = 8 // bits
    i = np.arange(LANES * vpb)
    m = np.zeros((LANES * vpb, LANES), np.float32)
    m[i, i // vpb] = 2.0 ** ((i % vpb) * bits)
    return m


def pack_matrix(bits: int) -> jax.Array:
    """``(LANES*vpb, LANES)`` bf16 matrix taking codes to packed bytes."""
    return jnp.asarray(_pack_np(bits), jnp.bfloat16)


def unpack_matrix(bits: int) -> jax.Array:
    """``(LANES, LANES*vpb)`` bf16 matrix taking bytes to shifted codes."""
    m = _pack_np(bits).T.copy()
    m[m != 0] = 1.0 / m[m != 0]
    return jnp.asarray(m, jnp.bfloat16)


def pack_chunk(codes: jax.Array, pmat: jax.Array) -> jax.Array:
    """Integral codes ``(..., LANES*vpb)`` -> int32 bytes ``(..., LANES)``."""
    b = jnp.matmul(codes.astype(jnp.bfloat16), pmat, precision=_EXACT,
                   preferred_element_type=jnp.float32)
    return b.astype(jnp.int32)


def unpack_chunk(p: jax.Array, umat: jax.Array, bits: int) -> jax.Array:
    """uint8 bytes ``(..., LANES)`` -> f32 codes ``(..., LANES*vpb)``."""
    pf = p.astype(jnp.int32).astype(jnp.bfloat16)     # <= 255: exact in bf16
    r = jnp.matmul(pf, umat, precision=_EXACT,
                   preferred_element_type=jnp.float32)
    fl = jnp.floor(r)
    levels = float(2 ** bits)
    return fl - levels * jnp.floor(fl * (1.0 / levels))


def _chunked(a: jax.Array, width: int):
    """Zero-pad the last axis to a multiple of ``width`` and split it into
    ``(..., chunks, width)``; returns the view and the unpadded length."""
    n = a.shape[-1]
    pad = (-n) % width
    if pad:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    return a.reshape(*a.shape[:-1], -1, width), n


def pack(codes: jax.Array, bits: int) -> jax.Array:
    """Pack integer codes (< 2**bits) into uint8 along the last axis,
    zero-padding it up to a multiple of ``vpb``."""
    if bits == 8:
        return codes.astype(jnp.uint8)
    vpb = 8 // bits
    c, n = _chunked(codes, chunk_elems(bits))
    b = pack_chunk(c, pack_matrix(bits))
    return b.reshape(*b.shape[:-2], -1)[..., :-(-n // vpb)].astype(jnp.uint8)


def unpack(packed: jax.Array, bits: int, n: int) -> jax.Array:
    """Inverse of :func:`pack`: the first ``n`` f32 codes of the last axis."""
    if bits == 8:
        return packed[..., :n].astype(jnp.int32).astype(jnp.float32)
    p, _ = _chunked(packed, LANES)
    c = unpack_chunk(p, unpack_matrix(bits), bits)
    return c.reshape(*c.shape[:-2], -1)[..., :n]
