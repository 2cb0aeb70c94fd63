"""Pallas TPU kernel: fused Moniqua decode-reduce (one gossip round's mixing).

Receiver side of Algorithm 1 lines 4-6, fused across *all* neighbors.  Given
the worker's own packed payload, its neighbors' packed payloads (already
circulated by the quantized collective-permute, one operand each), and the
local model tile ``y``, produce in one VMEM pass

    out = y + sum_s  w_s * (x_hat_s - x_hat_self)

where (per element, all f32 in VREGs)

    q_s        = dequant(unpack(p_s)) * B
    x_hat_s    = (q_s - y) mod B + y          (line 5, Lemma 1 recovery)
    x_hat_self = q_self - (y mod B) + y       (line 4, bias cancellation)

HBM traffic per tile: ``(m+1) * bits/8`` bytes of packed payloads + one read
of ``y`` + one write of the mixed tile.  The unfused path (see
``comm/gossip.py::moniqua_gossip``) materialises a *full f32 model copy per
neighbor* — ``m`` extra HBM writes + reads of ``4`` bytes/elem each — so for
a ring (m=2) at 1 bit the fused kernel moves ~8/25 of the unfused bytes, and
the advantage grows with neighbor count (docs/kernels.md derives the model).

The neighbor weights are *compile-time constants* (they come from the static
``Topology``), so the reduction fully unrolls with no weight operand; only
``B`` (a function of the traced theta schedule) is a runtime scalar.

Bit-exactness contract: ``decode_reduce_values`` (after :func:`dequant`) is
the single source of the per-element math for BOTH the kernel body and the
pure-jnp backend (``ops.moniqua_decode_reduce_jnp``), and both unpack with
the same ``core/packing.py`` chunk product.  Every *inexact* multiply is routed
through ``_shield`` — ``where(v == v, v, 0)``, a per-element NaN check no
optimizer can fold — because LLVM's FMA contraction otherwise fuses the
multiply with a downstream add/sub *through* HLO ``optimization_barrier``s
(barriers are dropped before codegen), and does so differently depending on
the surrounding fusion, leaving the two backends 1 ulp apart.  A select
between the mul and the add breaks the contractible adjacency at the
instruction level; a loop-invariant condition would be undone by loop
unswitching, hence the per-element form.  Exact multiplies (power-of-two
scalings) need no shield: contracting them is rounding-free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import packing

DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_COLS = 1024


def _shield(v: jax.Array) -> jax.Array:
    """Identity for finite v that no compiler pass can see through (see above)."""
    return jnp.where(v == v, v, jnp.zeros_like(v))


def dequant(codes: jax.Array, bits: int, B) -> jax.Array:
    """Integral f32 codes -> transmitted values ``q * B``."""
    # /levels is a power of two (exact); the *B product is not — shield it
    return _shield(((codes + 0.5) / 2 ** bits - 0.5) * B)


def unpack_values(p: jax.Array, bits: int, B) -> jax.Array:
    """packed uint8 array -> dequantized f32 values scaled by B (q * B)."""
    return dequant(packing.unpack(p, bits, p.shape[-1] * (8 // bits)),
                   bits, B)


def tile_values(p: jax.Array, bits: int, B, umat=None) -> jax.Array:
    """In-kernel :func:`unpack_values` of one chunk of ``packing.LANES``
    packed bytes (``umat`` = ``packing.unpack_matrix``, unused at 8 bits)."""
    if bits == 8:
        codes = p.astype(jnp.int32).astype(jnp.float32)
    else:
        codes = packing.unpack_chunk(p, umat, bits)
    return dequant(codes, bits, B)


def chunk_loop(bits: int, cols: int):
    """Static ``(code columns, byte columns)`` slices of one tile row: one
    chunk of ``packing.LANES`` packed bytes each."""
    w = packing.chunk_elems(bits)
    return [(slice(k * w, (k + 1) * w),
             slice(k * packing.LANES, (k + 1) * packing.LANES))
            for k in range(cols // w)]


def decode_reduce_values(qb_self: jax.Array, qb_nbrs, y: jax.Array, B,
                         weights) -> jax.Array:
    """Algorithm 1 lines 4-6 on dequantized payload values (shared math)."""
    y = y.astype(jnp.float32)
    ymod = y - _shield(B * jnp.floor(y / B + 0.5))      # cmod(y, B)
    xhat_self = qb_self - ymod + y                      # line 4
    acc = jnp.zeros_like(y)
    for qb, w in zip(qb_nbrs, weights):                 # static unroll over m
        d = qb - y
        xhat = (d - _shield(B * jnp.floor(d / B + 0.5))) + y    # line 5
        acc = acc + _shield(jnp.float32(w) * (xhat - xhat_self))
    return y + acc                                      # line 6


def alias_band_mask(qb: jax.Array, y: jax.Array, B, theta) -> jax.Array:
    """Modulo alias sentinel on one dequantized neighbor payload.

    The Lemma-1 recovered neighbor difference is ``dhat = cmod(qb - y, B)``
    (line 5 above, before adding ``y`` back).  Under the lemma's hypothesis
    ``|x_j - x_i| < theta`` the decode never wraps and
    ``|dhat| <= |x_j - x_i| + delta*B < theta + delta*B = B/2``, so the
    outer band ``|dhat| >= theta`` is unreachable except when the true
    distance is already within ``delta*B`` of the bound.  A nonzero count
    therefore means the theta budget is exhausted or violated.

    Detection semantics (aliasing is per-element undetectable from the
    payload alone — that is what aliasing *means* — so this is the
    strongest payload-only test): an element with true distance ``d``
    fires iff ``d mod B`` lands in the width-``2*delta*B`` window
    ``[theta, B - theta]`` straddling the wrap point ``B/2``.  Distances
    *crossing* the bound transit the window deterministically; a gross,
    already-wrapped violation (``d`` pseudo-uniform mod B across elements)
    fires with per-element rate ``~2*delta`` per neighbor — e.g. 1/128 at
    8 bits, 1/2 at 2 bits — so over a model's worth of elements any
    sustained violation produces counts in the thousands per round while
    a safe run stays at exactly zero.  Computable from payload + local
    reference only, i.e. from what a receiver has on real hardware
    (telemetry: see ``repro.obs.metrics.moniqua_alias_count``; pure-jnp
    twin of the recovered difference: ``ref.recovered_diff_ref``).

    Observational only — shares ``unpack_values`` with the kernel math but
    feeds nothing back into the mix, so telemetry on/off is bit-exact.
    """
    d = qb - y.astype(jnp.float32)
    dhat = d - B * jnp.floor(d / B + 0.5)               # cmod(d, B)
    return jnp.abs(dhat) >= jnp.asarray(theta, jnp.float32)


def _decode_reduce_kernel(ps_ref, *refs, bits: int, weights: tuple):
    """``refs`` is the ``m = len(weights)`` neighbor payload refs, then
    ``y_ref``, ``b_ref``, ``unpack_matrix_ref`` (below 8 bits only) and
    ``o_ref``.  Works one 128-byte chunk of the payloads at a time, which
    also bounds the f32 temporaries to one chunk per neighbor."""
    m = len(weights)
    pn_refs, (y_ref, b_ref), rest = refs[:m], refs[m:m + 2], refs[m + 2:]
    o_ref = rest[-1]
    umat = rest[0][...] if len(rest) == 2 else None
    B = b_ref[0]
    for cs, ps in chunk_loop(bits, y_ref.shape[1]):
        qb_self = tile_values(ps_ref[:, ps], bits, B, umat)
        qb_nbrs = [tile_values(r[:, ps], bits, B, umat) for r in pn_refs]
        out = decode_reduce_values(qb_self, qb_nbrs, y_ref[:, cs], B, weights)
        o_ref[:, cs] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "weights", "block_rows",
                                             "block_cols", "interpret"))
def decode_reduce(p_self: jax.Array, p_nbrs, y: jax.Array,
                  B: jax.Array, *, bits: int, weights: tuple,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  block_cols: int = DEFAULT_BLOCK_COLS,
                  interpret: bool = False) -> jax.Array:
    """Fused mix of ``m = len(weights)`` neighbor payloads into local ``y``.

    Shapes: ``y`` (rows, cols) or (workers, rows, cols), with
    ``cols % block_cols == 0`` (a last row block may be ragged);
    ``p_self`` and each of the ``m`` arrays of ``p_nbrs`` (neighbor s in
    topology offset order, separate operands) are ``y``'s shape with
    ``cols * bits / 8`` byte columns.
    """
    lead = y.shape[:-2]
    y3 = y if y.ndim == 3 else y[None]
    n, rows, cols = y3.shape
    vpb = 8 // bits
    m = len(weights)
    p_nbrs = tuple(p_nbrs)
    want = lead + (rows, cols // vpb)
    if len(p_nbrs) != m or any(p.shape != want
                               for p in (p_self,) + p_nbrs):
        raise ValueError(f"payloads {[p.shape for p in (p_self,) + p_nbrs]}"
                         f" != 1 + {m} of {want}")
    if cols % block_cols:
        raise ValueError(f"shape {y.shape} not tiled by "
                         f"({block_rows},{block_cols}); pad in ops.py")
    grid = (n, pl.cdiv(rows, block_rows), cols // block_cols)
    kernel = functools.partial(_decode_reduce_kernel, bits=bits,
                               weights=tuple(weights))
    p_spec = pl.BlockSpec((None, block_rows, block_cols // vpb),
                          lambda w, i, j: (w, i, j))
    in_specs = [p_spec] * (m + 1) + [
        pl.BlockSpec((None, block_rows, block_cols),
                     lambda w, i, j: (w, i, j)),
        pl.BlockSpec((1,), lambda w, i, j: (0,)),
    ]
    args = [p.reshape(n, rows, cols // vpb) for p in (p_self,) + p_nbrs]
    args += [y3, jnp.asarray(B, jnp.float32).reshape(1)]
    if vpb > 1:
        umat = packing.unpack_matrix(bits)
        in_specs.append(pl.BlockSpec(umat.shape, lambda w, i, j: (0, 0)))
        args.append(umat)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, block_rows, block_cols),
                               lambda w, i, j: (w, i, j)),
        out_shape=jax.ShapeDtypeStruct((n, rows, cols), y.dtype),
        interpret=interpret,
    )(*args)
    return out.reshape(y.shape)
