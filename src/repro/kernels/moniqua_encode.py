"""Pallas TPU kernel: fused Moniqua encode (rescale → mod → round → bit-pack).

The codec is the per-parameter hot loop of the paper's system: every gossip
round touches every parameter once on the send side.  Unfused, XLA would
materialise the f32 residue, the uint8 codes and the packed bytes as separate
HBM round-trips (3 reads + 3 writes per element); the kernel does one HBM read
(x tile → VMEM) and one HBM write (packed tile), with all arithmetic in VMEM /
VREGs — the encode becomes strictly HBM-bandwidth-bound at ``(2 + bits/8)/4``
of the cost of a f32 copy.

TPU adaptation notes (vs a CUDA bit-twiddling port):
  * tiles are (block_rows × block_cols) with block_cols a multiple of
    128·values_per_byte so the *packed* output tile keeps the 128-lane layout;
  * the interleaving pack runs on the MXU, one 128-lane chunk of packed
    bytes at a time (``core/packing.py``): Mosaic has no lane shuffle
    for it;
  * every float <-> integer cast goes through int32 (Mosaic has no
    float <-> uint32 conversion);
  * stochastic rounding uses a counter-based murmur3 hash of the global
    element index (shared randomness across workers, Supp. C) instead of a
    stateful PRNG, so grid blocks are independent and replayable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import packing
# the shared counter-based hash: kernel and every jnp path must draw the
# same uniform per (seed, element) or bit-exactness breaks
from repro.core.quantizers import _counter_uniform as _hash_uniform

DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_COLS = 1024  # multiple of 128 * max vpb (8)


def _encode_kernel(x_ref, seed_ref, b_ref, *refs, bits: int,
                   stochastic: bool, ncols: int):
    """One (rows, cols) tile -> (rows, cols/vpb) packed tile.

    ``refs`` is ``(pack_matrix_ref, o_ref)`` below 8 bits, else ``(o_ref,)``.
    The grid is ``(workers, row blocks, column blocks)``; the worker
    (program id 0) never enters the element index.

    ``seed_ref`` carries two replicated uint32 scalars: the hash seed and
    ``idx_base``, the flat-index offset of this array inside a larger
    bucketed layout (0 for a standalone encode).  Offsetting the counter
    index — rather than perturbing the seed — is what lets a per-leaf
    encode draw the *same* uniform per element as the one-shot encode of
    the whole flat bucket (``comm/bucket.py``).
    """
    levels = 2 ** bits
    vpb = 8 // bits
    rows, cols = x_ref.shape
    i = pl.program_id(1)
    j = pl.program_id(2)

    x = x_ref[...].astype(jnp.float32)
    B = b_ref[0]
    inv_b = 1.0 / B
    r = x * inv_b
    r = r - jnp.floor(r + 0.5)                     # (x/B) mod 1 in [-1/2, 1/2)
    lat = (r + 0.5) * levels - 0.5

    if stochastic:
        # global flat element index (row-major over one worker's array)
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
        col_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
        g_rows = (row_ids + i * rows).astype(jnp.uint32)
        g_cols = (col_ids + j * cols).astype(jnp.uint32)
        idx = seed_ref[1] + g_rows * jnp.uint32(ncols) + g_cols
        u = _hash_uniform(seed_ref[0], idx)
        c = jnp.floor(lat + u)
    else:
        c = jnp.floor(lat + 0.5)
    c = jnp.clip(c, 0, levels - 1)                 # integral f32 codes

    if vpb == 1:
        refs[-1][...] = c.astype(jnp.int32).astype(jnp.uint8)
        return
    # pack: code at column (b*vpb + s) lands in byte b, bit-slot s
    pmat_ref, o_ref = refs
    w = packing.chunk_elems(bits)
    for k in range(cols // w):
        packed = packing.pack_chunk(c[:, k * w:(k + 1) * w], pmat_ref[...])
        o_ref[:, k * packing.LANES:(k + 1) * packing.LANES] = (
            packed.astype(jnp.uint8))


@functools.partial(jax.jit, static_argnames=("bits", "stochastic", "block_rows",
                                             "block_cols", "interpret"))
def encode(x: jax.Array, B: jax.Array, seed: jax.Array, *, bits: int,
           stochastic: bool = True,
           block_rows: int = DEFAULT_BLOCK_ROWS,
           block_cols: int = DEFAULT_BLOCK_COLS,
           interpret: bool = False,
           idx_base: jax.Array | int = 0) -> jax.Array:
    """Encode ``x`` of shape (rows, cols) or (workers, rows, cols), with
    ``cols % block_cols == 0``; a last row block may be ragged.

    Returns packed uint8 of shape (..., rows, cols * bits / 8).  Each
    worker's (rows, cols) array hashes the same element indices, offset by
    ``idx_base`` (see ``_encode_kernel``).
    """
    x3 = x if x.ndim == 3 else x[None]
    n, rows, cols = x3.shape
    if cols % block_cols:
        raise ValueError(f"shape {x.shape} not tiled by "
                         f"({block_rows},{block_cols}); pad in ops.py")
    vpb = 8 // bits
    grid = (n, pl.cdiv(rows, block_rows), cols // block_cols)
    kernel = functools.partial(_encode_kernel, bits=bits,
                               stochastic=stochastic, ncols=cols)
    seed_base = jnp.stack([jnp.asarray(seed, jnp.uint32).reshape(()),
                           jnp.asarray(idx_base, jnp.uint32).reshape(())])
    in_specs = [
        pl.BlockSpec((None, block_rows, block_cols),
                     lambda w, i, j: (w, i, j)),
        pl.BlockSpec((2,), lambda w, i, j: (0,)),   # [seed, idx_base]
        pl.BlockSpec((1,), lambda w, i, j: (0,)),   # B    (replicated)
    ]
    args = [x3, seed_base, jnp.asarray(B, jnp.float32).reshape(1)]
    if vpb > 1:
        # constant block index: fetched into VMEM once for the whole grid
        pmat = packing.pack_matrix(bits)
        in_specs.append(pl.BlockSpec(pmat.shape, lambda w, i, j: (0, 0)))
        args.append(pmat)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, block_rows, block_cols // vpb),
                               lambda w, i, j: (w, i, j)),
        out_shape=jax.ShapeDtypeStruct((n, rows, cols // vpb), jnp.uint8),
        interpret=interpret,
    )(*args)
    return out if x.ndim == 3 else out[0]
